"""Pallas TPU kernel: block flash attention (training forward).

Used by the LM backbones for the exact-window / intra-block attention term
(the ``C_in`` part of the paper's Eq. 6 on the token graph).  Streaming
softmax with running (max, denom, acc) carried over KV tiles.

Layout decisions for the MXU:
  * q tile [bq, d] with d padded to 128 (lane width), bq = 256 default --
    the two matmuls per step are [bq, d] x [d, bk] and [bq, bk] x [bk, d];
  * KV is scanned in bk = 512 tiles via dynamic slices of the full-sequence
    block; VMEM envelope = (bq + 2 skv) * d floats, which fits the train_4k
    shape (4k * 128 * 4B * 2 = 4 MiB).  For 32k+ sequences the production
    config re-tiles with a 3-axis grid (documented in ops.py); correctness
    here is validated against ref.flash_attention in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *,
                  causal: bool, sm_scale: float, bk: int, seq_kv: int):
    qi = pl.program_id(1)
    bq, d = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * sm_scale

    nk = seq_kv // bk

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        new_m = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - new_m[:, None])
        alpha = jnp.exp(m - new_m)
        new_l = l * alpha + jnp.sum(p, axis=1)
        new_acc = acc * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return new_m, new_l, new_acc

    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        # skip fully-masked kv tiles: row block i only needs kv tiles <= i
        upto = jnp.minimum((qi + 1) * bq + bk - 1, seq_kv) // bk
    else:
        upto = nk
    m, l, acc = jax.lax.fori_loop(0, upto, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 256, bk: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q: [b, h, sq, d], k/v: [b, h, skv, d] -> [b, h, sq, d].

    sq must equal skv when causal (standard training layout).
    """
    b, h, sq, d = q.shape
    skv = k.shape[2]
    sm_scale = 1.0 / (d ** 0.5)
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, skv, d)
    vf = v.reshape(b * h, skv, d)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, sm_scale=sm_scale,
                          bk=bk, seq_kv=skv),
        grid=(b * h, sq // bq),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, skv, d), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, skv, d), lambda g, i: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
