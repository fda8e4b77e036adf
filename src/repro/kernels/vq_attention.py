"""Pallas TPU kernel: VQ-Attention decode step (codebook + exact window).

The paper's approximated message passing (Eq. 6) applied to a decoder LM's
attention: at decode step t the query attends to
  * k codeword (key, value) pairs summarizing all tokens older than the
    window, weighted by cluster mass (the ``C~_out X~`` term), and
  * w exact recent (key, value) pairs (the ``C_in X_B`` term),
in one fused streaming softmax.  Per-step cost O(k + w) instead of O(t) --
this is what makes the ``long_500k`` cells sub-quadratic for dense archs.

Grid is (batch * kv_heads,); each step handles the g = h_q / h_kv query heads
of one GQA group.  Codebook tiles [kcb, d], window tiles [w, d], both padded
to lane width; cluster mass enters as a log-additive bias (row-normalization
handled exactly, paper App. E).  VMEM envelope: (g + kcb + 2w) * d floats --
tiny (decode is memory-bound; this kernel's purpose is to shrink the KV
stream from t*d to (k + w)*d bytes per step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _vq_attn_kernel(q_ref, cbk_ref, cbv_ref, mass_ref, wk_ref, wv_ref,
                    wmask_ref, o_ref, *, sm_scale: float):
    g, d = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * sm_scale

    cbk = cbk_ref[...].astype(jnp.float32)                 # [kcb, d]
    mass = mass_ref[...][:, 0]                             # [kcb]
    s_cb = jax.lax.dot_general(
        q, cbk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [g, kcb]
    s_cb = s_cb + jnp.log(jnp.maximum(mass, 1e-9))[None, :]
    s_cb = jnp.where(mass[None, :] > 0, s_cb, _NEG_INF)

    wk = wk_ref[...].astype(jnp.float32)                   # [w, d]
    wmask = wmask_ref[...][:, 0]                           # [w]
    s_w = jax.lax.dot_general(
        q, wk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [g, w]
    s_w = jnp.where(wmask[None, :] > 0, s_w, _NEG_INF)

    m = jnp.maximum(jnp.max(s_cb, axis=1), jnp.max(s_w, axis=1))  # [g]
    p_cb = jnp.exp(s_cb - m[:, None])
    p_w = jnp.exp(s_w - m[:, None])
    denom = jnp.sum(p_cb, axis=1) + jnp.sum(p_w, axis=1)
    acc = jax.lax.dot(p_cb, cbv_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32) \
        + jax.lax.dot(p_w, wv_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    o_ref[...] = (acc / jnp.maximum(denom, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vq_attention_decode_pallas(q: jax.Array, cb_k: jax.Array, cb_v: jax.Array,
                               mass: jax.Array, win_k: jax.Array,
                               win_v: jax.Array, win_mask: jax.Array, *,
                               interpret: bool = False) -> jax.Array:
    """Batched VQ-Attention decode.

    q:        [n, g, d]   n = batch*kv_heads GQA groups, g q-heads per group
    cb_k/v:   [n, k, d]
    mass:     [n, k]
    win_k/v:  [n, w, d]
    win_mask: [n, w]
    -> [n, g, d]
    """
    n, g, d = q.shape
    kcb = cb_k.shape[1]
    w = win_k.shape[1]
    sm_scale = 1.0 / (d ** 0.5)

    out = pl.pallas_call(
        functools.partial(_vq_attn_kernel, sm_scale=sm_scale),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((None, g, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, kcb, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, kcb, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, kcb, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, w, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, w, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, w, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, g, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, g, d), q.dtype),
        interpret=interpret,
    )(q, cb_k, cb_v, mass[..., None], win_k, win_v, win_mask[..., None])
    return out
