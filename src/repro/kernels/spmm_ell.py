"""Pallas TPU kernel: ELLPACK SpMM (padded-neighbor message passing).

The intra-mini-batch term ``C_in X_B`` and the cluster-bucketing of
out-of-batch neighbors are segment sums over padded neighbor lists.  GPU
implementations use CSR SpMM with atomics; the TPU-native formulation is a
regular ELLPACK layout: every row has exactly D (padded) neighbor slots, so
the access pattern is a rank-1 gather + weighted accumulate with no dynamic
shapes and no atomics (DESIGN.md section 3, hardware adaptation).

This is the resident variant: the source matrix X sits whole in VMEM (the
HBM variant in spmm_ell_hbm.py takes over for n_src * f beyond VMEM -- see
ops.py).  Mosaic cannot gather a VMEM ref with a vector of ids, so the
gather is a one-hot product on the MXU instead.  Everything is transposed
so the batch rows ride the 128 lanes: for each ``CHUNK``-row block of the
source, the D slots build a weighted one-hot block ``A[s, i] = sum_d
val[i, d] * (ids[i, d] == s)`` with VPU compares, and ``X^T[:, block] @ A``
accumulates the tile's ``[f, rows]`` output.  Padding slots carry val == 0
and add nothing.  The work is ``b * n_src * D`` compare-selects, which the
resident budget keeps small (the sources here are mini-batches and
``[k, f_blk]`` codeword tables).

``onehot_ell_sum`` is shared with the fused context kernel
(context_ell.py), which uses it only for wide codeword branches: a narrow
``[k, f_blk]`` codebook is read by lane gathers in registers instead.
Here the source is a mini-batch of 128-wide rows, where a lookup would
cost ``f * n_src / 128`` permutes per slot group.  Quantized sources stay
in storage dtype in VMEM and are widened one block at a time.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Source rows per one-hot block: a [CHUNK, 128] f32 block is 32 vregs.
CHUNK = 256
LANES = 128
# Scoped VMEM for the kernels whose resident blocks (source, codebook) the
# dispatch budget sizes: Pallas double-buffers them, and v5e's 16 MiB
# default would halve the budget.
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def rup(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def lane_tile(b: int, bb: int) -> int:
    """Rows per grid step when rows ride the lanes: ``bb`` rounded up to a
    multiple of 128, and no wider than the padded batch."""
    return min(rup(max(bb, LANES), LANES), rup(max(b, 1), LANES))


def onehot_ell_sum(ids_ref, val_ref, src_ref, row0, deg: int) -> jax.Array:
    """``out[:, i] = sum_d val[d, i] * src[:, ids[row0 + d, i]]`` -> [f, bl].

    ``ids_ref`` [R, bl] int32 and ``val_ref`` [deg, bl] f32 hold a tile's
    slots transposed (rows on lanes); ``src_ref`` is the transposed source
    ``[f, n_pad]`` with ``n_pad`` a multiple of ``CHUNK``, in any dtype.
    """
    f, n_pad = src_ref.shape
    bl = val_ref.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, bl), 0)

    def block(c, acc):
        base = pl.multiple_of(c * CHUNK, CHUNK)

        def slot(d, a):
            ids = ids_ref[pl.ds(row0 + d, 1), :] - base        # [1, bl]
            return a + jnp.where(rows == ids, val_ref[pl.ds(d, 1), :], 0.0)

        a = jax.lax.fori_loop(0, deg, slot,
                              jnp.zeros((CHUNK, bl), jnp.float32))
        src = src_ref[:, pl.ds(base, CHUNK)].astype(jnp.float32)
        return acc + jnp.dot(src, a, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, n_pad // CHUNK, block,
                             jnp.zeros((f, bl), jnp.float32))


def _spmm_ell_kernel(ids_ref, val_ref, x_ref, *refs, deg: int,
                     scaled: bool):
    # refs is ([sc_ref,] o_ref); sc_ref is the [f, 1] dequant column -- ONE
    # per-channel multiply after the sum (the scale is row independent, so
    # it commutes with it)
    o_ref = refs[-1]
    acc = onehot_ell_sum(ids_ref, val_ref, x_ref, 0, deg)
    if scaled:
        acc = acc * refs[0][...]
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def spmm_ell_pallas(nbr_idx: jax.Array, nbr_val: jax.Array, x: jax.Array, *,
                    x_scale: Optional[jax.Array] = None,
                    bb: int = 128, interpret: bool = False) -> jax.Array:
    """nbr_idx/[b, D] int32, nbr_val/[b, D], x/[n_src, f] -> [b, f] f32.

    Padding slots must carry val == 0 (their index may point anywhere valid).
    ``x_scale`` ([1, f] f32) marks ``x`` as int8/fp8 rows with per-channel
    dequant scales, applied as a single epilogue multiply after the f32
    accumulate (DESIGN.md section 13).  ``bb`` rows per grid step, rounded
    up to a multiple of 128 (rows ride the lanes).
    """
    b, deg = nbr_idx.shape
    n_src, f = x.shape
    if deg == 0:
        return jnp.zeros((b, f), jnp.float32)
    bl = lane_tile(b, bb)
    bp = rup(b, bl)
    # transposed [D, bp] slots; padded lanes carry val 0
    ids_t = jnp.zeros((deg, bp), jnp.int32).at[:, :b].set(
        nbr_idx.astype(jnp.int32).T)
    val_t = jnp.zeros((deg, bp), jnp.float32).at[:, :b].set(
        nbr_val.astype(jnp.float32).T)
    n_pad = rup(n_src, CHUNK)
    x_t = jnp.zeros((f, n_pad), x.dtype).at[:, :n_src].set(x.T)

    in_specs = [
        pl.BlockSpec((deg, bl), lambda i: (0, i)),
        pl.BlockSpec((deg, bl), lambda i: (0, i)),
        pl.BlockSpec((f, n_pad), lambda i: (0, 0)),
    ]
    operands = [ids_t, val_t, x_t]
    if x_scale is not None:
        in_specs.append(pl.BlockSpec((f, 1), lambda i: (0, 0)))
        operands.append(x_scale.astype(jnp.float32).reshape(f, 1))
    out = pl.pallas_call(
        functools.partial(_spmm_ell_kernel, deg=deg,
                          scaled=x_scale is not None),
        grid=(bp // bl,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((f, bl), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((f, bp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    return out[:, :b].T
