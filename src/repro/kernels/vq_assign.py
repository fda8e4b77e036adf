"""Pallas TPU kernel: fused pairwise-distance + running argmin (VQ assign).

The hot inner loop of VQ-GNN: every mini-batch, every layer, every product-VQ
branch assigns b vectors to their nearest of k codewords.  On GPU this is a
cdist + argmin (two kernels + atomic-free reduction); the TPU formulation is
a single fused kernel:

  * distance reduces to  |c|^2 - 2 x.c^T  (the |x|^2 term is constant per
    row) so the dominant work is an MXU matmul of the [bb, f] x-tile against
    the [kb, f] codeword tile;
  * the argmin over k is carried across k-tiles as a running (min, argmin)
    pair held in the (revisited) output block -- grid is (b/bb, k/kb) with
    the k axis 'arbitrary' (sequential) so revisiting is legal.

VMEM envelope per step: bb*f + kb*f + bb*kb floats.  Defaults bb=256, kb=512,
f padded to a multiple of 128 (lane width) keep this < 1 MiB for f = 128.
Callers pad: extra k rows get value 1e15 so they never win the argmin; extra
b rows are sliced off by the wrapper in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def pad_assign_operands(x: jax.Array, codewords: jax.Array,
                        bb: int, kb: int):
    """Clamp tile sizes and pad operands for the shared assign-grid layout
    (used by vq_assign and the fused vq_update kernel -- one place owns the
    padding invariants): b -> bb multiple, k -> kb multiple, f -> lane-width
    multiple of 128 with zeros (leaves distances unchanged).  Padded
    codeword rows get value 1e15 so they never win the argmin.

    Returns (xp, cp, cn2, bb, kb, bp, kp, fp) with bb/kb clamped to the
    actual problem size (floor 8, the f32 sublane width).  ``cn2`` [1, kp]
    holds the codeword squared norms as a lane-major row: computed in the
    kernel, the [kb] column reduction would need a sublane-to-lane
    relayout that Mosaic materializes at ~100x the tile's VMEM.
    """
    b, f = x.shape
    k = codewords.shape[0]
    bb = min(bb, max(8, b))
    kb = min(kb, max(8, k))

    def rup(v, m):
        return (v + m - 1) // m * m

    bp, kp, fp = rup(b, bb), rup(k, kb), rup(f, 128)
    xp = jnp.zeros((bp, fp), x.dtype).at[:b, :f].set(x)
    cp = jnp.full((kp, fp), 1e15, jnp.float32).at[:k, :f].set(
        codewords.astype(jnp.float32)).at[:k, f:].set(0.0)
    cn2 = jnp.sum(cp * cp, axis=1)[None, :]
    return xp, cp, cn2, bb, kb, bp, kp, fp


def tile_argmin(x_ref, c_ref, cn_ref, kb: int):
    """Distances of the [bb, f] x-tile to the [kb, f] codeword tile and
    their per-row (min, first argmin) over the tile, argmin offset to the
    global codeword id.  The argmin is the smallest column attaining the
    min (jnp.argmin's tie rule), taken with a min-reduction over masked
    column ids."""
    ki = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)                    # [bb, f]
    c = c_ref[...].astype(jnp.float32)                    # [kb, f]
    # MXU: scores[b, k] = x . c^T
    scores = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    dist = cn_ref[...] - 2.0 * scores                     # [bb, kb]
    tile_min = jnp.min(dist, axis=1, keepdims=True)       # [bb, 1]
    cols = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    tile_arg = jnp.min(jnp.where(dist == tile_min, cols, kb), axis=1,
                       keepdims=True) + ki * kb
    return x, tile_min, tile_arg


def _vq_assign_kernel(x_ref, c_ref, cn_ref, val_ref, idx_ref, *, kb: int):
    ki = pl.program_id(1)
    _, tile_min, tile_arg = tile_argmin(x_ref, c_ref, cn_ref, kb)

    @pl.when(ki == 0)
    def _init():
        val_ref[...] = tile_min
        idx_ref[...] = tile_arg

    @pl.when(ki > 0)
    def _combine():
        prev = val_ref[...]
        take = tile_min < prev
        val_ref[...] = jnp.where(take, tile_min, prev)
        idx_ref[...] = jnp.where(take, tile_arg, idx_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("bb", "kb", "interpret", "want_min"))
def vq_assign_pallas(x: jax.Array, codewords: jax.Array, *,
                     bb: int = 256, kb: int = 512,
                     interpret: bool = False, want_min: bool = False):
    """x: [b, f], codewords: [k, f] -> assignment [b] int32.

    With ``want_min=True`` also returns the squared distance to the chosen
    codeword, [b] f32 (the carried running min plus the per-row |x|^2 the
    kernel factors out) -- callers that need the quantization error get it
    without a second distance pass.

    ``interpret`` defaults to False so a bare call on TPU compiles; the
    interpret-mode test/CI sweeps pass it explicitly.

    Handles all padding internally (b -> bb multiple, k -> kb multiple,
    f -> multiple of 128 with zeros, which leaves distances unchanged).
    """
    b, _ = x.shape
    xp, cp, cn2, bb, kb, bp, kp, fp = pad_assign_operands(x, codewords,
                                                          bb, kb)

    grid = (bp // bb, kp // kb)
    val, idx = pl.pallas_call(
        functools.partial(_vq_assign_kernel, kb=kb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, fp), lambda i, j: (i, 0)),
            pl.BlockSpec((kb, fp), lambda i, j: (j, 0)),
            pl.BlockSpec((1, kb), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(xp, cp, cn2)
    if not want_min:
        return idx[:b, 0]
    xn2 = jnp.sum(x.astype(jnp.float32) ** 2, axis=1)
    return idx[:b, 0], jnp.maximum(val[:b, 0] + xn2, 0.0)
