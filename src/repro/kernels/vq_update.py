"""Pallas TPU kernel: fused VQ assign + cluster statistics (VQ-Update).

The per-layer, per-batch hot loop of Algorithm 2 (streaming EMA codebook
update) needs, for every product-VQ branch: the nearest-codeword assignment
of b whitened rows, the per-codeword member counts, the per-codeword member
sums, and the per-row quantization error (for dead-codeword revival and the
relative-error monitor).  Computing these separately costs a second distance
pass plus a materialized [b, k] one-hot -- the same "gigantic intermediate"
failure mode the HBM SpMM work removed from message passing.

This kernel produces all four in a single (b/bb, k/kb) grid pass:

  * distances reduce to  |c|^2 - 2 x.c^T  (the |x|^2 term is constant per
    row) so the dominant work is an MXU matmul of the [bb, f] x-tile against
    the [kb, f] codeword tile -- identical to vq_assign.py;
  * the running (min, argmin) pair is carried across the sequential k-tiles
    in the revisited per-row output blocks (qerr, idx);
  * at the LAST k-tile of each row tile the argmin is final, so the cluster
    statistics are accumulated right there: a [bb, kp] selection mask
    (computed on the fly from the final indices, never written to HBM)
    reduces to counts via a VPU column sum and to sums via one MXU matmul
    mask^T . x.  The counts/sums outputs use a CONSTANT index map, so Pallas
    keeps them in VMEM as revisited accumulator blocks across the whole grid
    and writes them back exactly once;
  * |x|^2 is added to the carried min at the last k-tile, turning it into
    the true squared quantization error (clamped at 0 against cancellation).

VMEM envelope per step: bb*fp + kb*fp (operand tiles) + bb*kb (distance
tile) + bb*kp (selection mask, last tile only) + kp*fp + kp (stats
accumulators) floats.  Defaults bb=256, kb=512 with the paper-scale k=256,
f_blk=8 (fp=128) keep this well under 2 MiB.  Callers pad: extra k rows get
value 1e15 so they never win the argmin (their counts/sums stay zero); extra
b rows are masked out of the statistics in-kernel and sliced off by the
wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vq_assign import pad_assign_operands, tile_argmin

# Narrow emit dtypes and the largest k each can index: uint8 (the int8/fp8
# tiers' table dtype) and uint4 (the nibble-packed +a4 tiers; SIGNED int4
# tops out at 7 and would wrap ids 8..15, so it is deliberately absent).
# int32 is always valid and carries no limit.
_EMIT_K_LIMITS = {"uint8": 256, "uint4": 16}


def _vq_update_kernel(x_ref, c_ref, cn_ref, idx_ref, qerr_ref, cnt_ref,
                      sum_ref, *, bb: int, kb: int, b: int):
    i = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    x, tile_min, tile_arg = tile_argmin(x_ref, c_ref, cn_ref, kb)

    @pl.when(ki == 0)
    def _init_rows():
        qerr_ref[...] = tile_min
        idx_ref[...] = tile_arg.astype(idx_ref.dtype)

    @pl.when(ki > 0)
    def _combine():
        prev = qerr_ref[...]
        take = tile_min < prev
        qerr_ref[...] = jnp.where(take, tile_min, prev)
        idx_ref[...] = jnp.where(
            take, tile_arg,
            idx_ref[...].astype(jnp.int32)).astype(idx_ref.dtype)

    @pl.when(jnp.logical_and(i == 0, ki == 0))
    def _init_stats():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    @pl.when(ki == nk - 1)
    def _accumulate():
        kp = cnt_ref.shape[1]
        final = idx_ref[...].astype(jnp.int32)            # [bb, 1] post-combine
        rows = i * bb + jax.lax.broadcasted_iota(jnp.int32, (bb, 1), 0)
        valid = rows < b                                  # padded rows: no stats
        cols = jax.lax.broadcasted_iota(jnp.int32, (bb, kp), 1)
        sel = jnp.where(jnp.logical_and(final == cols, valid), 1.0, 0.0)
        cnt_ref[...] += jnp.sum(sel, axis=0, keepdims=True)   # [1, kp]
        sum_ref[...] += jax.lax.dot_general(
            sel, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        xn2 = jnp.sum(x * x, axis=1, keepdims=True)
        qerr_ref[...] = jnp.maximum(qerr_ref[...] + xn2, 0.0)


@functools.partial(jax.jit,
                   static_argnames=("bb", "kb", "interpret", "emit_dtype"))
def vq_assign_update_pallas(
        x: jax.Array, codewords: jax.Array, *,
        bb: int = 256, kb: int = 512, interpret: bool = False,
        emit_dtype=jnp.int32,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused assign + stats.  x: [b, f], codewords: [k, f].

    Returns (assignment [b] ``emit_dtype``, qerr [b] f32, counts [k] f32,
    sums [k, f] f32) where qerr[i] = ||x_i - c_{assignment[i]}||^2 and
    counts/sums are the per-codeword member histogram and member sum --
    exactly the statistics Algorithm 2's EMA update consumes, with no
    one-hot intermediate and no second distance pass.

    ``emit_dtype=jnp.uint8`` (valid for k <= 256) EMITS the assignment in
    the int8 path's storage dtype: with a single k-tile (kp <= 256) the
    kernel's idx output block is uint8 natively -- padded codeword columns
    carry 1e15 distance and never win the argmin, so every emitted index
    is < k.  Multi-k-tile grids carry int32 intermediates in the revisited
    block (tile offsets exceed the narrow range) and narrow in the wrapper.
    ``emit_dtype=jnp.uint4`` (the +a4 tiers, valid for k <= 16) shares the
    native uint8 output block -- Mosaic has no sub-byte output windows --
    and narrows to uint4 in the wrapper; callers nibble-pack from there
    (``distributed.quantization.pack_nibbles``).

    Handles all padding internally via the shared
    :func:`~repro.kernels.vq_assign.pad_assign_operands` (padded codewords
    sit far away -> never selected, zero stats; padded b rows are masked
    out of the stats in-kernel).
    """
    b, f = x.shape
    k = codewords.shape[0]
    emit = jnp.dtype(emit_dtype)
    k_limit = _EMIT_K_LIMITS.get(emit.name)
    if emit != jnp.int32 and k_limit is None:
        raise ValueError(
            f"emit_dtype={emit.name!r} is not a supported assignment "
            f"storage dtype; want jnp.int32 or one of "
            f"{sorted(_EMIT_K_LIMITS)}")
    if emit != jnp.int32 and k > k_limit:
        raise ValueError(
            f"emit_dtype={emit.name!r} supports k <= {k_limit}, got "
            f"k={k}; use emit_dtype=jnp.int32 (always valid)"
            + (" or jnp.uint8 (k <= 256)" if emit == jnp.uint4 else ""))
    xp, cp, cn2, bb, kb, bp, kp, fp = pad_assign_operands(x, codewords,
                                                          bb, kb)
    # sub-byte dtypes ride the uint8 output block; byte-wide emit dtypes go
    # out natively when the grid has a single k-tile
    block_emit = jnp.uint8 if emit == jnp.uint4 else emit
    idx_dtype = block_emit if (emit == jnp.int32 or
                               (kp <= kb and kp <= 256)) else jnp.int32

    grid = (bp // bb, kp // kb)
    idx, qerr, counts, sums = pl.pallas_call(
        functools.partial(_vq_update_kernel, bb=bb, kb=kb, b=b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, fp), lambda i, j: (i, 0)),
            pl.BlockSpec((kb, fp), lambda i, j: (j, 0)),
            pl.BlockSpec((1, kb), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            # constant index maps: revisited VMEM accumulators (module doc)
            pl.BlockSpec((1, kp), lambda i, j: (0, 0)),
            pl.BlockSpec((kp, fp), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), idx_dtype),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, kp), jnp.float32),
            jax.ShapeDtypeStruct((kp, fp), jnp.float32),
        ],
        interpret=interpret,
    )(xp, cp, cn2)
    return (idx[:b, 0].astype(emit), qerr[:b, 0],
            counts[0, :k], sums[:k, :f])
