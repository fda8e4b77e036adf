"""Pallas TPU kernel: ELLPACK SpMM with an HBM-resident source matrix.

Production variant of ``spmm_ell`` for ``n_src * f`` beyond the VMEM
budget (DESIGN.md section 3, resident vs HBM): the dense source matrix
``x`` stays in ``memory_space=ANY`` (HBM on a real TPU) and the kernel
gathers exactly the rows its tile needs, one row DMA per live neighbor
slot.

A slot is live when its value is non-zero.  Dead slots -- padding, and in
the in-batch term every neighbor outside the batch -- would add ``0 * x``,
so they issue no copy.  Ahead of the kernel, in XLA, ``compact_slots``
moves each row's live slots to the front in their original order and
``live_slots`` counts them; the counts ride in SMEM beside the ids.  Row
``r`` copies ``x[ids[r, d]]`` into row ``r`` of the VMEM buffer ``buf[d]``
for its slots ``d < count[r]`` only, each slot on its own DMA semaphore,
and the accumulate of slot ``d`` waits on exactly the copies started for
it.  The accumulate runs as far as the tile's largest count; a row whose
slot ``d`` is dead selects 0 in place of ``buf[d]``, whose row it never
copied.  The live products are added in the same order as an accumulate
over every slot, and the dead ones added ``+0`` there, so the output is
the same to the bit.  HBM traffic is ``sum(count) * f`` elements -- the
live gather itself, independent of ``n_src`` and of any index locality.

Row DMAs move whole 32-bit lane rows: an int8/fp8 source is widened to f32
ahead of the kernel (exact), and a width that is not a multiple of 128 is
zero-padded; the per-channel scale applies once in the epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmm_ell import rup


def live_slots(nbr_val: jax.Array) -> jax.Array:
    """[b, D] values -> [b] int32: each row's live (non-zero) slots, which
    is the number of row DMAs the kernel issues for it."""
    return jnp.sum(nbr_val != 0, axis=1, dtype=jnp.int32)


def compact_slots(nbr_idx: jax.Array, nbr_val: jax.Array):
    """Each row's live slots first, in their original order; the slots
    after them carry id 0 and value 0.  -> (ids, values, live_slots).

    Built from a ``[b, D, D]`` position match reduced over the source
    slot (one live slot per target at most, so the sums are exact), not a
    scatter."""
    live = nbr_val != 0
    pos = jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1
    target = jnp.arange(nbr_val.shape[1], dtype=jnp.int32)
    match = live[:, :, None] & (pos[:, :, None] == target)    # [b, D, D]
    idx = jnp.sum(jnp.where(match, nbr_idx[:, :, None], 0), axis=1)
    val = jnp.sum(jnp.where(match, nbr_val[:, :, None], 0), axis=1)
    return idx, val, live_slots(nbr_val)


def _spmm_ell_hbm_kernel(cnt_ref, idx_ref, val_ref, x_ref, *refs, deg: int,
                         scaled: bool):
    # refs is ([sc_ref,] o_ref, buf, sems, hist)
    refs = list(refs)
    sc_ref = refs.pop(0) if scaled else None
    o_ref, buf, sems, hist = refs
    bb, f = o_ref.shape

    def row_copy(r, d, src_row):
        return pltpu.make_async_copy(x_ref.at[pl.ds(src_row, 1)],
                                     buf.at[d, pl.ds(r, 1)], sems.at[d])

    # hist[c]: rows of the tile with c live slots.
    def clear(c, carry):
        hist[c] = 0
        return carry
    jax.lax.fori_loop(0, deg + 1, clear, 0)

    def start_row(r, top):
        n = cnt_ref[r, 0]

        def start_slot(d, c):
            row_copy(r, d, idx_ref[r, d]).start()
            return c
        jax.lax.fori_loop(0, n, start_slot, 0)
        hist[n] = hist[n] + 1
        return jnp.maximum(top, n)

    top = jax.lax.fori_loop(0, bb, start_row, jnp.int32(0))

    lanes = jax.lax.broadcasted_iota(jnp.int32, (bb, deg), 1)
    vals = val_ref[...]

    def accumulate(d, carry):
        acc, started = carry
        started = started - hist[d]             # rows with a live slot d

        def wait_row(_, c):
            row_copy(0, d, 0).wait()
            return c
        jax.lax.fori_loop(0, started, wait_row, 0)
        w = jnp.sum(jnp.where(lanes == d, vals, 0.0), axis=1,
                    keepdims=True)                             # [bb, 1]
        return acc + w * jnp.where(w != 0, buf[d], 0.0), started

    acc, _ = jax.lax.fori_loop(
        0, top, accumulate, (jnp.zeros((bb, f), jnp.float32), jnp.int32(bb)))
    if sc_ref is not None:
        acc = acc * sc_ref[...]
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def spmm_ell_hbm_pallas(nbr_idx: jax.Array, nbr_val: jax.Array,
                        x: jax.Array, *, x_scale: jax.Array | None = None,
                        bb: int = 128, interpret: bool = False) -> jax.Array:
    """nbr_idx/[b, D] int32, nbr_val/[b, D], x/[n_src, f] -> [b, f] f32.

    Same contract as ``spmm_ell_pallas`` (padding slots carry val == 0),
    but ``x`` lives in ``memory_space=ANY``, only the tile's gathered
    ``[D, bb, f]`` rows are ever resident in VMEM, and only slots with a
    non-zero value are gathered.

    ``x_scale`` ([1, f] or [f] per-channel dequant scales) marks ``x`` as
    int8/fp8 rows: they are widened before the row DMAs, the accumulate
    stays f32, and the scales apply once in the epilogue.
    """
    b, deg = nbr_idx.shape
    n_src, f = x.shape
    if deg == 0:
        return jnp.zeros((b, f), jnp.float32)
    bb = min(bb, rup(b, 8))
    bp = rup(b, bb)
    fp = rup(f, 128)
    with jax.named_scope("spmm_compact"):
        idx_c, val_c, cnt = compact_slots(nbr_idx.astype(jnp.int32),
                                          nbr_val.astype(jnp.float32))
        rows = ((0, bp - b), (0, 0))
        idx_c, val_c = jnp.pad(idx_c, rows), jnp.pad(val_c, rows)
        cnt = jnp.pad(cnt[:, None], rows)

    in_specs = [
        pl.BlockSpec((bb, 1), lambda i: (i, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((bb, deg), lambda i: (i, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((bb, deg), lambda i: (i, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    x_p = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, fp - f)))
    operands = [cnt, idx_c, val_c, x_p]
    if x_scale is not None:
        in_specs.append(pl.BlockSpec((1, fp), lambda i: (0, 0)))
        operands.append(jnp.pad(x_scale.astype(jnp.float32).reshape(1, f),
                                ((0, 0), (0, fp - f))))
    out = pl.pallas_call(
        functools.partial(_spmm_ell_hbm_kernel, deg=deg,
                          scaled=x_scale is not None),
        grid=(bp // bb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, fp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, fp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((deg, bb, fp), jnp.float32),
                        pltpu.SemaphoreType.DMA((deg,)),
                        pltpu.SMEM((deg + 1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)
    return out[:b, :f]
