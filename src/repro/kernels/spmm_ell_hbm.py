"""Pallas TPU kernel: ELLPACK SpMM with an HBM-resident source matrix.

Production variant of ``spmm_ell`` for ``n_src * f`` beyond the VMEM
budget (DESIGN.md section 3, resident vs HBM): the dense source matrix
``x`` stays in ``memory_space=ANY`` (HBM on a real TPU) and the kernel
gathers exactly the rows its tile needs, one row DMA per neighbor slot.

The tile's neighbor ids ride in SMEM and address the DMAs: slot ``d`` of
row ``r`` copies ``x[ids[r, d]]`` into row ``r`` of the VMEM buffer
``buf[d]``.  Every slot is copied, padding included (padding ids point at
valid rows and carry val == 0), so the buffer never holds uninitialised
data.  Each slot has its own DMA semaphore, so the weighted accumulate of
slot ``d`` starts as soon as that slot's ``bb`` rows have landed, while the
later slots' copies are still in flight.  HBM traffic is ``b * D * f``
elements -- the gather itself, independent of ``n_src`` and of any index
locality.

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


def _spmm_ell_hbm_kernel(idx_ref, val_ref, x_ref, *refs, deg: int,
                         scaled: bool):
    # refs is ([sc_ref,] o_ref, buf, sems)
    refs = list(refs)
    sc_ref = refs.pop(0) if scaled else None
    o_ref, buf, sems = refs
    bb, f = o_ref.shape

    def row_copy(r, d, src_row):
        return pltpu.make_async_copy(x_ref.at[pl.ds(src_row, 1)],
                                     buf.at[d, pl.ds(r, 1)], sems.at[d])

    def start_slot(d, c):
        def start_row(r, c2):
            row_copy(r, d, idx_ref[r, d]).start()
            return c2
        return jax.lax.fori_loop(0, bb, start_row, c)

    jax.lax.fori_loop(0, deg, start_slot, 0)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (bb, deg), 1)
    vals = val_ref[...]

    def accumulate(d, acc):
        def wait_row(r, c):
            row_copy(r, d, 0).wait()
            return c
        jax.lax.fori_loop(0, bb, wait_row, 0)
        w = jnp.sum(jnp.where(lanes == d, vals, 0.0), axis=1,
                    keepdims=True)                             # [bb, 1]
        return acc + w * buf[d]

    acc = jax.lax.fori_loop(0, deg, accumulate,
                            jnp.zeros((bb, f), jnp.float32))
    if sc_ref is not None:
        acc = acc * sc_ref[...]
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def spmm_ell_hbm_pallas(nbr_idx: jax.Array, nbr_val: jax.Array,
                        x: jax.Array, *, x_scale: jax.Array | None = None,
                        bb: int = 128, interpret: bool = False) -> jax.Array:
    """nbr_idx/[b, D] int32, nbr_val/[b, D], x/[n_src, f] -> [b, f] f32.

    Same contract as ``spmm_ell_pallas`` (padding slots carry val == 0),
    but ``x`` lives in ``memory_space=ANY`` and only the tile's gathered
    ``[D, bb, f]`` rows are ever resident in VMEM.

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
    idx_p = jnp.zeros((bp, deg), jnp.int32).at[:b].set(
        nbr_idx.astype(jnp.int32))
    val_p = jnp.zeros((bp, deg), jnp.float32).at[:b].set(
        nbr_val.astype(jnp.float32))

    in_specs = [
        pl.BlockSpec((bb, deg), lambda i: (i, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((bb, deg), lambda i: (i, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    x_p = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, fp - f)))
    operands = [idx_p, val_p, x_p]
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
                        pltpu.SemaphoreType.DMA((deg,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)
    return out[:b, :f]
