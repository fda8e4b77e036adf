"""Pallas TPU kernel: fused multi-branch VQ-context ELLPACK SpMM.

The out-of-batch ("context") term of Eq. 6 reconstructs each out-of-batch
neighbor from its product-VQ codewords and accumulates the weighted
messages:

    out[i] = sum_d vals[i, d] * concat_beta X~^beta[R^beta[ids[i, d]]]

The paper's scaling argument (Sec. 3.3) is that this term only ever touches
a [k, f_blk] codeword table per branch -- O(k * f) state, independent of
graph size.  The per-branch form (``ops._context_ell_loop``) pays one SpMM
kernel launch per branch plus a concat; this kernel performs the whole
computation in ONE grid pass over row tiles:

  * the per-branch assignment gather ``R^beta[ids]`` runs in XLA ahead of
    the kernel (SMEM cannot hold an [n, n_branches] table at graph scale,
    and Mosaic gathers with a vector of ids only within a vreg) and arrives
    transposed, ``[n_branches * D8, b]`` (D padded to a multiple of 8),
    so batch rows ride the lanes;
  * all branches' codewords live VMEM-resident as lookup tables: one
    128-lane row per (branch, column, group of 128 codewords), k padded
    with zeros to a multiple of 128 -- k * f is tiny by construction, the
    point of VQ;
  * per branch, each vreg of 8 slots (sublanes) x 128 rows (lanes) splits
    its codeword ids as ``128 * hi + lo``; per column and group ``h`` a
    lane gather by ``lo`` (``jnp.take_along_axis``, Mosaic's
    ``tpu.dynamic_gather``, a native lane permute on the chip) reads the
    table row and a select keeps it where ``hi == h``; the products with
    the slot values accumulate in f32 and the 8 sublanes are summed once
    per column into that branch's ``[f_pad, rows]`` slice of the
    branch-concatenated output.  The work is ``D * f * ceil(k / 128) / 8``
    vreg permutes per 128 rows, against the one-hot's ``D * k * nb / 8``
    vreg compare-selects (``spmm_ell.onehot_ell_sum``, the resident
    SpMM's form); no per-branch intermediate leaves VMEM;
  * the lookup's cost grows with the branch's columns and the one-hot's
    does not, so a term of wide branches (``uses_lookup`` false: 44
    columns and up at k = 1024) runs the one-hot MXU product instead, over
    each branch's codebook transposed to ``[f_pad, k_pad]``, in the same
    grid.

The same kernel is the streaming Eq. 7 backward (DESIGN.md section 10):
called with the reverse-edge operands and the *gradient* codewords it
computes ``sum_d rev_vals[:, d] * G~[c(rev_ids[:, d])]``, and the optional
``w_t`` epilogue fuses the trailing ``@ W^T`` (one resident MXU matmul per
row tile), so ``inject_context_grad`` needs no ``[b, Dr, f_grad]``
residual -- the codebook itself is the residual.

Low-precision operands (DESIGN.md sections 13/15): the codeword tables may
be int8 or float8_e4m3fn with a per-branch/per-channel f32 scale
(``cw_scale [nb, 1, f_blk]``,
``distributed.quantization.quantize_codewords``) and the assignment table
may be uint8 (k <= 256) or nibble-packed (``PackedAssignment``, k <= 16,
two ids per byte); the XLA-side gather reads either storage form.  The
codebook enters the kernel in storage dtype and is widened to an f32 copy
once per row tile (the lookup returns each codeword exactly; the one-hot
widens a block at a time); the
accumulate runs in f32, and the dequant multiply is a single epilogue
column ``acc * scale``: scales are k-independent, so the multiply commutes
with the over-neighbors sum and with the fused ``w_t`` MXU epilogue
ordering (scale first, then ``@ W^T``).

Padding contract (shared with spmm_ell): slots with ``vals == 0`` may
point at any valid node id; rows padded to the lane tile, and slots padded
to a multiple of 8, carry zero vals.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.distributed.quantization import PackedAssignment
from repro.kernels.spmm_ell import (CHUNK, LANES, VMEM_LIMIT_BYTES,
                                    lane_tile, onehot_ell_sum, rup)

# Slots per lookup vreg: one slot per sublane.
SUB = 8
# Lookup columns that cost as much as one one-hot pass over the same
# codewords (``uses_lookup``), measured on a v5e at D = 32 over 42,336
# rows: at k = 1024 a lookup column costs about 0.049 ms and a one-hot
# branch 1.8-2.1 ms, which cross between 41 and 48 columns.
LOOKUP_COLS = 44


def uses_lookup(k: int, f_blk: int) -> bool:
    """True where the lane lookup costs less than the one-hot for a branch
    of ``f_blk`` columns over ``k`` codewords.

    The lookup's work grows as ``f_blk * ceil(k / 128)`` lane permutes, the
    one-hot's as ``rup(k, CHUNK)`` compare-selects whatever f_blk (the MXU
    takes the columns); ``LOOKUP_COLS`` is their measured ratio
    (``benchmarks/context_kernel_chip.py``).
    """
    return f_blk * pl.cdiv(k, LANES) * LANES < LOOKUP_COLS * rup(k, CHUNK)


def _context_ell_kernel(aid_ref, val_ref, tab_ref, *refs, nb: int,
                        f_blk: int, n_grp: int, scaled: bool,
                        fused_wt: bool, widen: bool, lookup: bool):
    # refs is ([sc_ref,] [wt_ref,] o_ref, acc_ref[, wide_ref])
    refs = list(refs)
    sc_ref = refs.pop(0) if scaled else None
    wt_ref = refs.pop(0) if fused_wt else None
    o_ref, acc_ref = refs[:2]
    if widen:
        # a quantized codebook enters in storage dtype, widened once per
        # row tile (f_blk * k values; the lookups read the f32 copy)
        refs[2][...] = tab_ref[...].astype(jnp.float32)
        tab_ref = refs[2]
    f_cat, bl = acc_ref.shape
    f_pad = f_cat // nb
    d8 = val_ref.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (f_pad, LANES), 0)

    def onehot_branch(beta, carry):
        # wide branches: the resident SpMM's one-hot MXU product over the
        # branch's [f_pad, k_pad] codebook, widened a block at a time
        part = onehot_ell_sum(aid_ref, val_ref, tab_ref.at[beta],
                              beta * d8, d8)
        acc_ref[pl.ds(pl.multiple_of(beta * f_pad, SUB), f_pad), :] = part
        return carry

    def lookup_branch(beta, carry):
        def lane_chunk(c, carry):
            lanes = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
            ids = aid_ref[pl.ds(pl.multiple_of(beta * d8, SUB), d8), lanes]
            lo, hi = ids & (LANES - 1), ids >> 7      # id = 128 * hi + lo
            vals = val_ref[:, lanes]                  # [d8, 128]

            def column(j, part):
                row0 = (beta * f_blk + j) * n_grp

                def group(h, word):
                    # codewords 128 h .. 128 h + 127 of column j, one
                    # lane permute per vreg of 8 slots
                    tab = jnp.broadcast_to(tab_ref[pl.ds(row0 + h, 1), :],
                                           (d8, LANES))
                    got = jnp.take_along_axis(tab, lo, axis=1,
                                              mode="promise_in_bounds")
                    return jnp.where(hi == h, got, word)

                word = jax.lax.fori_loop(
                    0, n_grp, group, jnp.zeros((d8, LANES), jnp.float32),
                    unroll=True)
                col = jnp.sum(word * vals, axis=0, keepdims=True)
                return jnp.where(rows == j, col, part)

            # unrolled, the columns share the hi == h compares: a fifth
            # faster than the rolled loop at f_blk 4 on a v5e
            part = jax.lax.fori_loop(
                0, f_blk, column, jnp.zeros((f_pad, LANES), jnp.float32),
                unroll=True)
            acc_ref[pl.ds(pl.multiple_of(beta * f_pad, SUB), f_pad),
                    lanes] = part
            return carry

        return jax.lax.fori_loop(0, bl // LANES, lane_chunk, carry)

    jax.lax.fori_loop(0, nb, lookup_branch if lookup else onehot_branch,
                      0)
    tile = acc_ref[...]
    if sc_ref is not None:
        tile = tile * sc_ref[...]         # dequant BEFORE the W^T mix
    if wt_ref is not None:
        # fused epilogue: the Eq. 7 ``@ W^T`` as one resident MXU matmul
        tile = jnp.dot(wt_ref[...], tile,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    o_ref[...] = tile


def vmem_bytes(nb: int, k: int, f_blk: int, deg: int, bl: int = LANES,
               f_out: Optional[int] = None, scaled: bool = False,
               cw_itemsize: int = 4) -> int:
    """VMEM the fused kernel holds for one term: every block and scratch
    buffer, tile-padded, the blocks double-buffered.

    The codebook (lookup tables of ``nb * f_blk * ceil(k / 128)`` rows of
    128 codewords plus their f32 copy when stored narrower, or, where
    ``uses_lookup`` is false, ``nb`` one-hot tables of ``[f_blk, k]``, in
    storage dtype of ``cw_itemsize`` bytes) and the id and value blocks
    dominate; the ``[nb, n]`` assignment table is gathered in XLA and never
    enters VMEM.
    """
    d8 = rup(deg, SUB)
    f_pad = rup(f_blk, SUB)
    f_cat = nb * f_pad

    def tile(r, c, itemsize=4):
        return rup(r, SUB * 4 // itemsize) * rup(c, LANES) * itemsize

    scratch = tile(f_cat, bl)
    if uses_lookup(k, f_blk):
        tab_rows = nb * f_blk * pl.cdiv(k, LANES)
        if cw_itemsize != 4:
            tab_rows = rup(tab_rows, 4 * SUB)     # the wrapper's whole tiles
            scratch += tile(tab_rows, LANES)
        tab = tile(tab_rows, LANES, cw_itemsize)
    else:
        tab = nb * tile(f_pad, rup(k, CHUNK), cw_itemsize)
    blocks = (tab + tile(nb * d8, bl) + tile(d8, bl)
              + tile(f_cat if f_out is None else f_out, bl))
    if scaled:
        blocks += tile(f_cat, 1)
    if f_out is not None:
        blocks += tile(f_out, f_cat)
    return 2 * blocks + scratch


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def context_ell_pallas(out_ids: jax.Array, out_vals: jax.Array,
                       assignment: jax.Array, codewords: jax.Array, *,
                       cw_scale: Optional[jax.Array] = None,
                       w_t: Optional[jax.Array] = None,
                       bb: int = 128, interpret: bool = False) -> jax.Array:
    """Fused multi-branch codeword SpMM (one kernel for any n_branches).

    out_ids:    [b, D] int32  global node ids (padding: val == 0)
    out_vals:   [b, D]        edge values
    assignment: [n_branches, n] int32 or uint8 (k <= 256) codeword ids, or
                a nibble-packed ``PackedAssignment`` (k <= 16)
    codewords:  [n_branches, k, f_blk]  feature OR gradient codewords
                (f32, or int8/fp8 when ``cw_scale`` is given)
    cw_scale:   optional [n_branches, 1, f_blk] f32 per-branch/per-channel
                dequant scales (one epilogue multiply)
    w_t:        optional [n_branches * f_blk, f_out] fused epilogue matmul

    Returns [b, n_branches * f_blk] (branch-concatenated), or [b, f_out]
    with the ``w_t`` epilogue.  ``bb`` rows per grid step, rounded up to a
    multiple of 128 (rows ride the lanes).  A branch reads its codewords
    by lane lookup, or by one-hot where ``uses_lookup`` finds that cheaper.
    """
    _, k, f_blk = codewords.shape
    return _context_ell(out_ids, out_vals, assignment, codewords,
                        cw_scale=cw_scale, w_t=w_t, bb=bb,
                        interpret=interpret, lookup=uses_lookup(k, f_blk))


def _context_ell(out_ids, out_vals, assignment, codewords, *, cw_scale,
                 w_t, bb: int, interpret: bool, lookup: bool) -> jax.Array:
    """``context_ell_pallas`` with its inner form given: lane lookups, or
    the one-hot MXU product (``lookup=False``)."""
    b, deg = out_ids.shape
    nb, k, f_blk = codewords.shape
    if deg == 0:
        f_out = nb * f_blk if w_t is None else w_t.shape[1]
        return jnp.zeros((b, f_out), jnp.float32)

    bl = lane_tile(b, bb)
    bp = rup(b, bl)
    d8 = rup(deg, SUB)
    ids_t = out_ids.astype(jnp.int32).T                      # [D, b]
    aid = assignment.gather(ids_t) \
        if isinstance(assignment, PackedAssignment) \
        else assignment[:, ids_t]                            # [nb, D, b]
    # padded slots and rows carry id 0 and val 0
    aid = jnp.pad(aid.astype(jnp.int32),
                  ((0, 0), (0, d8 - deg), (0, bp - b))).reshape(nb * d8, bp)
    val_t = jnp.pad(out_vals.astype(jnp.float32).T,
                    ((0, d8 - deg), (0, bp - b)))

    # lookup tables: row (beta * f_blk + j) * n_grp + h holds column j of
    # branch beta's codewords 128 h .. 128 h + 127 (zeros past k), in
    # storage dtype; a narrower table is padded to whole (32, 128) tiles
    # and widened to f32 in the kernel -- the lookup returns each codeword
    # exactly.  A one-hot table is each branch's codebook transposed to
    # [f_pad, k_pad].
    n_grp = pl.cdiv(k, LANES)
    f_pad = rup(f_blk, SUB)
    tab = jnp.swapaxes(codewords, 1, 2)                      # [nb, f, k]
    scratch = []
    if lookup:
        tab = jnp.pad(tab, ((0, 0), (0, 0), (0, n_grp * LANES - k)))
        tab = tab.reshape(nb * f_blk * n_grp, LANES)
        if tab.dtype != jnp.float32:
            tab = jnp.pad(tab, ((0, rup(tab.shape[0], 4 * SUB)
                                 - tab.shape[0]), (0, 0)))
            scratch.append(pltpu.VMEM(tab.shape, jnp.float32))
    else:
        tab = jnp.pad(tab, ((0, 0), (0, f_pad - f_blk),
                            (0, rup(k, CHUNK) - k)))
    f_cat = nb * f_pad

    def spread(a):
        """[nb * f_blk, ...] -> [f_cat, ...]: branch beta's f_blk rows land
        at beta * f_pad, zeros elsewhere (the accumulator layout)."""
        a = a.reshape(nb, f_blk, *a.shape[1:])
        a = jnp.pad(a, [(0, 0), (0, f_pad - f_blk)]
                    + [(0, 0)] * (a.ndim - 2))
        return a.reshape(f_cat, *a.shape[2:])

    in_specs = [
        pl.BlockSpec((nb * d8, bl), lambda i: (0, i)),
        pl.BlockSpec((d8, bl), lambda i: (0, i)),
        pl.BlockSpec(tab.shape, lambda i: (0,) * tab.ndim),
    ]
    operands = [aid, val_t, tab]
    if cw_scale is not None:
        in_specs.append(pl.BlockSpec((f_cat, 1), lambda i: (0, 0)))
        operands.append(spread(cw_scale.astype(jnp.float32).reshape(
            nb * f_blk, 1)))
    f_out = f_cat
    if w_t is not None:
        f_out = w_t.shape[1]
        in_specs.append(pl.BlockSpec((f_out, f_cat), lambda i: (0, 0)))
        operands.append(spread(w_t.astype(jnp.float32)).T)
    out = pl.pallas_call(
        functools.partial(_context_ell_kernel, nb=nb, f_blk=f_blk,
                          n_grp=n_grp, scaled=cw_scale is not None,
                          fused_wt=w_t is not None, widen=bool(scratch),
                          lookup=lookup),
        grid=(bp // bl,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((f_out, bl), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((f_out, bp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((f_cat, bl), jnp.float32)] + scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    out = out[:, :b].T
    if w_t is None:
        out = out.reshape(b, nb, f_pad)[:, :, :f_blk].reshape(b, nb * f_blk)
    return out
