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
    and Mosaic cannot gather VMEM with a vector of ids) and arrives
    transposed, ``[n_branches * D, b]``, so batch rows ride the lanes;
  * all branches' codeword tables live VMEM-resident, each transposed to
    ``[f_pad, k]`` (``f_blk`` rounded up to the 8-row sublane tile) --
    k * f is tiny by construction, the point of VQ;
  * per branch, the resident SpMM's one-hot MXU product
    (``spmm_ell.onehot_ell_sum``) turns the tile's D slots into
    ``X~^beta^T @ A`` and writes that branch's ``[f_pad, rows]`` slice of
    the branch-concatenated output; no per-branch intermediate leaves VMEM.

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
codebook stays in storage dtype in VMEM and is widened a block at a time;
the accumulate runs in f32, and the dequant multiply is a single epilogue
column ``acc * scale``: scales are k-independent, so the multiply commutes
with the over-neighbors sum and with the fused ``w_t`` MXU epilogue
ordering (scale first, then ``@ W^T``).

Padding contract (shared with spmm_ell): slots with ``vals == 0`` may
point at any valid node id; rows padded to the lane tile carry zero vals.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.distributed.quantization import PackedAssignment
from repro.kernels.spmm_ell import (CHUNK, VMEM_LIMIT_BYTES, lane_tile,
                                    onehot_ell_sum, rup)


def _context_ell_kernel(aid_ref, val_ref, cw_ref, *refs, deg: int, nb: int,
                        scaled: bool, fused_wt: bool):
    # refs is ([sc_ref,] [wt_ref,] o_ref, acc_ref)
    refs = list(refs)
    sc_ref = refs.pop(0) if scaled else None
    wt_ref = refs.pop(0) if fused_wt else None
    o_ref, acc_ref = refs
    f_pad = cw_ref.shape[1]

    def branch(beta, carry):
        part = onehot_ell_sum(aid_ref, val_ref, cw_ref.at[beta],
                              beta * deg, deg)                 # [f_pad, bl]
        acc_ref[pl.ds(pl.multiple_of(beta * f_pad, 8), f_pad), :] = part
        return carry

    jax.lax.fori_loop(0, nb, branch, 0)
    tile = acc_ref[...]
    if sc_ref is not None:
        tile = tile * sc_ref[...]         # dequant BEFORE the W^T mix
    if wt_ref is not None:
        # fused epilogue: the Eq. 7 ``@ W^T`` as one resident MXU matmul
        tile = jnp.dot(wt_ref[...], tile,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    o_ref[...] = tile


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
    multiple of 128 (rows ride the lanes).
    """
    b, deg = out_ids.shape
    nb, k, f_blk = codewords.shape
    if deg == 0:
        f_out = nb * f_blk if w_t is None else w_t.shape[1]
        return jnp.zeros((b, f_out), jnp.float32)

    bl = lane_tile(b, bb)
    bp = rup(b, bl)
    ids_t = out_ids.astype(jnp.int32).T                      # [D, b]
    aid = assignment.gather(ids_t) \
        if isinstance(assignment, PackedAssignment) \
        else assignment[:, ids_t]                            # [nb, D, b]
    aid = jnp.zeros((nb * deg, bp), jnp.int32).at[:, :b].set(
        aid.astype(jnp.int32).reshape(nb * deg, b))
    val_t = jnp.zeros((deg, bp), jnp.float32).at[:, :b].set(
        out_vals.astype(jnp.float32).T)

    # per-branch transposed codebook [nb, f_pad, k_pad]
    f_pad, k_pad = rup(f_blk, 8), rup(k, CHUNK)
    cw = jnp.pad(jnp.swapaxes(codewords, 1, 2),
                 ((0, 0), (0, f_pad - f_blk), (0, k_pad - k)))
    f_cat = nb * f_pad

    def spread(a):
        """[nb * f_blk, ...] -> [f_cat, ...]: branch beta's f_blk rows land
        at beta * f_pad, zeros elsewhere (the accumulator layout)."""
        a = a.reshape(nb, f_blk, *a.shape[1:])
        a = jnp.pad(a, [(0, 0), (0, f_pad - f_blk)]
                    + [(0, 0)] * (a.ndim - 2))
        return a.reshape(f_cat, *a.shape[2:])

    in_specs = [
        pl.BlockSpec((nb * deg, bl), lambda i: (0, i)),
        pl.BlockSpec((deg, bl), lambda i: (0, i)),
        pl.BlockSpec(cw.shape, lambda i: (0, 0, 0)),
    ]
    operands = [aid, val_t, cw]
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
        functools.partial(_context_ell_kernel, deg=deg, nb=nb,
                          scaled=cw_scale is not None,
                          fused_wt=w_t is not None),
        grid=(bp // bl,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((f_out, bl), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((f_out, bp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((f_cat, bl), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    out = out[:, :b].T
    if w_t is None:
        out = out.reshape(b, nb, f_pad)[:, :, :f_blk].reshape(b, nb * f_blk)
    return out
