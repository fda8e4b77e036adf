"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the numerical ground truth the corresponding kernel is
validated against (tests sweep shapes/dtypes and assert_allclose).  They are
also the CPU execution path of ``ops.py`` -- on the CPU host the system runs
these, on TPU the Pallas kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.quantization import PackedAssignment


def vq_assign(x: jax.Array, codewords: jax.Array) -> jax.Array:
    """Nearest codeword by squared L2.  x: [b, f], codewords: [k, f] -> [b]."""
    x32 = x.astype(jnp.float32)
    c32 = codewords.astype(jnp.float32)
    # |x - c|^2 = |x|^2 - 2 x.c + |c|^2 ; |x|^2 is constant per row.
    scores = x32 @ c32.T                                  # [b, k]
    dist = jnp.sum(c32 * c32, axis=1)[None, :] - 2.0 * scores
    return jnp.argmin(dist, axis=1).astype(jnp.int32)


def vq_assign_update(x: jax.Array, codewords: jax.Array
                     ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused assign + cluster stats oracle (kernels/vq_update.py).

    x: [b, f], codewords: [k, f] -> (assignment [b] int32,
    qerr [b] = ||x - c_assign||^2, counts [k], sums [k, f]).  The stats are
    scatter-adds keyed by the assignment -- no [b, k] one-hot intermediate,
    which also makes this the fast CPU execution path of ops.py.
    """
    x32 = x.astype(jnp.float32)
    c32 = codewords.astype(jnp.float32)
    scores = x32 @ c32.T                                  # [b, k]
    dist = jnp.sum(c32 * c32, axis=1)[None, :] - 2.0 * scores
    idx = jnp.argmin(dist, axis=1).astype(jnp.int32)
    mind = jnp.take_along_axis(dist, idx[:, None], 1)[:, 0]
    qerr = jnp.maximum(mind + jnp.sum(x32 * x32, axis=1), 0.0)
    k = c32.shape[0]
    counts = jnp.zeros((k,), jnp.float32).at[idx].add(1.0)
    sums = jnp.zeros((k, x32.shape[1]), jnp.float32).at[idx].add(x32)
    return idx, qerr, counts, sums


def spmm_ell(nbr_idx: jax.Array, nbr_val: jax.Array, x: jax.Array,
             x_scale: jax.Array | None = None) -> jax.Array:
    """Padded-neighbor (ELLPACK) sparse @ dense.

    nbr_idx: [b, D] int32 (padding entries may point anywhere, their val is 0)
    nbr_val: [b, D] float
    x:       [n_src, f] (int8 or float8_e4m3fn rows when ``x_scale`` is
             given; the gather stays in storage dtype, the einsum upcasts)
    x_scale: optional [1, f] f32 per-channel dequant scales; applied as one
             epilogue multiply after the accumulate (row-independent scales
             commute with the over-neighbors sum -- the kernels' contract)
    returns  [b, f] with out[i] = sum_d val[i,d] * x[idx[i,d]]
    """
    gathered = x[nbr_idx]                                  # [b, D, f]
    out = jnp.einsum('bd,bdf->bf', nbr_val.astype(jnp.float32),
                     gathered.astype(jnp.float32))
    if x_scale is not None:
        out = out * x_scale.astype(jnp.float32).reshape(1, -1)
    return out


def context_ell(out_ids: jax.Array, out_vals: jax.Array,
                assignment: jax.Array, codewords: jax.Array,
                w_t: jax.Array | None = None,
                cw_scale: jax.Array | None = None) -> jax.Array:
    """Multi-branch VQ-context SpMM oracle (kernels/context_ell.py).

    out_ids/out_vals: [b, D] (padding entries carry val == 0)
    assignment: [n_branches, n] int32 (or uint8 storage, k <= 256; or a
                nibble-packed ``PackedAssignment``, k <= 16 -- the oracle
                unpacks it up front, the kernel shift/masks in-register)
    codewords: [n_branches, k, f_blk] (int8/fp8 when ``cw_scale`` is given)
    cw_scale: optional [n_branches, 1, f_blk] f32 per-branch/per-channel
              dequant scales, applied as one epilogue row multiply (the
              scales are k-independent -- same contract as the kernel)
    w_t: optional [n_branches * f_blk, f_out] fused epilogue matmul

    out[i] = sum_d val[i, d] * concat_beta cw[beta, assignment[beta, ids[i, d]]]
    (optionally @ w_t) -- the Eq. 6 context term and, with reverse-edge
    operands + gradient codewords, the streaming Eq. 7 backward.
    """
    nb, k, f_blk = codewords.shape
    b = out_ids.shape[0]
    if out_ids.shape[1] == 0:
        f_out = nb * f_blk if w_t is None else w_t.shape[1]
        return jnp.zeros((b, f_out), jnp.float32)
    if isinstance(assignment, PackedAssignment):
        assignment = assignment.unpack()
    branch_ids = assignment.astype(jnp.int32)[:, out_ids]  # [nb, b, D]
    vals = out_vals.astype(jnp.float32)
    # per-branch gather + contraction inside ONE computation (the branch
    # loop is a trace-time unroll, and this shape compiles to faster XLA
    # CPU code than a single [nb, b, D, f_blk] flat-gather einsum)
    out = jnp.concatenate(
        [jnp.einsum('bd,bdf->bf', vals,
                    codewords[i].astype(jnp.float32)[branch_ids[i]])
         for i in range(nb)], axis=-1)
    if cw_scale is not None:
        # dequant AFTER the accumulate, BEFORE the W^T mix (kernel ordering)
        out = out * cw_scale.astype(jnp.float32).reshape(1, nb * f_blk)
    if w_t is not None:
        out = out @ w_t.astype(jnp.float32)
    return out
