"""Approximated forward & backward message passing (paper Eq. 6 / Eq. 7).

The paper splits the messages of a mini-batch into
  * intra-batch messages  C_in X_B            -- computed exactly,
  * out-of-batch messages C~_out X~           -- approximated via codewords,
and back-propagates with the *transposed* approximated weight matrix, using
gradient codewords G~ for the "blue" messages that flow from out-of-batch
nodes (Fig. 2).  Autodiff cannot produce that rule (the codebook is streaming
EMA state), so the backward injection is a ``jax.custom_vjp``.

Two implementation forms, mathematically identical (DESIGN.md section 3):
  * reconstruction form (sparse convolutions): out-of-batch neighbor j's
    features are reconstructed from its per-branch codewords,
    X^_j = concat_beta X~^beta[R^beta[j]], and messages are passed per edge --
    this is the paper's App. E "another implementation" and equals the
    [b, k] sketch because  sum_j C_ij X^_j = sum_v (C_out R)_iv X~_v.
  * sketch form (dense/global convolutions, VQ-Attention): the [b, k]
    cluster-level mixing matrix C~_out = C_out R directly.

Both context directions route through ``kops.context_ell`` -- ONE fused
multi-branch kernel dispatch (DESIGN.md section 10).  The Eq. 7 injection
carries *lazy* residuals: instead of materializing the reconstructed
gradient-codeword tensor ``[b, Dr, f_grad]`` in the forward pass, the
residual is ``(rev_vals, rev_ids, grad_codewords, assignment, w)`` --
O(b * Dr) edge operands plus the O(k * f) codebook the step keeps resident
anyway -- and the backward pass streams the phantom term through the same
fused kernel (optionally with the ``@ W^T`` epilogue fused in).

Gradient extraction for the codebook update uses the *probe trick*: a zeros
input added at the pre-activation; its cotangent under jax.grad is exactly
G^(l+1) = grad_Z loss (Alg. 1 line 15 needs it for the VQ update).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.quantization import PackedAssignment
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# the custom backward rule (Eq. 7's out-of-batch gradient messages)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def inject_context_grad(x_b: jax.Array, rev_vals: jax.Array,
                        rev_ids: jax.Array, grad_codewords: jax.Array,
                        assignment: jax.Array,
                        w: Optional[jax.Array]) -> jax.Array:
    """Identity on ``x_b`` in the forward pass; lazy Eq. 7 residuals.

    In the backward pass, adds the paper's out-of-batch gradient messages

        grad_X_B  +=  ( sum_d rev_vals[:, d] * G~[c(rev_ids[:, d])] ) @ W^T

    where ``rev_vals[i, d] = C_{j_d, i}`` are the weights of the reverse
    (batch -> out-of-batch) edges and ``G~[c(j)]`` is the branch-concat
    gradient codeword of node j under ``assignment``.  This is the
    ``D_out G~ W^T`` term of Eq. 7 (``D_out = (C^T)_out R``), computed by
    the streaming ``kops.context_ell`` kernel at backward time -- the
    forward pass saves only ``(rev_vals, rev_ids, grad_codewords,
    assignment, w)``, never a ``[b, Dr, f_grad]`` reconstruction.

    ``w=None`` skips the W^T factor -- used by row-normalized convolutions
    where the probe (and hence the gradient codewords) live at the
    pre-normalization message level (paper App. E decoupling trick).
    """
    del rev_vals, rev_ids, grad_codewords, assignment, w
    return x_b


def _inject_fwd(x_b, rev_vals, rev_ids, grad_codewords, assignment, w):
    return x_b, (rev_vals, rev_ids, grad_codewords, assignment, w)


def _inject_bwd(res, g):
    rev_vals, rev_ids, grad_codewords, assignment, w = res
    w_t = None if w is None else w.astype(jnp.float32).T
    phantom = kops.context_ell(rev_ids, rev_vals, assignment,
                               grad_codewords, w_t)
    # tree_map: grad_codewords may be a QTensor (int8 values + f32 scales)
    return (g + phantom.astype(g.dtype), jnp.zeros_like(rev_vals), None,
            jax.tree_util.tree_map(jnp.zeros_like, grad_codewords), None,
            None if w is None else jnp.zeros_like(w))


inject_context_grad.defvjp(_inject_fwd, _inject_bwd)


@jax.custom_vjp
def inject_context_grad_materialized(x_b: jax.Array, rev_vals: jax.Array,
                                     grad_hat: jax.Array,
                                     w: Optional[jax.Array]) -> jax.Array:
    """Eq. 7 injection with an explicit ``grad_hat [b, Dr, f]`` tensor.

    For convolutions whose injected gradient is NOT a pure per-branch
    codeword gather (GAT: the reconstructed codeword concat passes through
    the per-head value map before the edge weighting, so branches mix) --
    the lazy form cannot express it and the reconstruction is a genuine
    residual.  Fixed convolutions must use :func:`inject_context_grad`.
    """
    del rev_vals, grad_hat, w
    return x_b


def _inject_mat_fwd(x_b, rev_vals, grad_hat, w):
    return x_b, (rev_vals, grad_hat, w)


def _inject_mat_bwd(res, g):
    rev_vals, grad_hat, w = res
    phantom = jnp.einsum('bd,bdf->bf', rev_vals.astype(jnp.float32),
                         grad_hat.astype(jnp.float32))
    if w is not None:
        phantom = phantom @ w.astype(jnp.float32).T
    return (g + phantom.astype(g.dtype), jnp.zeros_like(rev_vals),
            jnp.zeros_like(grad_hat),
            None if w is None else jnp.zeros_like(w))


inject_context_grad_materialized.defvjp(_inject_mat_fwd, _inject_mat_bwd)


@jax.custom_vjp
def inject_context_grad_table(x_b: jax.Array, rev_vals: jax.Array,
                              grad_table: jax.Array,
                              w: Optional[jax.Array]) -> jax.Array:
    """Eq. 7 injection against a row-independent gradient table.

    For sketch-form (dense) convolutions the receiving "neighbors" are the
    k clusters themselves, identical for every batch row: the phantom term
    is ``rev_vals [b, m] @ grad_table [m, f]``.  The residual is the
    O(m * f) table -- not its ``[b, m, f]`` broadcast.
    """
    del rev_vals, grad_table, w
    return x_b


def _inject_tab_fwd(x_b, rev_vals, grad_table, w):
    return x_b, (rev_vals, grad_table, w)


def _inject_tab_bwd(res, g):
    rev_vals, grad_table, w = res
    phantom = rev_vals.astype(jnp.float32) @ grad_table.astype(jnp.float32)
    if w is not None:
        phantom = phantom @ w.astype(jnp.float32).T
    return (g + phantom.astype(g.dtype), jnp.zeros_like(rev_vals),
            jnp.zeros_like(grad_table),
            None if w is None else jnp.zeros_like(w))


inject_context_grad_table.defvjp(_inject_tab_fwd, _inject_tab_bwd)


# ---------------------------------------------------------------------------
# codeword reconstruction (gather per-branch codewords, merge to full width)
# ---------------------------------------------------------------------------

def reconstruct(codewords: jax.Array, assignment: jax.Array,
                node_ids: jax.Array) -> jax.Array:
    """Rebuild full-width vectors for arbitrary nodes from product-VQ state.

    codewords:  [n_branches, k, f_blk]  (feature *or* gradient codewords)
    assignment: [n_branches, n]         per-branch codeword ids of all nodes
                (int32/uint8 array or nibble-packed ``PackedAssignment``)
    node_ids:   [...] int               global node ids to reconstruct
    returns     [..., n_branches * f_blk]
    """
    n_branches = codewords.shape[0]
    ids = assignment.gather(node_ids) \
        if isinstance(assignment, PackedAssignment) \
        else assignment[:, node_ids]                    # [nb, ...]
    gathered = jax.vmap(lambda cw, a: cw[a])(codewords, ids)  # [nb, ..., f_blk]
    out = jnp.moveaxis(gathered, 0, -2)                 # [..., nb, f_blk]
    return out.reshape(*out.shape[:-2], n_branches * codewords.shape[-1])


# ---------------------------------------------------------------------------
# forward context messages
# ---------------------------------------------------------------------------

def context_messages_reconstruct(out_vals: jax.Array, out_ids: jax.Array,
                                 feat_codewords: jax.Array,
                                 assignment: jax.Array) -> jax.Array:
    """Out-of-batch forward messages, reconstruction form.

    out_vals: [b, D]   C_{i, j_d} for out-of-batch neighbors (0 = padding)
    out_ids:  [b, D]   their global node ids
    feat_codewords: [n_branches, k, f_blk];  assignment: [n_branches, n]
    returns   [b, f]   =  sum_d out_vals[:, d] * X^_{j_d}

    ONE fused ``kops.context_ell`` dispatch regardless of n_branches
    (DESIGN.md section 10): assignment gather + codeword gather + weighted
    accumulate over D happen inside a single kernel against the resident
    [n_branches * k, f_blk] codeword tables -- no per-branch Python loop,
    no [n_branches, b, D] gathered-assignment intermediate, and the naive
    [b, D, f] reconstruction is never materialized on device.
    """
    cw = jax.lax.stop_gradient(feat_codewords)
    return kops.context_ell(out_ids, out_vals, assignment, cw)


def context_messages_sketch(c_out_sketch: jax.Array,
                            feat_codewords: jax.Array) -> jax.Array:
    """Out-of-batch forward messages, sketch form (dense convolutions).

    c_out_sketch:  [n_branches, b, k]   C~_out = C_out R, per branch
    feat_codewords:[n_branches, k, f_blk]
    returns        [b, n_branches * f_blk]
    """
    cw = jax.lax.stop_gradient(feat_codewords.astype(jnp.float32))
    per_branch = jnp.einsum('nbk,nkf->nbf',
                            c_out_sketch.astype(jnp.float32), cw)
    nb, b, fb = per_branch.shape
    return per_branch.transpose(1, 0, 2).reshape(b, nb * fb)


# ---------------------------------------------------------------------------
# exact intra-batch messages
# ---------------------------------------------------------------------------

def intra_messages(in_pos: jax.Array, in_vals: jax.Array,
                   x_b: jax.Array) -> jax.Array:
    """Exact intra-mini-batch messages  C_in X_B.

    in_pos:  [b, D] int32 -- neighbor position inside the batch (-1 padding /
             out-of-batch; those slots must carry in_vals == 0)
    in_vals: [b, D]
    x_b:     [b, f]
    """
    idx = jnp.maximum(in_pos, 0)
    return kops.spmm_ell(idx, in_vals, x_b)


# ---------------------------------------------------------------------------
# the assembled approximated message passing of one convolution
# ---------------------------------------------------------------------------

class ConvOperands(NamedTuple):
    """Per-mini-batch operands of one convolution's approximated MP.

    Built by ``repro.core.conv`` from the mini-batch pack + current VQ state.
    """
    in_pos: jax.Array      # [b, D]   intra-batch neighbor positions (-1 pad)
    in_vals: jax.Array     # [b, D]   C_in values (0 on padding)
    out_ids: jax.Array     # [b, D]   out-of-batch neighbor global ids
    out_vals: jax.Array    # [b, D]   C_out values (0 on padding)
    rev_ids: jax.Array     # [b, Dr]  reverse-edge (batch -> out) target ids
    rev_vals: jax.Array    # [b, Dr]  C^T_out values (0 on padding)


def approx_message_passing(ops_: ConvOperands, x_b: jax.Array,
                           feat_codewords: jax.Array,
                           grad_codewords: jax.Array,
                           assignment: jax.Array,
                           w: Optional[jax.Array],
                           inject: bool = True) -> jax.Array:
    """Full Eq. 6 forward with the Eq. 7 backward injection attached.

    Returns M = C_in X_B + C~_out X~  of shape [b, f]; its cotangent under
    autodiff is  C_in^T G_B (+ exact learnable-h paths)  and the custom rule
    adds  D_out G~ (W^T).  The injection is lazy (module docstring): the
    forward pass stores edge operands + the codebook, not a reconstructed
    ``[b, Dr, f_grad]`` tensor, and the backward streams Eq. 7 through the
    same fused context kernel the forward uses.
    """
    # named scopes label the parts in the HLO metadata (xprof), nothing else
    if inject:
        with jax.named_scope("context"):
            x_b = inject_context_grad(
                x_b, ops_.rev_vals, ops_.rev_ids,
                jax.lax.stop_gradient(grad_codewords), assignment, w)
    with jax.named_scope("in_batch"):
        m = intra_messages(ops_.in_pos, ops_.in_vals, x_b)
    with jax.named_scope("context"):
        return m + context_messages_reconstruct(
            ops_.out_vals, ops_.out_ids, feat_codewords, assignment)
