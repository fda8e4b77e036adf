"""Generalized graph convolution (paper Sec. 2, Eq. 1-2) operand builders.

A convolution matrix ``C^(s)`` is either *fixed* (GCN / SAGE-Mean / GIN / GDC
-- entries derivable from the adjacency structure and degrees) or *learnable*
(GAT / Graph-Transformer -- ``C_ij = frak_C_ij * h_theta(X_i, X_j)``,
optionally row-normalized).

This module converts a mini-batch "pack" (padded neighbor lists produced by
the graph pipeline) + the current VQ state into the per-convolution
:class:`~repro.core.message_passing.ConvOperands`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import codebook as cbm
from repro.core.codebook import CodebookState, CodebookConfig
from repro.core.message_passing import ConvOperands
from repro.distributed.quantization import PackedAssignment, QTensor
from repro.kernels import ops as kops


class MinibatchPack(NamedTuple):
    """Device-side mini-batch of nodes with padded (ELLPACK) neighbor lists.

    Produced by ``repro.graph.batching``; all shapes static per dataset.
    ``nbr_*`` are the in-edges (messages INTO batch nodes, forward pass);
    ``rev_*`` are the out-edges (messages FROM batch nodes -- the "blue"
    backward messages of Fig. 2).  Positions are the index inside the batch
    if the other endpoint is also in the batch, else -1.
    ``slot_mask`` (optional, [b]) is 0 on the wrap-padded slots of a tail
    batch -- those rows are real (wrapped) nodes whose messages stay valid,
    but the loss must skip them (DESIGN.md section 9).
    """
    batch_ids: jax.Array   # [b]      global node ids
    nbr_ids: jax.Array     # [b, D]   in-neighbor global ids (0 on padding)
    nbr_mask: jax.Array    # [b, D]   1.0 on real edges
    nbr_pos: jax.Array     # [b, D]   in-batch position or -1
    rev_ids: jax.Array     # [b, Dr]  out-edge target global ids
    rev_mask: jax.Array    # [b, Dr]
    rev_pos: jax.Array     # [b, Dr]
    slot_mask: Optional[jax.Array] = None

    @property
    def b(self) -> int:
        return self.batch_ids.shape[0]


class QuantizedCodewords(NamedTuple):
    """int8 kernel-operand snapshot of a layer's codeword tables.

    Each QTensor pairs [n_branches, k, f_blk] int8 values with
    [n_branches, 1, f_blk] f32 per-branch/per-channel scales -- the layout
    ``kops.context_ell`` consumes natively (DESIGN.md section 13).
    """
    feat: QTensor   # feature codewords X~ (Eq. 6 forward)
    grad: QTensor   # gradient codewords G~ (Eq. 7 backward)


class LayerVQState(NamedTuple):
    """Per-layer streaming VQ state: codebook + global assignment table.

    ``assignment`` is int32, uint8 under the int8/fp8 operand tiers
    (k <= 256), or a nibble-packed ``PackedAssignment`` under the +a4
    tiers (k <= 16) -- the kernels accept every storage form.  ``qcw``,
    when present, is the int8 or fp8 snapshot of the codeword tables the
    layers feed the context kernels instead of dense f32 slices; it is
    refreshed by the codebook update (quantize-on-update, in the snapshot's
    own storage dtype) and preserved untouched by assignment scatters.
    """
    codebook: CodebookState
    # [n_branches, n] codeword id per node: int32 | uint8 | PackedAssignment
    assignment: jax.Array | PackedAssignment
    counts: jax.Array      # [n_branches, k] f32    histogram of `assignment`
    qcw: Optional[QuantizedCodewords] = None


def branch_histogram(ids: jax.Array, k: int,
                     weights: Optional[jax.Array] = None) -> jax.Array:
    """Per-branch codeword histogram as ONE flattened segment-sum.

    ids: [n_branches, m] int codeword ids; weights: optional [n_branches, m]
    (default 1.0 per id) -> [n_branches, k] float32.

    Offsetting branch beta's ids by beta * k turns the per-branch
    histograms into a single 1-D segment-sum over n_branches * k buckets --
    one scatter instead of the n_branches-deep vmap'd ``.at[].add`` chains
    these hot paths (every train step) used to compile to.
    """
    nb, m = ids.shape
    flat = (ids.astype(jnp.int32)
            + (k * jnp.arange(nb, dtype=jnp.int32))[:, None]).reshape(-1)
    w = jnp.ones((nb * m,), jnp.float32) if weights is None \
        else weights.astype(jnp.float32).reshape(-1)
    return jax.ops.segment_sum(
        w, flat, num_segments=nb * k).reshape(nb, k)


@jax.named_scope("vq_update")
def refresh_assignment(state: LayerVQState, batch_ids: jax.Array,
                       new_assign: jax.Array) -> LayerVQState:
    """Scatter the refreshed batch assignments into the global table
    (Alg. 1 line 16, 'synchronize the codeword assignment matrix')."""
    k = state.counts.shape[-1]
    packed = isinstance(state.assignment, PackedAssignment)
    old = state.assignment.gather(batch_ids) if packed \
        else state.assignment[:, batch_ids]                     # [nb, b]
    # -1 on the evicted ids, +1 on the refreshed ones, in one segment-sum
    delta = branch_histogram(
        jnp.concatenate([old, new_assign.astype(old.dtype)], axis=1), k,
        jnp.concatenate([jnp.full(old.shape, -1.0, jnp.float32),
                         jnp.ones(new_assign.shape, jnp.float32)], axis=1))
    if packed:
        # parity-pass nibble scatter; batch_ids are distinct per batch (the
        # EpochPlan pack contract), which scatter_nibbles requires
        assignment = state.assignment.scatter(batch_ids, new_assign)
    else:
        assignment = state.assignment.at[:, batch_ids].set(
            new_assign.astype(state.assignment.dtype))
    return LayerVQState(state.codebook, assignment, state.counts + delta,
                        state.qcw)


def assignment_dtype(cfg: CodebookConfig):
    """Element dtype of the global assignment table under the active
    kernel precision tier: uint8 when a quantized tier is on and k fits a
    byte (the 4x VMEM-envelope win on the fused context kernel's resident
    table), else int32.  The +a4 tiers additionally nibble-pack the uint8
    values two-per-byte -- see ``assignment_packed``."""
    quantized = kops.precision_codeword_dtype() is not None and cfg.k <= 256
    return jnp.uint8 if quantized else jnp.int32


def assignment_packed(cfg: CodebookConfig) -> bool:
    """True when the active tier nibble-packs the assignment table
    (a '+a4' tier and k <= 16; larger k silently stays unpacked, matching
    the uint8 fallback to int32 for k > 256)."""
    return kops.precision_packs_assignment() and cfg.k <= 16


def quantize_layer_state(state: LayerVQState, f_feat: int,
                         cfg: CodebookConfig,
                         dtype=jnp.int8) -> LayerVQState:
    """(Re)build the quantized codeword snapshot from the current codebook,
    reusing the previous snapshot's scales inside the drift band.

    ``dtype`` (int8 or float8_e4m3fn) only matters on the first build;
    with an existing snapshot the requantization keeps its storage dtype
    (data-driven -- this runs inside jitted update steps, which must not
    read the precision knob)."""
    prev = state.qcw
    qf, qg = cbm.quantized_codewords(
        state.codebook, f_feat, cfg,
        prev_feat=None if prev is None else prev.feat,
        prev_grad=None if prev is None else prev.grad,
        dtype=dtype)
    return state._replace(qcw=QuantizedCodewords(qf, qg))


def layer_codewords(vq: LayerVQState, f_feat: int, cfg: CodebookConfig, *,
                    dense: bool = False):
    """The (feature, gradient) codeword operands a layer feeds the context
    kernels: the int8 QTensor snapshot when one is attached, else dense f32
    slices.  ``dense=True`` forces f32 materialization -- GAT and the
    Graph-Transformer mix branches through per-head weight maps, so their
    math needs real tables, not kernel-side dequant epilogues.
    """
    if vq.qcw is not None and not dense:
        return vq.qcw.feat, vq.qcw.grad
    return (cbm.feature_codewords(vq.codebook, f_feat, cfg),
            cbm.gradient_codewords(vq.codebook, f_feat, cfg))


def init_layer_vq_state(key: jax.Array, n_nodes: int, f_feat: int,
                        f_grad: int, cfg: CodebookConfig) -> LayerVQState:
    from repro.core.codebook import init_codebook
    k_cb, k_assign = jax.random.split(key)
    cb = init_codebook(k_cb, f_feat, f_grad, cfg)
    dtype = assignment_dtype(cfg)
    assignment = jax.random.randint(
        k_assign, (cb.n_branches, n_nodes), 0, cfg.k).astype(dtype)
    counts = branch_histogram(assignment, cfg.k)
    if assignment_packed(cfg):
        assignment = PackedAssignment.pack(assignment)
    state = LayerVQState(cb, assignment, counts)
    cw_dtype = kops.precision_codeword_dtype()
    if cw_dtype is not None:
        state = quantize_layer_state(state, f_feat, cfg, dtype=cw_dtype)
    return state


# ---------------------------------------------------------------------------
# fixed convolution edge values (paper Table 1)
# ---------------------------------------------------------------------------

def fixed_edge_values(kind: str, pack: MinibatchPack,
                      degrees: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Edge values of a fixed convolution for a mini-batch.

    kind:
      'gcn'  : C = D~^-1/2 A~ D~^-1/2  (self-loop handled via `self_vals`)
      'mean' : C = D^-1 A              (SAGE-Mean aggregator)
      'adj'  : C = A                   (GIN aggregation / GAT mask)
    degrees: [n] float -- raw degrees (no self loop).

    Returns (in_vals, out_vals, rev_vals, self_vals):
      in_vals/out_vals split the forward in-edge values by in/out-of-batch;
      rev_vals are the C_{j,i} values on out-edges to out-of-batch targets;
      self_vals [b] is the diagonal (self-loop) weight, 0 if none.
    """
    deg_i = degrees[pack.batch_ids]                       # [b]
    deg_in = degrees[pack.nbr_ids]                        # [b, D]
    deg_rev = degrees[pack.rev_ids]                       # [b, Dr]

    if kind == 'gcn':
        dt_i = deg_i + 1.0
        vals = pack.nbr_mask / jnp.sqrt(dt_i[:, None] * (deg_in + 1.0))
        rev = pack.rev_mask / jnp.sqrt((deg_rev + 1.0) * dt_i[:, None])
        self_vals = 1.0 / dt_i
    elif kind == 'mean':
        vals = pack.nbr_mask / jnp.maximum(deg_i, 1.0)[:, None]
        rev = pack.rev_mask / jnp.maximum(deg_rev, 1.0)
        self_vals = jnp.zeros_like(deg_i)
    elif kind == 'adj':
        vals = pack.nbr_mask
        rev = pack.rev_mask
        self_vals = jnp.zeros_like(deg_i)
    else:
        raise ValueError(f"unknown fixed conv kind: {kind}")

    in_vals = jnp.where(pack.nbr_pos >= 0, vals, 0.0)
    out_vals = jnp.where(pack.nbr_pos < 0, vals, 0.0)
    # only out-of-batch reverse targets are injected (in-batch ones are
    # handled exactly by autodiff through the intra term)
    rev_vals = jnp.where(pack.rev_pos < 0, rev, 0.0)
    return in_vals, out_vals, rev_vals, self_vals


@jax.named_scope("edge_norm")
def fixed_conv_operands(kind: str, pack: MinibatchPack,
                        degrees: jax.Array) -> tuple[ConvOperands, jax.Array]:
    in_vals, out_vals, rev_vals, self_vals = fixed_edge_values(
        kind, pack, degrees)
    ops_ = ConvOperands(
        in_pos=pack.nbr_pos, in_vals=in_vals,
        out_ids=pack.nbr_ids, out_vals=out_vals,
        rev_ids=pack.rev_ids, rev_vals=rev_vals)
    return ops_, self_vals


# ---------------------------------------------------------------------------
# dense/global convolution sketch masses (Graph-Transformer; paper Table 5)
# ---------------------------------------------------------------------------

def out_of_batch_cluster_mass(state: LayerVQState,
                              batch_ids: jax.Array) -> jax.Array:
    """fraC~_out for the all-ones mask of global attention: [n_branches, k].

    For a dense convolution the fixed mask is all-ones, so the sketch
    ``frak_C_out R`` reduces per row to the out-of-batch cluster sizes
    (global histogram minus the batch members' clusters) -- O(k) instead of
    O(n), the paper's key win for global-context GNNs.
    """
    k = state.counts.shape[-1]
    batch_assign = state.assignment.gather(batch_ids) \
        if isinstance(state.assignment, PackedAssignment) \
        else state.assignment[:, batch_ids]               # [nb, b]
    batch_counts = branch_histogram(batch_assign, k)
    return jnp.maximum(state.counts - batch_counts, 0.0)
