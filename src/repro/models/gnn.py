"""Full GNN models: assembly, losses, train steps, VQ mini-batch inference.

Three execution paths over one parameter set:
  * full-graph  -- the paper's oracle ("Full-Graph" rows of Table 4);
  * sampler     -- exact message passing on a sampled subgraph (baselines);
  * VQ          -- the paper's mini-batch algorithm (Alg. 1): approximated
                   message passing + probe-trick gradient taps + streaming
                   codebook/assignment refresh after every step.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import trace_count
from repro.core import codebook as cbm
from repro.core.codebook import CodebookConfig
from repro.core.conv import LayerVQState, MinibatchPack, init_layer_vq_state, \
    quantize_layer_state, refresh_assignment
from repro.distributed.collectives import gather_from_shards, psum_tree, \
    shard_scatter_rows
from repro.distributed.quantization import PackedAssignment
from repro.graph.batching import EpochPlan, FullGraphOperands, plan_batch, \
    plan_batch_sharded
from repro.kernels import ops as kops
from repro.nn.gnn_layers import BACKBONES
from repro.train.optimizer import Optimizer

Params = Any


class GNNConfig(NamedTuple):
    backbone: str = "gcn"
    f_in: int = 128
    hidden: int = 128
    n_out: int = 40
    n_layers: int = 3
    heads: int = 4
    task: str = "node"            # "node" | "link"
    multilabel: bool = False
    grad_inject: bool = True      # Eq. 7 out-of-batch gradient injection
    # (paper-faithful ON; our experiments find forward-VQ alone already
    # reaches parity while stale gradient codewords can add noise --
    # EXPERIMENTS.md "reproduction nuances")
    codebook: CodebookConfig = CodebookConfig(k=256, f_prod=4)

    def layer_dims(self) -> list[tuple[int, int]]:
        dims = []
        f = self.f_in
        for l in range(self.n_layers):
            last = l == self.n_layers - 1
            f_out = (self.n_out if (last and self.task == "node")
                     else self.hidden)
            dims.append((f, f_out))
            f = f_out
        return dims

    def layer_codebook_cfg(self) -> CodebookConfig:
        if self.backbone == "transformer":
            # dense learnable convolution needs full-width codewords
            return self.codebook._replace(f_prod=1 << 30)
        return self.codebook


def init_gnn(key: jax.Array, cfg: GNNConfig) -> list[Params]:
    bk = BACKBONES[cfg.backbone]
    keys = jax.random.split(key, cfg.n_layers)
    params = []
    for k, (fi, fo) in zip(keys, cfg.layer_dims()):
        if cfg.backbone in ("gat", "transformer") and fo % cfg.heads != 0:
            # widen the output of attention layers to a head multiple; a
            # final linear head maps to n_out
            fo = ((fo + cfg.heads - 1) // cfg.heads) * cfg.heads
        params.append(bk.init(k, fi, fo, heads=cfg.heads))
    return params


def _layer_out_dims(cfg: GNNConfig) -> list[tuple[int, int]]:
    dims = cfg.layer_dims()
    if cfg.backbone in ("gat", "transformer"):
        dims = [(fi, ((fo + cfg.heads - 1) // cfg.heads) * cfg.heads)
                for fi, fo in dims]
        fixed = []
        f = cfg.f_in
        for _, fo in dims:
            fixed.append((f, fo))
            f = fo
        return fixed
    return dims


def init_vq_states(key: jax.Array, cfg: GNNConfig,
                   n_nodes: int) -> list[LayerVQState]:
    bk = BACKBONES[cfg.backbone]
    cb_cfg = cfg.layer_codebook_cfg()
    states = []
    for i, (fi, fo) in enumerate(_layer_out_dims(cfg)):
        k = jax.random.fold_in(key, i)
        fg = bk.f_grad(fi, fo, heads=cfg.heads)
        states.append(init_layer_vq_state(k, n_nodes, fi, fg, cb_cfg))
    return states


def quantize_vq_states(vq_states: list[LayerVQState], cfg: GNNConfig,
                       precision: str | None = None) -> list[LayerVQState]:
    """Quantized serving conversion of the per-layer VQ states.

    ``precision`` is a tier from ``kops.PRECISIONS`` (default: the active
    ``kernel_precision()``; plain ``quantize_vq_states(vq, cfg)`` under the
    fp32 default keeps the historical behavior of the int8 tier).  Each
    layer gets a uint8 assignment table (k <= 256 -- the 4x VMEM win on
    the fused context kernel's resident table), nibble-packed two-ids-per-
    byte under the '+a4' tiers (k <= 16, 8x vs int32), and an attached
    QTensor codeword snapshot in the tier's storage dtype (int8 or
    float8_e4m3fn), so every context dispatch downstream consumes
    quantized operands (DESIGN.md sections 13/15).  Idempotent; the fp32
    codebook stays in place for updates and dense (GAT/transformer) reads.
    """
    if precision is None:
        p = kops.kernel_precision()
        precision = p if p != "fp32" else "int8"
    cw_dtype = kops.precision_codeword_dtype(precision)
    if cw_dtype is None:
        return list(vq_states)
    pack = kops.precision_packs_assignment(precision)
    cb_cfg = cfg.layer_codebook_cfg()
    if cb_cfg.k > 256:
        raise ValueError(
            f"quantized assignment tables need k <= 256, got k={cb_cfg.k}")
    if pack and cb_cfg.k > 16:
        raise ValueError(
            f"nibble-packed ('+a4') assignment tables need k <= 16, got "
            f"k={cb_cfg.k}; use precision={precision.split('+')[0]!r}")
    out = []
    for (fi, _), vq in zip(_layer_out_dims(cfg), vq_states):
        a = vq.assignment
        if isinstance(a, PackedAssignment):
            a = a if pack else a.unpack()
        else:
            a = a.astype(jnp.uint8)
            if pack:
                a = PackedAssignment.pack(a)
        st = vq._replace(assignment=a, qcw=None)
        out.append(quantize_layer_state(st, fi, cb_cfg, dtype=cw_dtype))
    return out


def probe_shapes(cfg: GNNConfig, b: int) -> list[tuple[int, ...]]:
    bk = BACKBONES[cfg.backbone]
    return [bk.probe_shape(b, fi, fo, heads=cfg.heads)
            for fi, fo in _layer_out_dims(cfg)]


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _act_for_layer(cfg: GNNConfig, l: int):
    last = l == cfg.n_layers - 1
    return (lambda z: z) if last else jax.nn.relu


def full_forward(params: list[Params], x: jax.Array,
                 ops_: FullGraphOperands, cfg: GNNConfig) -> jax.Array:
    bk = BACKBONES[cfg.backbone]
    for l, p in enumerate(params):
        x = bk.full_apply(p, x, ops_, _act_for_layer(cfg, l))
    return x


def vq_forward(params: list[Params], x_b: jax.Array,
               probes: Optional[list[jax.Array]],
               pack: MinibatchPack, vq_states: list[LayerVQState],
               degrees: jax.Array, cfg: GNNConfig,
               inject: Optional[bool] = None
               ) -> tuple[jax.Array, list[jax.Array]]:
    """Returns (output, per-layer input activations) -- the activations pair
    with the probe cotangents for the codebook update (Alg. 1 line 15).

    ``inject`` overrides ``cfg.grad_inject`` (the Eq. 7 custom-VJP wrapper);
    inference/eval passes False -- the injection only matters under
    ``jax.grad`` and its lazy residuals (message_passing.py) are a
    training-path contract, not an eval cost.  ``probes=None`` skips the
    probe taps entirely (gradient-free paths: inference executor, serving)
    instead of adding per-layer zero tensors.
    """
    bk = BACKBONES[cfg.backbone]
    cb_cfg = cfg.layer_codebook_cfg()
    inject = cfg.grad_inject if inject is None else inject
    acts = []
    x = x_b
    for l, (p, vq, (fi, fo)) in enumerate(
            zip(params, vq_states, _layer_out_dims(cfg))):
        acts.append(x)
        x = bk.vq_apply(p, x, None if probes is None else probes[l],
                        pack, vq, degrees, cb_cfg,
                        _act_for_layer(cfg, l), fi, fo, inject=inject)
    return x, acts


# ---------------------------------------------------------------------------
# losses / metrics
# ---------------------------------------------------------------------------

def node_loss_terms(logits: jax.Array, labels: jax.Array, multilabel: bool,
                    mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(numerator, denominator) of the masked-mean CE/BCE.

    The single-device loss is ``num / max(den, 1)``; the data-parallel
    epoch executor psums each term over the mesh axis before dividing so
    the sharded loss equals the full-batch masked mean exactly."""
    if multilabel:
        per = jnp.mean(
            jnp.maximum(logits, 0) - logits * labels +
            jnp.log1p(jnp.exp(-jnp.abs(logits))), axis=-1)
    else:
        logp = jax.nn.log_softmax(logits, axis=-1)
        per = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
    return jnp.sum(per * mask), jnp.sum(mask)


def node_loss(logits: jax.Array, labels: jax.Array, multilabel: bool,
              mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean CE/BCE over (optionally masked) rows.  The mask implements the
    paper's transductive mini-batching: batches traverse ALL nodes (so every
    node's codeword assignment stays fresh) but only labeled nodes
    contribute to the loss."""
    if mask is None:
        mask = jnp.ones(logits.shape[0], logits.dtype)
    num, den = node_loss_terms(logits, labels, multilabel, mask)
    return num / jnp.maximum(den, 1.0)


def node_metric(logits: jax.Array, labels: jax.Array,
                multilabel: bool) -> jax.Array:
    if multilabel:   # micro-F1 at threshold 0
        pred = logits > 0
        tp = jnp.sum(pred * labels)
        return 2 * tp / jnp.maximum(jnp.sum(pred) + jnp.sum(labels), 1.0)
    return jnp.mean(jnp.argmax(logits, -1) == labels)


def link_loss(emb: jax.Array, pos: jax.Array, neg: jax.Array,
              pair_mask: Optional[jax.Array] = None) -> jax.Array:
    """emb indexed locally: pos/neg [e, 2] into emb rows.  pair_mask allows
    padding the pair lists to a static size (compile-once semantics)."""
    def score(pairs):
        return jnp.sum(emb[pairs[:, 0]] * emb[pairs[:, 1]], axis=-1)
    sp, sn = score(pos), score(neg)
    # stable BCE: log(1+e^z) = softplus(z) (log1p(exp(.)) overflows at init)
    lp, ln = jax.nn.softplus(-sp), jax.nn.softplus(sn)
    if pair_mask is None:
        return jnp.mean(lp) + jnp.mean(ln)
    m = jnp.maximum(pair_mask.sum(), 1.0)
    return jnp.sum(lp * pair_mask) / m + jnp.sum(ln * pair_mask) / m


def hits_at_k(pos_scores: np.ndarray, neg_scores: np.ndarray,
              k: int = 50) -> float:
    if len(pos_scores) == 0:
        # no positive pairs in the split: hits@k is 0 by convention (the
        # mean of an empty array would silently propagate NaN into the
        # metric history)
        return 0.0
    if len(neg_scores) < k:
        thresh = neg_scores.min() if len(neg_scores) else -np.inf
    else:
        thresh = np.sort(neg_scores)[-k]
    return float((pos_scores > thresh).mean())


# ---------------------------------------------------------------------------
# VQ train step (Alg. 1)
# ---------------------------------------------------------------------------

def _vq_step_body(params, vq_states, opt_state, pack: MinibatchPack,
                  x_b, labels_b, degrees, cfg: GNNConfig, opt: Optimizer,
                  loss_mask=None, neg_pairs=None, pos_pairs=None,
                  axis_name=None):
    """One Alg. 1 step, trace-level -- the ONE implementation behind the
    jit'd per-step entry point, the ``lax.scan`` epoch executor, and (with
    ``axis_name``) the shard_map data-parallel executor, so every path
    stays numerically consistent.

    With ``axis_name`` set (node task only), ``x_b``/``pack`` are this
    replica's shard of the batch and the replicas are glued into one model
    per step: the loss is the GLOBAL masked mean (num/den psum'd), param
    grads are psum'd before the optimizer, codebook (counts, sums) and
    whitening moments are psum'd inside ``cbm.update``, and the refreshed
    assignments are all-gathered into the replicated global table
    (DESIGN.md section 9, "codebook psum rule").
    """
    probes = [jnp.zeros(s, jnp.float32) for s in probe_shapes(cfg, pack.b)]
    if cfg.task == "node":
        lmask = loss_mask if loss_mask is not None \
            else jnp.ones((pack.b,), jnp.float32)
        den = jnp.sum(lmask)
        if axis_name is not None:
            den = jax.lax.psum(den, axis_name)   # independent of params
    else:
        assert axis_name is None, "dp epoch executor is node-task only"

    def loss_fn(params, probes):
        out, acts = vq_forward(params, x_b, probes, pack, vq_states,
                               degrees, cfg)
        if cfg.task == "node":
            num, _ = node_loss_terms(out, labels_b, cfg.multilabel, lmask)
            loss = num / jnp.maximum(den, 1.0)
        else:
            loss = link_loss(out, pos_pairs, neg_pairs)
        return loss, (acts, out)

    (loss, (acts, out)), (gparams, gprobes) = jax.value_and_grad(
        loss_fn, argnums=(0, 1), has_aux=True)(params, probes)

    if axis_name is not None:
        loss = jax.lax.psum(loss, axis_name)
        gparams = psum_tree(gparams, axis_name)
    with jax.named_scope("optimizer"):
        new_params, new_opt = opt.update(gparams, opt_state, params)

    # ---- Alg. 1 line 15-16: VQ update + assignment synchronization ----
    # cbm.update is fused (one distance pass per branch, codebook.py module
    # docstring); its UpdateStats also hands back the whitened-space VQ
    # relative error per layer, surfaced to the trainer as a free monitor.
    cb_cfg = cfg.layer_codebook_cfg()
    refresh_ids = pack.batch_ids
    if axis_name is not None:
        refresh_ids = jax.lax.all_gather(
            pack.batch_ids, axis_name).reshape(-1)
    new_states, vq_errs = [], []
    for l, vq in enumerate(vq_states):
        feats = acts[l].astype(jnp.float32)
        grads = gprobes[l].reshape(pack.b, -1).astype(jnp.float32)
        # gradients enter the codebook unscaled: Alg. 2's implicit whitening
        # normalizes every concat dim, so codebook geometry is invariant to
        # their magnitude and the EMA stats are fp32 (no fp-range guard)
        new_cb, stats = cbm.update(vq.codebook, feats, grads, cb_cfg,
                                   axis_name=axis_name)
        assign = stats.assignment
        if axis_name is None:
            vq_errs.append(stats.relative_error())
        else:
            a = jax.lax.all_gather(assign, axis_name)  # [ndev, nb, b_loc]
            assign = a.transpose(1, 0, 2).reshape(a.shape[1], -1)
            vq_errs.append(jnp.sqrt(
                jax.lax.psum(jnp.sum(stats.qerr), axis_name) /
                (jax.lax.psum(jnp.sum(stats.vnorm2), axis_name) + 1e-12)))
        st = refresh_assignment(
            LayerVQState(new_cb, vq.assignment, vq.counts, vq.qcw),
            refresh_ids, assign)
        if vq.qcw is not None:
            # quantize-on-update: rebuild the int8 codeword snapshot from
            # the post-EMA codebook; scales are reused inside the drift
            # band so barely-moving tables keep byte-stable int8 state
            st = quantize_layer_state(st, feats.shape[-1], cb_cfg)
        new_states.append(st)

    return new_params, new_states, new_opt, loss, out, jnp.stack(vq_errs)


@functools.partial(jax.jit, static_argnames=("cfg", "opt"))
def vq_train_step(params, vq_states, opt_state, pack: MinibatchPack,
                  x_b, labels_b, degrees, cfg: GNNConfig, opt: Optimizer,
                  loss_mask=None, neg_pairs=None, pos_pairs=None):
    return _vq_step_body(params, vq_states, opt_state, pack, x_b, labels_b,
                         degrees, cfg, opt, loss_mask=loss_mask,
                         neg_pairs=neg_pairs, pos_pairs=pos_pairs)


def _vq_epoch_body(params, vq_states, opt_state, plan: EpochPlan,
                   perm, slot_mask, x, labels, train_mask, degrees, *,
                   cfg: GNNConfig, opt: Optimizer, axis_name=None,
                   sharded_state=False, compress=False):
    """``lax.scan`` of ``_vq_step_body`` over the S stacked batches of a
    node permutation (trace-level; node task).  Each step slices its batch
    out of the pack-once :class:`~repro.graph.batching.EpochPlan`
    (``plan_batch``: row gather + node->slot scatter, no host round-trip).
    With ``axis_name`` this is the per-replica body of the shard_map
    data-parallel executor (``distributed/data_parallel.py``) and
    ``perm``/``slot_mask`` are the replica's [S, b/ndev] shard.

    With ``sharded_state`` additionally set (DESIGN.md section 14),
    ``plan``/``x``/``labels``/``train_mask`` are this shard's contiguous
    row BLOCK of the padded global node tables rather than full replicas:
    every per-batch row access goes cross-shard
    (``plan_batch_sharded`` + ``gather_from_shards``), while the step
    math -- psum'd grads/loss, codebook counts/sums/revival, assignment
    all-gather -- is byte-identical to the replicated DP path.
    ``compress`` routes the feature-row gather through the int8
    ``compressed_psum`` payload (lossy, opt-in)."""
    def body(carry, xs):
        params, vq, ost = carry
        bids, smask = xs
        if sharded_state:
            pack = plan_batch_sharded(plan, bids, axis_name, smask)
            x_b = gather_from_shards(x, bids, axis_name, compress=compress)
            labels_b = gather_from_shards(labels, bids, axis_name)
            lmask = gather_from_shards(train_mask, bids, axis_name) * smask
        else:
            pack = plan_batch(plan, bids, smask)
            x_b, labels_b = x[bids], labels[bids]
            lmask = train_mask[bids] * smask
        params, vq, ost, loss, _, errs = _vq_step_body(
            params, vq, ost, pack, x_b, labels_b, degrees, cfg,
            opt, loss_mask=lmask, axis_name=axis_name)
        return (params, vq, ost), (loss, errs)

    (params, vq_states, opt_state), (losses, vq_errs) = jax.lax.scan(
        body, (params, vq_states, opt_state), (perm, slot_mask))
    return params, vq_states, opt_state, losses, vq_errs


@functools.partial(jax.jit, static_argnames=("cfg", "opt"),
                   donate_argnames=("params", "vq_states", "opt_state"))
def vq_train_epoch(params, vq_states, opt_state, plan: EpochPlan,
                   perm: jax.Array, slot_mask: jax.Array, x, labels,
                   train_mask, degrees, cfg: GNNConfig, opt: Optimizer):
    """One epoch of Alg. 1 executed entirely on device (DESIGN.md sec. 9):
    one jit call scanning the per-step body over the stacked batches, with
    ``(params, vq_states, opt_state)`` carried in donated buffers.

    perm:       [S, b] int  node ids per batch (``epoch_slices``)
    slot_mask:  [S, b]      0 on wrap-padded tail slots (loss-masked)
    x / labels / train_mask: full [n, ...] device-resident arrays
    Returns (params, vq_states, opt_state, losses [S], vq_errs [S, L]).
    """
    return _vq_epoch_body(params, vq_states, opt_state, plan, perm,
                          slot_mask, x, labels, train_mask, degrees,
                          cfg=cfg, opt=opt)


@functools.partial(jax.jit, static_argnames=("cfg",))
def vq_eval_batch(params, vq_states, pack: MinibatchPack, x_b, degrees,
                  cfg: GNNConfig):
    out, _ = vq_forward(params, x_b, None, pack, vq_states, degrees, cfg,
                        inject=False)
    return out


# ---------------------------------------------------------------------------
# device-resident mini-batched inference (DESIGN.md section 11)
# ---------------------------------------------------------------------------

# Bumped at TRACE time of the jitted inference entry points.  The
# compile-count contract tests and the repro.analysis jaxpr pass pin the
# executor's promise on it: one inference pass costs n_layers layer traces
# (and a serve step one trace), independent of the batch count S and of
# whether the batch size divides n.  Re-exported here for compatibility;
# the counter itself lives in the shared telemetry module.
INFER_TRACE_COUNT = trace_count.INFER_TRACE_COUNT


def _vq_infer_layer_body(params_l, vq_state: LayerVQState, plan: EpochPlan,
                         perm, slot_mask, acts, degrees, *,
                         cfg: GNNConfig, layer: int) -> jax.Array:
    """One layer's sweep over ALL S batches as a single ``lax.scan``
    (trace-level).  Each step derives its pack in-jit from the pack-once
    plan (``plan_batch``), runs the probe-free codeword forward, and
    scatters the batch's output into the [n+1, f_out] activation table
    carried through the scan (in-place on device; the sacrificial row n
    absorbs wrap-padded tail slots so a node duplicated by the padding
    keeps its real-slot output).
    """
    INFER_TRACE_COUNT.bump("layer")
    bk = BACKBONES[cfg.backbone]
    cb_cfg = cfg.layer_codebook_cfg()
    fi, fo = _layer_out_dims(cfg)[layer]
    act = _act_for_layer(cfg, layer)
    n = plan.n

    def body(out, xs):
        bids, smask = xs
        pack = plan_batch(plan, bids, smask)
        y = bk.vq_apply(params_l, acts[bids], None, pack, vq_state,
                        degrees, cb_cfg, act, fi, fo, inject=False)
        dst = jnp.where(smask > 0, bids, n).astype(jnp.int32)
        return out.at[dst].set(y), None

    out0 = jnp.zeros((n + 1, fo), acts.dtype)
    out, _ = jax.lax.scan(body, out0, (perm, slot_mask))
    return out[:n]


@functools.partial(jax.jit,
                   static_argnames=("cfg", "layer", "inductive"))
def vq_infer_layer(params_l, vq_state: LayerVQState, plan: EpochPlan,
                   perm: jax.Array, slot_mask: jax.Array, acts: jax.Array,
                   degrees, cfg: GNNConfig, layer: int,
                   inductive: bool = False
                   ) -> tuple[jax.Array, LayerVQState]:
    """Layer-locked mini-batched codeword inference for ONE layer, entirely
    on device: one jit call scanning all S batches (DESIGN.md section 11).

    perm:       [S, b] int  node ids per batch (``inference_slices``)
    slot_mask:  [S, b]      0 on wrap-padded tail slots (outputs discarded)
    acts:       [n, f_in]   every node's layer input (layer l-1 outputs)

    With ``inductive`` the feature-half codeword assignment of EVERY node
    is refreshed from ``acts`` before the sweep (paper Sec. 6: unseen nodes
    get their nearest codeword by feature distance) -- inside the same jit,
    so the inductive path costs zero extra host round-trips.  Returns the
    [n, f_out] output table and the (possibly refreshed) layer state.
    """
    if inductive:
        fi, _ = _layer_out_dims(cfg)[layer]
        assign = cbm.assign_features_only(
            vq_state.codebook, acts, fi, cfg.layer_codebook_cfg())
        vq_state = refresh_assignment(
            vq_state, jnp.arange(plan.n, dtype=jnp.int32), assign)
    out = _vq_infer_layer_body(params_l, vq_state, plan, perm, slot_mask,
                               acts, degrees, cfg=cfg, layer=layer)
    return out, vq_state


def vq_infer_epoch(params: list[Params], vq_states: list[LayerVQState],
                   plan: EpochPlan, perm: jax.Array, slot_mask: jax.Array,
                   x: jax.Array, degrees, cfg: GNNConfig, *,
                   inductive: bool = False
                   ) -> tuple[jax.Array, list[LayerVQState]]:
    """Whole-network layer-synchronous inference on the epoch executor:
    n_layers jit calls total (one ``vq_infer_layer`` scan per layer, so
    layer l+1 sees refreshed layer-l activations -- and, inductively,
    assignments -- for every node).  Compile count is O(n_layers),
    independent of S and of n % batch_size."""
    acts = x
    states = list(vq_states)
    for l in range(cfg.n_layers):
        acts, states[l] = vq_infer_layer(
            params[l], states[l], plan, perm, slot_mask, acts, degrees,
            cfg, l, inductive)
    return acts, states


@functools.partial(jax.jit, static_argnames=("cfg",))
def vq_serve_batch(params, vq_states, plan: EpochPlan, bids: jax.Array,
                   x: jax.Array, degrees, cfg: GNNConfig) -> jax.Array:
    """ONE-compile serving step: all-layer codeword forward for a request
    micro-batch of node ids (launch/serve_gnn.py).

    O(b) work per request -- in-jit ``plan_batch`` + feature-row gather +
    the probe-free ``vq_forward`` with codeword context standing in for
    every out-of-batch neighbor at every layer: no L-hop neighborhood
    expansion (the paper's Sec. 6 inference claim, served).  Duplicate ids
    (request padding / repeated requests) are safe: the node->slot scatter
    keeps one authoritative slot and all duplicate rows compute identical
    outputs.  Note the regime difference with :func:`vq_infer_epoch`: the
    serve step feeds layer l+1 with the batch's OWN layer-l outputs (for
    identical batch partitions the two coincide exactly; the executor is
    the layer-locked offline sweep, the serve step the online per-request
    form)."""
    INFER_TRACE_COUNT.bump("serve")
    pack = plan_batch(plan, bids.astype(jnp.int32))
    out, _ = vq_forward(params, x[bids], None, pack, vq_states, degrees,
                        cfg, inject=False)
    return out


# ---------------------------------------------------------------------------
# row-sharded inference / serving bodies (DESIGN.md section 14)
# ---------------------------------------------------------------------------

def _vq_infer_layer_body_sharded(params_l, vq_state: LayerVQState,
                                 plan: EpochPlan, perm, slot_mask, acts,
                                 degrees, *, cfg: GNNConfig, layer: int,
                                 axis_name: str, n_global: int,
                                 compress: bool = False) -> jax.Array:
    """Row-sharded twin of :func:`_vq_infer_layer_body` (shard_map body).

    ``plan``/``acts`` are this shard's row blocks of the padded global
    tables; ``perm``/``slot_mask`` are this shard's slice of the SCAN
    axis -- each shard sweeps S/ndev FULL batches per layer, so every
    batch computes with exact full-batch in-batch positions and the
    result is bit-identical to the replicated single-device executor
    while compute and activation storage both split ndev ways.  Batch
    outputs scatter cross-shard (``shard_scatter_rows``); wrap-padded
    and all-masked (scan-padding) slots are diverted to the sacrificial
    global row ``n_global``, which lives inside the padded table and is
    never read back.  Requires S padded to a multiple of ndev
    (all-masked batches) so the per-step collectives stay lockstep.
    """
    INFER_TRACE_COUNT.bump("layer")
    bk = BACKBONES[cfg.backbone]
    cb_cfg = cfg.layer_codebook_cfg()
    fi, fo = _layer_out_dims(cfg)[layer]
    act = _act_for_layer(cfg, layer)

    def body(out, xs):
        bids, smask = xs
        pack = plan_batch_sharded(plan, bids, axis_name, smask)
        x_b = gather_from_shards(acts, bids, axis_name, compress=compress)
        y = bk.vq_apply(params_l, x_b, None, pack, vq_state,
                        degrees, cb_cfg, act, fi, fo, inject=False)
        dst = jnp.where(smask > 0, bids, n_global).astype(jnp.int32)
        return shard_scatter_rows(out, dst, y, axis_name), None

    out0 = jnp.zeros((acts.shape[0], fo), acts.dtype)
    out, _ = jax.lax.scan(body, out0, (perm, slot_mask))
    return out


def _vq_infer_layer_sharded(params_l, vq_state: LayerVQState,
                            plan: EpochPlan, perm, slot_mask, acts,
                            degrees, *, cfg: GNNConfig, layer: int,
                            axis_name: str, n_global: int,
                            inductive: bool = False,
                            compress: bool = False
                            ) -> tuple[jax.Array, LayerVQState]:
    """Sharded twin of :func:`vq_infer_layer` (trace-level; the jit'd
    shard_map wrapper lives in ``distributed/data_parallel.py``).  The
    inductive refresh assigns each shard's LOCAL activation rows
    (``assign_features_only`` is purely row-wise: it whitens with the
    codebook's stored moments), all-gathers the per-shard assignment
    stripes into the replicated global table, and slices off the pad
    rows -- every shard derives the identical refreshed state, keeping
    the replicated-codebook invariant."""
    if inductive:
        fi, _ = _layer_out_dims(cfg)[layer]
        assign_loc = cbm.assign_features_only(
            vq_state.codebook, acts, fi, cfg.layer_codebook_cfg())
        a = jax.lax.all_gather(assign_loc, axis_name)  # [ndev, nb, n_loc]
        assign = a.transpose(1, 0, 2).reshape(a.shape[1], -1)[:, :n_global]
        vq_state = refresh_assignment(
            vq_state, jnp.arange(n_global, dtype=jnp.int32), assign)
    out = _vq_infer_layer_body_sharded(
        params_l, vq_state, plan, perm, slot_mask, acts, degrees, cfg=cfg,
        layer=layer, axis_name=axis_name, n_global=n_global,
        compress=compress)
    return out, vq_state


def _vq_serve_body_sharded(params, vq_states, plan: EpochPlan,
                           bids: jax.Array, x, degrees, cfg: GNNConfig, *,
                           axis_name: str, compress: bool = False
                           ) -> jax.Array:
    """Sharded twin of :func:`vq_serve_batch` (shard_map body): the
    request ids arrive REPLICATED, each shard cross-shard-gathers the
    batch's plan rows and feature rows from its block and then runs the
    identical full-batch probe-free forward -- exact parity with the
    unsharded serve step, with the mesh buying graph-state capacity
    (the O(b*L) serve compute is replicated; serve batches are tiny
    next to the [n, D] state this path exists to split)."""
    INFER_TRACE_COUNT.bump("serve")
    bids = bids.astype(jnp.int32)
    pack = plan_batch_sharded(plan, bids, axis_name)
    x_b = gather_from_shards(x, bids, axis_name, compress=compress)
    out, _ = vq_forward(params, x_b, None, pack, vq_states, degrees,
                        cfg, inject=False)
    return out


# ---------------------------------------------------------------------------
# full-graph / subgraph train steps (oracle + sampling baselines)
# ---------------------------------------------------------------------------

def _full_step_body(params, opt_state, x, ops_: FullGraphOperands,
                    labels, loss_mask, cfg: GNNConfig, opt: Optimizer,
                    neg_pairs=None, pos_pairs=None, pair_mask=None):
    """One exact-message-passing train step, trace-level -- the ONE
    implementation behind the jit'd per-(sub)graph entry point AND the
    ``lax.scan`` sampler epoch executor, mirroring ``_vq_step_body``."""
    def loss_fn(params):
        out = full_forward(params, x, ops_, cfg)
        if cfg.task == "node":
            return node_loss(out, labels, cfg.multilabel, loss_mask)
        return link_loss(out, pos_pairs, neg_pairs, pair_mask)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    with jax.named_scope("optimizer"):
        new_params, new_opt = opt.update(grads, opt_state, params)
    return new_params, new_opt, loss


@functools.partial(jax.jit, static_argnames=("cfg", "opt"))
def full_train_step(params, opt_state, x, ops_: FullGraphOperands,
                    labels, loss_mask, cfg: GNNConfig, opt: Optimizer,
                    neg_pairs=None, pos_pairs=None, pair_mask=None):
    """loss_mask: [n] float weights over nodes (mask-based so padded
    subgraphs of a bucketed static size reuse one compilation)."""
    return _full_step_body(params, opt_state, x, ops_, labels, loss_mask,
                           cfg, opt, neg_pairs=neg_pairs,
                           pos_pairs=pos_pairs, pair_mask=pair_mask)


@functools.partial(jax.jit, static_argnames=("cfg", "opt"),
                   donate_argnames=("params", "opt_state"))
def sampler_train_epoch(params, opt_state, splan, x, labels,
                        cfg: GNNConfig, opt: Optimizer):
    """One sampling-baseline epoch entirely on device (DESIGN.md sec. 12):
    ``lax.scan`` of the exact-subgraph step over the S stacked batches of a
    :class:`~repro.graph.batching.SamplerEpochPlan`, with ``(params,
    opt_state)`` carried in donated buffers -- the sampler-side twin of
    ``vq_train_epoch``, so VQ-vs-sampling comparisons are
    executor-vs-executor.

    Each step slices its padded subgraph operands out of the plan, gathers
    the batch's features/labels from the full [n, ...] device tables
    in-jit, and runs the shared ``_full_step_body``.  Padding rows (empty
    neighbor lists, loss weight 0) gather node 0's row; they feed no
    messages into real rows and carry no loss, so their cotangents vanish
    identically.  Node task only (link pair mining is host-side).

    Returns (params, opt_state, losses [S]).
    """
    assert cfg.task == "node", "sampler epoch executor is node-task only"

    def body(carry, xs):
        params, ost = carry
        nid, nbr, nmask, deg, lmask = xs
        ops_ = FullGraphOperands(nbr_ids=nbr, nbr_mask=nmask, degrees=deg)
        params, ost, loss = _full_step_body(
            params, ost, x[nid], ops_, labels[nid], lmask, cfg, opt)
        return (params, ost), loss

    (params, opt_state), losses = jax.lax.scan(
        body, (params, opt_state),
        (splan.node_ids, splan.nbr_ids, splan.nbr_mask, splan.degrees,
         splan.loss_mask))
    return params, opt_state, losses


@functools.partial(jax.jit, static_argnames=("cfg",))
def full_predict(params, x, ops_: FullGraphOperands, cfg: GNNConfig):
    return full_forward(params, x, ops_, cfg)
