"""GNN experiment harness: the paper's training regimes on one API.

  train_full     -- "Full-Graph" oracle rows of Table 4
  train_vq       -- VQ-GNN (Alg. 1), mini-batched, streaming codebooks
  train_sampler  -- NS-SAGE / LABOR / Cluster-GCN / GraphSAINT-RW
                    baselines, on the sampler epoch executor by default
                    (pre-sample an epoch, pack once, one lax.scan --
                    DESIGN.md sec. 12; REPRO_SAMPLER_EXECUTOR=0 falls back
                    to the per-batch host loop)
  train_hybrid   -- VQ/sampling hybrid: sampler-expanded batches on the
                    UNCHANGED VQ epoch executor (exact messages inside the
                    sampled set, VQ context outside)
  train_scenario -- one front for every scale method (the scenario-matrix
                    registry; REPRO_SCALE_METHOD picks the default)
  vq_inference   -- mini-batched codeword inference (the paper's 4x
                    inference speedup claim; supports the inductive setting
                    via feature-half assignment).  Device-resident: one
                    jitted lax.scan per layer over static wrap-padded
                    batches (models.gnn.vq_infer_epoch, DESIGN.md sec. 11);
                    the serving front is launch/serve_gnn.py

Each returns a result dict with metric history, per-epoch loss traces,
wall-times, and the memory/message accounting used by benchmarks
(Table 2/3 analogues).
"""
from __future__ import annotations

import os
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codebook as cbm
from repro.core.conv import refresh_assignment
from repro.distributed.quantization import dtype_nbits
from repro.kernels import ops as kops
from repro.distributed.data_parallel import ShardedGraphState, \
    vq_train_epoch_dp, vq_train_epoch_sharded
from repro.graph.batching import (build_epoch_plan, epoch_slices,
                                  full_operands, inference_slices,
                                  make_pack, minibatch_stream,
                                  pack_sampler_epoch, pad_bucket,
                                  plan_batch, subgraph_operands)
from repro.graph.batching import PAD_BUCKET_CAP  # noqa: F401  (re-export)
from repro.graph.sampling import (SAMPLER_METHODS, hybrid_epoch_batches,
                                  partition_graph, sample_epoch)
from repro.graph.structure import Graph
from repro.models.gnn import (GNNConfig, _act_for_layer, _layer_out_dims,
                              full_predict, full_train_step, hits_at_k,
                              init_gnn, init_vq_states, node_metric,
                              sampler_train_epoch, vq_infer_epoch,
                              vq_train_epoch, vq_train_step)
from repro.nn.gnn_layers import BACKBONES
from repro.train.optimizer import adam, rmsprop

# canonical implementation moved to repro.graph.batching (the packer is its
# natural home); re-exported here for the existing import sites
_pad_bucket = pad_bucket


def _eval_full(params, g, cfg, x, ops):
    out = full_predict(params, x, ops, cfg)
    labels = jnp.asarray(g.labels)
    return {
        "val": float(node_metric(out[g.val_idx], labels[g.val_idx],
                                 cfg.multilabel)),
        "test": float(node_metric(out[g.test_idx], labels[g.test_idx],
                                  cfg.multilabel)),
    }


def _eval_link(params, g, cfg, x, ops):
    emb = np.asarray(full_predict(params, x, ops, cfg))

    def scores(pairs):
        return (emb[pairs[:, 0]] * emb[pairs[:, 1]]).sum(-1)
    return {
        "val": hits_at_k(scores(g.val_edges), scores(g.val_neg_edges)),
        "test": hits_at_k(scores(g.test_edges), scores(g.test_neg_edges)),
    }


def _evaluate(params, g, cfg, x, ops):
    return (_eval_link if cfg.task == "link" else _eval_full)(
        params, g, cfg, x, ops)


# ---------------------------------------------------------------------------
# memory accounting (paper Table 3: bytes materialized per mini-batch)
# ---------------------------------------------------------------------------

def vq_batch_bytes(b: int, deg: int, f: int, L: int, k: int,
                   f_prod: int = 4, f_grad: Optional[int] = None,
                   precision: Optional[str] = None) -> int:
    """VQ-GNN per-batch device bytes: batch features/acts + packed neighbor
    lists + codebooks + reconstructed context messages.

    The codebook term uses the codebook's ACTUAL ``branch_layout`` (largest
    common divisor of the feature/grad widths capped by both block-size
    budgets) so the Table 3 accounting matches what ``init_codebook``
    allocates: the naive ``f // f_prod`` branch count disagrees whenever
    ``f`` is not divisible by ``f_prod`` or the layout is capped by the
    gradient width (e.g. any transformer-backbone full-width codebook).
    ``f_grad`` defaults to ``f`` (the Z-level gradient codewords of the
    fixed-convolution backbones).

    ``precision`` (a :data:`repro.kernels.ops.PRECISIONS` tier; default
    fp32 accounting) sizes the per-layer codeword tables the kernels
    actually read under that tier -- e.g. int8/fp8 tables at 8 bits plus
    their f32 per-channel scale rows -- via the shared
    :func:`~repro.distributed.quantization.dtype_nbits`, so sub-byte
    operand widths stay exact (bit-accumulated, rounded up once)."""
    f_grad = f if f_grad is None else f_grad
    n_branches, fb, gb = cbm.branch_layout(f, f_grad, f_prod)
    pack = b * deg * 4 * 6                     # ids/mask/pos x2 directions
    acts = L * b * f * 4
    cw_dtype = None if precision is None \
        else kops.precision_codeword_dtype(precision)
    if cw_dtype is None:
        books = L * n_branches * k * (fb + gb) * 4
    else:
        bits = L * n_branches * k * (fb + gb) * dtype_nbits(cw_dtype)
        books = (bits + 7) // 8 \
            + L * n_branches * (fb + gb) * 4   # f32 per-channel scales
    recon = b * deg * f * 4                    # reconstructed neighbors
    return pack + acts + books + recon


def subgraph_batch_bytes(n_sub: int, m_sub: int, f: int, L: int) -> int:
    """Sampler per-batch bytes: subgraph features+acts+edges."""
    return n_sub * f * 4 * L + m_sub * 2 * 8


def messages_per_batch_vq(g: Graph, b: int) -> float:
    """Paper Sec. 4: VQ preserves ALL messages to the batch: b*d of them."""
    return b * float(g.m) / g.n


# ---------------------------------------------------------------------------
# trainers
# ---------------------------------------------------------------------------

def train_full(g: Graph, cfg: GNNConfig, *, epochs: int, lr: float = 1e-2,
               seed: int = 0, eval_every: int = 10) -> dict:
    ops = full_operands(g)
    x = jnp.asarray(g.features)
    labels = jnp.asarray(g.labels)
    params = init_gnn(jax.random.PRNGKey(seed), cfg)
    opt = adam(lr)
    ost = opt.init(params)
    hist, t0 = [], time.time()
    rng = np.random.default_rng(seed)
    mask_np = np.zeros(g.n, np.float32)
    mask_np[g.train_idx] = 1.0
    mask = jnp.asarray(mask_np)
    for ep in range(epochs):
        if cfg.task == "link":
            e = g.train_edges
            neg = np.stack([rng.integers(0, g.n, len(e)),
                            rng.integers(0, g.n, len(e))], 1)
            params, ost, loss = full_train_step(
                params, ost, x, ops, labels, mask, cfg,
                opt, neg_pairs=jnp.asarray(neg), pos_pairs=jnp.asarray(e))
        else:
            params, ost, loss = full_train_step(
                params, ost, x, ops, labels, mask, cfg, opt)
        if (ep + 1) % eval_every == 0 or ep == epochs - 1:
            m = _evaluate(params, g, cfg, x, ops)
            hist.append({"epoch": ep + 1, "time": time.time() - t0, **m})
    return {"history": hist, "final": hist[-1], "params": params,
            "mem_bytes": g.n * g.f * 4 * cfg.n_layers + g.m * 16}


def train_vq(g: Graph, cfg: GNNConfig, *, epochs: int, batch_size: int,
             lr: float = 3e-3, seed: int = 0, eval_every: int = 10,
             deg_cap: Optional[int] = None, mesh=None,
             shard_graph: bool = False,
             batch_fn: Optional[Callable] = None) -> dict:
    """VQ-GNN training (Alg. 1).

    Node-task training runs on the device-resident epoch executor by
    default: the graph is packed ONCE into an ``EpochPlan`` and each epoch
    is one ``vq_train_epoch`` call (``lax.scan`` over the stacked batches,
    DESIGN.md section 9).  ``REPRO_EPOCH_EXECUTOR=0`` falls back to the
    host-driven per-step loop (debugging; also the link-task path, whose
    per-batch pair mining is host-side).  Both paths consume identical
    wrap-padded batches from the same rng stream, so they match
    numerically on a fixed seed.
    ``mesh`` (optional, a 1-axis "data" ``Mesh``) runs the epoch under
    ``shard_map`` data parallelism (``vq_train_epoch_dp``).
    ``shard_graph`` (requires ``mesh``) additionally row-shards every
    node-indexed table (EpochPlan / features / labels / train mask) over
    the mesh (``vq_train_epoch_sharded``, DESIGN.md section 14), making
    mesh size a graph-capacity knob; value-identical to the replicated
    DP run at the same mesh size.
    ``batch_fn`` (optional, node task) overrides the per-epoch batch
    construction: ``batch_fn(rng) -> (ids [S, b'], slot_mask [S, b'])``
    with distinct ids per row -- the hook the VQ/sampling hybrid uses to
    feed sampler-expanded batches through the unchanged executor
    (``train_hybrid``, DESIGN.md section 12).
    """
    ops = full_operands(g)
    x = jnp.asarray(g.features)
    labels = jnp.asarray(g.labels)
    params = init_gnn(jax.random.PRNGKey(seed), cfg)
    vq = init_vq_states(jax.random.PRNGKey(seed + 1), cfg, g.n)
    opt = rmsprop(lr)   # paper App. F: RMSprop for VQ-GNN
    ost = opt.init(params)
    rng = np.random.default_rng(seed)
    train_mask = np.zeros(g.n, np.float32)
    train_mask[g.train_idx] = 1.0

    use_epoch = (cfg.task == "node"
                 and os.environ.get("REPRO_EPOCH_EXECUTOR", "1") != "0")
    if batch_fn is not None and cfg.task != "node":
        raise ValueError("batch_fn= is a node-task batch-construction "
                         "hook (link pair mining is per-batch host work)")
    if batch_fn is not None and mesh is not None:
        # the dp path's per-shard split assumes the fixed epoch_slices
        # batch width; sampler-widened rows would break its divisibility
        # contract silently
        raise ValueError("batch_fn= and mesh= are mutually exclusive")
    if mesh is not None and not use_epoch:
        # never fall back to single-device training silently when the
        # caller explicitly asked for data parallelism
        raise ValueError(
            "mesh= (shard_map data parallelism) requires the epoch "
            "executor: node task and REPRO_EPOCH_EXECUTOR != 0")
    if shard_graph and mesh is None:
        raise ValueError(
            "shard_graph=True row-shards the node tables over a mesh -- "
            "pass mesh= (graph_dp_mesh) as well")
    if mesh is not None:
        # surface epoch_slices' pool clamp here, against the caller's
        # numbers, instead of letting the dp divisibility check report a
        # batch size the caller never passed
        eff_b = min(batch_size, g.n)
        nd = mesh.shape["data"]
        if eff_b % nd != 0:
            raise ValueError(
                f"effective batch size {eff_b} (batch_size={batch_size} "
                f"clamped to the {g.n}-node pool) is not divisible by the "
                f"data mesh size {nd} -- each mesh device trains on "
                f"b/{nd} rows of every batch"
                + (f"; with shard_graph it also owns a contiguous "
                   f"1/{nd} row block of the node tables (padded to a "
                   f"multiple of {nd} rows internally), so only the "
                   f"batch size needs adjusting: pick a multiple of {nd}"
                   if shard_graph else ""))
    plan = build_epoch_plan(g, deg_cap, full_ops=ops) if use_epoch else None
    tm = jnp.asarray(train_mask)
    sstate = None
    if shard_graph:
        # built once per run, like the plan: every node-indexed table is
        # padded + row-placed here and the epoch loop ships only [S, b]
        # id arrays.  ops/x stay host/replicated for _evaluate -- the
        # capacity story is measured on the executor's operands
        # (bench_epoch's graph_state_ratio), eval is offline.
        sstate = ShardedGraphState(mesh, plan, x, ops.degrees,
                                   labels=labels, train_mask=tm)

    hist, t0 = [], time.time()
    vq_errs = None
    losses_tr: list = []
    for ep in range(epochs):
        if use_epoch:
            ids, smask = (batch_fn(rng) if batch_fn is not None else
                          epoch_slices(rng.permutation(np.arange(g.n)),
                                       batch_size))
            ids_d = jnp.asarray(ids.astype(np.int32))
            smask_d = jnp.asarray(smask)
            if sstate is not None:
                params, vq, ost, losses, errs = vq_train_epoch_sharded(
                    sstate, params, vq, ost, ids_d, smask_d, cfg, opt)
            elif mesh is not None:
                params, vq, ost, losses, errs = vq_train_epoch_dp(
                    mesh, params, vq, ost, plan, ids_d, smask_d, x,
                    labels, tm, ops.degrees, cfg, opt)
            else:
                params, vq, ost, losses, errs = vq_train_epoch(
                    params, vq, ost, plan, ids_d, smask_d, x, labels, tm,
                    ops.degrees, cfg, opt)
            losses_tr.append(np.asarray(losses))
            if errs.shape[0]:
                vq_errs = errs[-1]
        elif cfg.task == "node":
            # host-driven per-step loop over the SAME batches the executor
            # would scan (epoch_slices of one permutation draw, or the
            # caller's batch_fn) -- numerically identical to the former
            # minibatch_stream fallback, but batch_fn-aware so hybrid
            # parity can be checked executor-off too
            ids, smask = (batch_fn(rng) if batch_fn is not None else
                          epoch_slices(rng.permutation(np.arange(g.n)),
                                       batch_size))
            for s in range(ids.shape[0]):
                bidx = np.asarray(ids[s])
                pack = make_pack(g, bidx, deg_cap, slot_mask=smask[s])
                lm = train_mask[bidx] * np.asarray(smask[s])
                params, vq, ost, loss, _, vq_errs = vq_train_step(
                    params, vq, ost, pack, x[bidx], labels[bidx],
                    ops.degrees, cfg, opt, loss_mask=jnp.asarray(lm))
        else:
            # link task: per-batch pair mining stays host-side
            for pack in minibatch_stream(g, batch_size, rng,
                                         deg_cap=deg_cap):
                bidx = np.asarray(pack.batch_ids)
                # intra-batch positive pairs + random negatives, mined
                # over the REAL slots only: wrap-padded tail slots are
                # nodes already supervised earlier in the epoch
                # (MinibatchPack.slot_mask contract)
                slots = np.arange(len(bidx))
                if pack.slot_mask is not None:
                    slots = slots[np.asarray(pack.slot_mask) > 0]
                inb = np.full(g.n, -1)
                inb[bidx[slots]] = slots
                e = g.train_edges
                sel = (inb[e[:, 0]] >= 0) & (inb[e[:, 1]] >= 0)
                pos = np.stack([inb[e[sel, 0]], inb[e[sel, 1]]], 1)
                if len(pos) < 2:
                    pos = np.zeros((2, 2), np.int64)
                neg = slots[rng.integers(0, len(slots), pos.shape)]
                params, vq, ost, loss, _, vq_errs = vq_train_step(
                    params, vq, ost, pack, x[bidx], labels[bidx],
                    ops.degrees, cfg, opt, pos_pairs=jnp.asarray(pos),
                    neg_pairs=jnp.asarray(neg))
        if (ep + 1) % eval_every == 0 or ep == epochs - 1:
            # evaluation runs unsharded beside x: a jit over mesh-
            # replicated params would ask XLA to partition the full-graph
            # forward's Pallas kernels, which Mosaic refuses on a TPU
            m = _evaluate(params if mesh is None else
                          jax.device_put(params, x.sharding), g, cfg, x, ops)
            # whitened-space VQ relative error of the last batch, emitted by
            # the fused update kernel (no extra distance computation); stays
            # unset when the epoch had no batch (empty node pool)
            if vq_errs is not None:
                m["vq_err"] = float(jnp.mean(vq_errs))
            hist.append({"epoch": ep + 1, "time": time.time() - t0, **m})
    deg = deg_cap or g.max_degree()
    # hidden-width layer model: the gradient codewords live at the level
    # the backbone probes (f_out for fixed convs, f_out + heads for GAT),
    # so the codebook term must use the backbone's f_grad -- defaulting it
    # to cfg.hidden re-creates the naive-branch-count accounting bug for
    # every backbone where f_grad != f
    fi0, fo0 = _layer_out_dims(cfg)[0]
    f_grad = BACKBONES[cfg.backbone].f_grad(fi0, fo0, heads=cfg.heads)
    return {"history": hist, "final": hist[-1], "params": params,
            "vq_states": vq, "losses": losses_tr,
            "mem_bytes": vq_batch_bytes(
                batch_size, deg, cfg.hidden, cfg.n_layers, cfg.codebook.k,
                f_prod=cfg.layer_codebook_cfg().f_prod, f_grad=f_grad,
                precision=kops.kernel_precision()),
            "messages": messages_per_batch_vq(g, batch_size)}


def train_sampler(g: Graph, cfg: GNNConfig, method: str, *, epochs: int,
                  batch_size: int, lr: float = 1e-3, seed: int = 0,
                  eval_every: int = 10, fanout: int = 5,
                  walk_length: int = 3, n_parts: int = 32,
                  fanouts: Optional[list] = None,
                  parts_per_batch: Optional[int] = None) -> dict:
    """Sampling-baseline training; ``method`` in ``SAMPLER_METHODS``
    (ns-sage / labor / cluster-gcn / graphsaint-rw).

    Every epoch is pre-sampled on host into ONE batch list
    (``sample_epoch``), then by default runs on the device-resident
    sampler epoch executor: ``pack_sampler_epoch`` stacks the induced
    subgraphs into a padded [S, P, ...] plan and
    ``models.gnn.sampler_train_epoch`` scans the exact-subgraph step over
    it -- the same pack-once/``lax.scan`` regime VQ training rides, so the
    paper's Table 2/4 comparison is executor-vs-executor (DESIGN.md
    section 12).  ``REPRO_SAMPLER_EXECUTOR=0`` falls back to the per-batch
    host loop (debugging; also the link-task path, whose pair mining is
    host-side).  Both paths consume the SAME pre-sampled batches for a
    fixed seed, and padding rows are message- and loss-neutral (empty
    neighbor lists, loss weight 0 under the masked-mean loss), so they
    match numerically.

    ``fanouts`` (per-layer list) overrides the uniform ``fanout``;
    ``parts_per_batch`` overrides the Cluster-GCN default
    ``max(1, n_parts // 8)``.
    """
    if method not in SAMPLER_METHODS:
        raise ValueError(f"unknown sampler {method!r}; expected one of "
                         f"{SAMPLER_METHODS}")
    ops = full_operands(g)
    x = jnp.asarray(g.features)
    labels_np = g.labels
    labels = jnp.asarray(labels_np)
    params = init_gnn(jax.random.PRNGKey(seed), cfg)
    opt = adam(lr)
    ost = opt.init(params)
    rng = np.random.default_rng(seed)
    part = partition_graph(g, n_parts, rng) if method == "cluster-gcn" \
        else None
    fanouts = list(fanouts) if fanouts is not None \
        else [fanout] * cfg.n_layers
    ppb = parts_per_batch if parts_per_batch is not None \
        else max(1, n_parts // 8)
    deg_cap = g.max_degree()
    use_exec = (cfg.task == "node"
                and os.environ.get("REPRO_SAMPLER_EXECUTOR", "1") != "0")
    hist, t0 = [], time.time()
    losses_tr: list = []
    max_sub, max_msg = 0, 0
    max_pairs = 4096

    for ep in range(epochs):
        batches = sample_epoch(g, method, batch_size=batch_size, rng=rng,
                               fanouts=fanouts, walk_length=walk_length,
                               partition=part, parts_per_batch=ppb)
        for src, _, nodes, _, _ in batches:
            max_sub = max(max_sub, len(nodes))
            max_msg = max(max_msg, len(src))
        if use_exec:
            splan = pack_sampler_epoch(batches, deg_cap)
            params, ost, losses = sampler_train_epoch(
                params, ost, splan, x, labels, cfg, opt)
            losses_tr.append(np.asarray(losses))
        else:
            ep_losses = []
            for src, dst, nodes, seed_pos, seed_w in batches:
                n_real = len(nodes)
                n_pad = _pad_bucket(n_real)
                sub_ops = subgraph_operands(src, dst, n_pad, deg_cap)
                xs = jnp.zeros((n_pad, g.f), jnp.float32
                               ).at[:n_real].set(x[nodes])
                lpad = np.zeros((n_pad,) + labels_np.shape[1:],
                                labels_np.dtype)
                lpad[:n_real] = labels_np[nodes]
                ls = jnp.asarray(lpad)
                mask = np.zeros(n_pad, np.float32)
                mask[seed_pos] = seed_w
                if cfg.task == "link":
                    inb = np.full(g.n, -1)
                    inb[nodes] = np.arange(n_real)
                    e = g.train_edges
                    sel = (inb[e[:, 0]] >= 0) & (inb[e[:, 1]] >= 0)
                    pos = np.stack([inb[e[sel, 0]], inb[e[sel, 1]]], 1)
                    if len(pos) < 2:
                        continue
                    pos = pos[:max_pairs]
                    pmask = np.zeros(max_pairs, np.float32)
                    pmask[:len(pos)] = 1.0
                    pos = np.concatenate(
                        [pos,
                         np.zeros((max_pairs - len(pos), 2), np.int64)])
                    neg = rng.integers(0, n_real, pos.shape)
                    params, ost, loss = full_train_step(
                        params, ost, xs, sub_ops, ls, jnp.asarray(mask),
                        cfg, opt, neg_pairs=jnp.asarray(neg),
                        pos_pairs=jnp.asarray(pos),
                        pair_mask=jnp.asarray(pmask))
                else:
                    params, ost, loss = full_train_step(
                        params, ost, xs, sub_ops, ls, jnp.asarray(mask),
                        cfg, opt)
                ep_losses.append(float(loss))
            losses_tr.append(np.asarray(ep_losses, np.float32))
        if (ep + 1) % eval_every == 0 or ep == epochs - 1:
            m = _evaluate(params, g, cfg, x, ops)
            hist.append({"epoch": ep + 1, "time": time.time() - t0, **m})
    return {"history": hist, "final": hist[-1], "params": params,
            "losses": losses_tr,
            "mem_bytes": subgraph_batch_bytes(max_sub, max_msg, cfg.hidden,
                                              cfg.n_layers),
            "messages": max_msg * cfg.n_layers}


def train_hybrid(g: Graph, cfg: GNNConfig, *, epochs: int, batch_size: int,
                 lr: float = 3e-3, seed: int = 0, eval_every: int = 10,
                 deg_cap: Optional[int] = None, fanout: int = 5,
                 fanouts: Optional[list] = None,
                 n_ctx: Optional[int] = None) -> dict:
    """VQ/sampling hybrid (Message Invariance, DESIGN.md section 12):
    LABOR-expanded batches on the UNCHANGED VQ executor.

    Each batch is ``batch_size`` loss-bearing seeds plus up to ``n_ctx``
    of their sampled neighbors as loss-masked context slots
    (``hybrid_epoch_batches``).  No model change is involved: ``vq_apply``
    already routes messages from in-batch neighbors through the exact
    intra-batch SpMM (``nbr_pos >= 0``) and only the remaining
    out-of-batch term through the codeword context kernel, so widening the
    batch with sampled neighbors converts exactly those messages from
    VQ-approximated to exact.  ``n_ctx=0`` degenerates to plain VQ
    training bit-for-bit; ``n_ctx >= n - batch_size`` makes every message
    exact (the full-graph regime at batch granularity).
    """
    if cfg.task != "node":
        raise ValueError("train_hybrid is node-task only (the hybrid is a "
                         "batch-construction strategy for Alg. 1)")
    fo = list(fanouts) if fanouts is not None else [fanout] * cfg.n_layers
    return train_vq(
        g, cfg, epochs=epochs, batch_size=batch_size, lr=lr, seed=seed,
        eval_every=eval_every, deg_cap=deg_cap,
        batch_fn=lambda rng: hybrid_epoch_batches(g, batch_size, fo, rng,
                                                  n_ctx=n_ctx))


SCALE_METHODS = ("full", "vq", "ns_sage", "labor", "cluster", "saint",
                 "hybrid")

_SAMPLER_OF = {"ns_sage": "ns-sage", "labor": "labor",
               "cluster": "cluster-gcn", "saint": "graphsaint-rw"}


def train_scenario(g: Graph, cfg: GNNConfig, method: Optional[str] = None,
                   *, epochs: int, batch_size: int, seed: int = 0,
                   eval_every: int = 10, lr: Optional[float] = None,
                   **knobs) -> dict:
    """One front for every scale method of the scenario matrix.

    ``method`` is one of ``SCALE_METHODS`` (full / vq / ns_sage / labor /
    cluster / saint / hybrid); when None it comes from the
    ``REPRO_SCALE_METHOD`` env knob (default "vq").  Per-method tuning
    knobs are read from the environment when not passed explicitly:
    ``REPRO_SAMPLER_FANOUT``, ``REPRO_WALK_LENGTH``, ``REPRO_N_PARTS``,
    ``REPRO_HYBRID_CTX``.  Extra ``knobs`` are forwarded to the
    underlying trainer.
    """
    method = method or os.environ.get("REPRO_SCALE_METHOD", "vq")
    if method not in SCALE_METHODS:
        raise ValueError(f"unknown scale method {method!r}; expected one "
                         f"of {SCALE_METHODS}")

    def env_int(name, default):
        return int(os.environ.get(name, default))

    if method == "full":
        return train_full(g, cfg, epochs=epochs, lr=lr or 1e-2, seed=seed,
                          eval_every=eval_every, **knobs)
    if method == "vq":
        return train_vq(g, cfg, epochs=epochs, batch_size=batch_size,
                        lr=lr or 3e-3, seed=seed, eval_every=eval_every,
                        **knobs)
    if method == "hybrid":
        knobs.setdefault("fanout", env_int("REPRO_SAMPLER_FANOUT", 5))
        knobs.setdefault("n_ctx", env_int("REPRO_HYBRID_CTX", batch_size))
        return train_hybrid(g, cfg, epochs=epochs, batch_size=batch_size,
                            lr=lr or 3e-3, seed=seed,
                            eval_every=eval_every, **knobs)
    knobs.setdefault("fanout", env_int("REPRO_SAMPLER_FANOUT", 5))
    knobs.setdefault("walk_length", env_int("REPRO_WALK_LENGTH", 3))
    knobs.setdefault("n_parts", env_int("REPRO_N_PARTS", 32))
    return train_sampler(g, cfg, _SAMPLER_OF[method], epochs=epochs,
                         batch_size=batch_size, lr=lr or 1e-3, seed=seed,
                         eval_every=eval_every, **knobs)


# ---------------------------------------------------------------------------
# VQ mini-batched inference (paper Sec. 6 inference speedup + inductive)
# ---------------------------------------------------------------------------

def vq_inference(params, vq_states, g: Graph, cfg: GNNConfig,
                 batch_size: int, *, inductive: bool = False) -> np.ndarray:
    """Layer-synchronous mini-batched inference using codeword context.

    Runs on the device-resident inference executor by default
    (``models.gnn.vq_infer_epoch``, DESIGN.md section 11): the graph is
    packed ONCE into an ``EpochPlan`` (aliasing ``full_operands``' in-edge
    tables), the node set is split into static wrap-padded [S, b] batches
    (``inference_slices``), and each layer's sweep over all S batches is
    one jitted ``lax.scan`` scattering outputs into the device-resident
    [n, f] activation table.  XLA compiles O(n_layers) executables --
    independent of S and of ``g.n % batch_size`` (the pre-executor path
    was fully eager, one dispatch per (batch, layer), with a ragged tail
    batch and a host concatenate per layer).

    ``REPRO_INFER_EXECUTOR=0`` falls back to the eager per-batch loop
    (debugging); both paths traverse identical wrap-padded batches and
    write only real slots, so they agree to float tolerance.

    Inductive extra step (paper Sec. 6): unseen nodes get their codeword
    assignment from the *feature half* of the layer's codebook before the
    layer executes -- inside the jitted layer sweep on the executor path.
    """
    ops = full_operands(g)
    x = jnp.asarray(g.features)
    plan = build_epoch_plan(g, full_ops=ops)
    ids, smask = inference_slices(g.n, batch_size)
    perm = jnp.asarray(ids.astype(np.int32))
    sm = jnp.asarray(smask)

    if os.environ.get("REPRO_INFER_EXECUTOR", "1") != "0":
        acts, _ = vq_infer_epoch(params, vq_states, plan, perm, sm, x,
                                 ops.degrees, cfg, inductive=inductive)
        return np.asarray(acts)
    return eager_inference_loop(params, vq_states, plan, ids, smask, x,
                                ops.degrees, cfg, inductive=inductive)


def eager_inference_loop(params, vq_states, plan, ids: np.ndarray,
                         smask: np.ndarray, x, degrees, cfg: GNNConfig, *,
                         inductive: bool = False) -> np.ndarray:
    """The pre-executor inference regime: zero jit, one eager ``vq_apply``
    dispatch per (batch, layer), a host round-trip per layer -- on the
    same wrap-padded batches with the same real-slot-only writes as the
    executor, so the two paths agree to float tolerance.  The
    ``REPRO_INFER_EXECUTOR=0`` debugging fallback AND the baseline the
    CI-gated ``benchmarks/bench_inference.py`` comparison times (one
    implementation, no drift between what ships and what is measured)."""
    cb_cfg = cfg.layer_codebook_cfg()
    states = list(vq_states)
    bk = BACKBONES[cfg.backbone]
    n = plan.n
    acts = x
    for l, (fi, fo) in enumerate(_layer_out_dims(cfg)):
        st = states[l]
        if inductive:
            assign = cbm.assign_features_only(st.codebook, acts, fi, cb_cfg)
            st = refresh_assignment(st, jnp.arange(n), assign)
            states[l] = st
        out = np.zeros((n, fo), np.float32)
        for s in range(ids.shape[0]):
            pack = plan_batch(plan, jnp.asarray(ids[s].astype(np.int32)))
            y = bk.vq_apply(params[l], acts[ids[s]], None, pack, st,
                            degrees, cb_cfg, _act_for_layer(cfg, l),
                            fi, fo, inject=False)
            real = smask[s] > 0
            out[ids[s][real]] = np.asarray(y)[real]
        acts = jnp.asarray(out)
    return np.asarray(acts)
