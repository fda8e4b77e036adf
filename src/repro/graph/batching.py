"""Host-side mini-batch packing: Graph -> MinibatchPack (static ELL shapes).

The packer is the only host<->device seam of the graph path: it ships, per
mini-batch, Theta(b * D) integers/floats -- batch features, padded neighbor
ids, in-batch positions -- never O(n).  At pod scale this runs per-host on
its data shard; here it is a numpy routine feeding jit'd steps.

Epoch executor (DESIGN.md section 9): :func:`build_epoch_plan` packs the
WHOLE graph once into device-resident per-node neighbor tables; after that
every epoch's S stacked [S, b, D] batches are derived *in-jit* from a node
permutation by :func:`plan_batch` (gather rows + recompute in-batch
positions with a node->slot scatter), so the training loop never returns to
host-side packing.
"""
from __future__ import annotations

from typing import Iterator, NamedTuple, Optional

import numpy as np
import jax.numpy as jnp

from repro.core.conv import MinibatchPack
from repro.graph.structure import CSR, Graph


def _pack_rows(csr: CSR, ids: np.ndarray, deg_cap: int,
               inv: np.ndarray | None = None
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """Padded (ELLPACK) neighbor rows for ``ids`` -- fully vectorized CSR
    slicing (one fancy-gather over ``csr.indices``, no per-row Python loop:
    the per-batch host cost is a handful of numpy kernels regardless of b).
    ``inv`` (node -> in-batch position, -1 elsewhere) is optional: callers
    that do not need positions -- or recompute them in-jit, like
    ``build_epoch_plan`` -- pass None and skip that [b, D] gather."""
    ids = np.asarray(ids, np.int64)
    b = len(ids)
    starts = csr.indptr[ids]                                  # [b]
    degs = np.minimum(csr.indptr[ids + 1] - starts, deg_cap)  # [b]
    offs = np.arange(deg_cap, dtype=np.int64)[None, :]        # [1, D]
    valid = offs < degs[:, None]                              # [b, D]
    if csr.m == 0:
        nbr = np.zeros((b, deg_cap), np.int32)
    else:
        nbr = csr.indices[np.where(valid, starts[:, None] + offs, 0)
                          ].astype(np.int32)
        nbr[~valid] = 0
    mask = valid.astype(np.float32)
    pos = None if inv is None else \
        np.where(valid, inv[nbr], np.int32(-1)).astype(np.int32)
    return nbr, mask, pos


def make_pack(g: Graph, batch_ids: np.ndarray, deg_cap: int | None = None,
              *, slot_mask: np.ndarray | None = None) -> MinibatchPack:
    """Pack a mini-batch.  ``slot_mask`` (optional, [b]) marks padding
    slots of a wrap-padded tail batch with 0 so the loss skips them
    (:func:`epoch_slices`)."""
    deg_cap = deg_cap or g.max_degree()
    inv = np.full(g.n, -1, np.int32)
    # reversed writes: a duplicated id keeps its FIRST slot (plan_batch)
    inv[batch_ids[::-1]] = np.arange(len(batch_ids), dtype=np.int32)[::-1]
    nbr, nmask, npos = _pack_rows(g.in_csr, batch_ids, deg_cap, inv)
    rev, rmask, rpos = _pack_rows(g.out_csr, batch_ids, deg_cap, inv)
    return MinibatchPack(
        batch_ids=jnp.asarray(batch_ids.astype(np.int32)),
        nbr_ids=jnp.asarray(nbr), nbr_mask=jnp.asarray(nmask),
        nbr_pos=jnp.asarray(npos),
        rev_ids=jnp.asarray(rev), rev_mask=jnp.asarray(rmask),
        rev_pos=jnp.asarray(rpos), slot_mask=None if slot_mask is None
        else jnp.asarray(slot_mask.astype(np.float32)))


class FullGraphOperands(NamedTuple):
    """Whole-(sub)graph ELL operands for exact message passing.

    Used by the full-graph oracle, the sampling baselines (on their sampled
    subgraphs) and the inference path.  NamedTuple -> a jit-able pytree.
    """
    nbr_ids: jnp.ndarray    # [n, D]
    nbr_mask: jnp.ndarray   # [n, D]
    degrees: jnp.ndarray    # [n]


def full_operands(g: Graph, deg_cap: int | None = None
                  ) -> FullGraphOperands:
    deg_cap = deg_cap or g.max_degree()
    ids = np.arange(g.n)
    nbr, mask, _ = _pack_rows(g.in_csr, ids, deg_cap)
    return FullGraphOperands(
        nbr_ids=jnp.asarray(nbr), nbr_mask=jnp.asarray(mask),
        degrees=jnp.asarray(g.degrees()))


def subgraph_operands(src: np.ndarray, dst: np.ndarray, n_sub: int,
                      deg_cap: int) -> FullGraphOperands:
    from repro.graph.structure import csr_from_coo
    csr = csr_from_coo(src.astype(np.int64), dst.astype(np.int64), n_sub)
    nbr, mask, _ = _pack_rows(csr, np.arange(n_sub), deg_cap)
    return FullGraphOperands(
        nbr_ids=jnp.asarray(nbr), nbr_mask=jnp.asarray(mask),
        degrees=jnp.asarray(csr.degrees()))


def inductive_view(g: Graph) -> Graph:
    """Training view for the inductive setting (PPI): val/test nodes and all
    their edges are invisible during training (paper Sec. 6)."""
    visible = np.zeros(g.n, bool)
    visible[g.train_idx] = True
    keep_src, keep_dst = [], []
    for i in np.where(visible)[0]:
        ns = g.in_csr.neighbors(i)
        ns = ns[visible[ns]]
        keep_src.append(ns)
        keep_dst.append(np.full(len(ns), i, np.int64))
    src = np.concatenate(keep_src) if keep_src else np.zeros(0, np.int64)
    dst = np.concatenate(keep_dst) if keep_dst else np.zeros(0, np.int64)
    from repro.graph.structure import build_graph
    return build_graph(src, dst, g.n, g.features, g.labels,
                       (g.train_idx, g.val_idx, g.test_idx),
                       multilabel=g.multilabel, name=g.name + "-inductive")


PAD_BUCKET_CAP = 1 << 22


def pad_bucket(n: int, cap: int = PAD_BUCKET_CAP) -> int:
    """Round a sampled-subgraph size up to a power-of-two bucket (>= 256),
    clamped to ``cap``, so one compile is reused: varying sampled-subgraph
    shapes otherwise recompile every batch and eventually exhaust the XLA
    CPU JIT.

    A subgraph larger than the cap is a hard error -- silently clamping
    ``n`` itself would drop real nodes (`.at[:n_real].set` overflow) and
    surface as a bare IndexError far from the cause.  With ``n <= cap``
    enforced, the bucket clamp can only shrink padding (sizes in
    (cap/2, cap] share the cap bucket), never drop real nodes."""
    if n > cap:
        raise ValueError(
            f"sampled subgraph has {n} nodes, above the pad-bucket cap "
            f"{cap}: shrink the sampler batch size / walk length / fanout "
            f"or raise the cap")
    b = 256
    while b < n:
        b *= 2
    return min(b, cap)


def epoch_slices(perm: np.ndarray,
                 batch_size: int) -> tuple[np.ndarray, np.ndarray]:
    """Split a node permutation into S static-shape batches: [S, b] ids +
    [S, b] slot mask.

    The tail batch is wrap-padded with nodes from the START of the
    permutation (real nodes -> their messages and assignment refreshes stay
    valid; they merely occur twice in the epoch) and the padding slots are
    masked out of the loss via the 0 entries of the slot mask.  Shared by
    the host-driven stream and the device-resident epoch executor so both
    paths traverse identical batches for the same permutation.

    ``batch_size`` is clamped to the pool size, which guarantees every
    batch holds DISTINCT nodes (for S >= 2 the pad, < b, comes from batch
    0's range; for S == 1 there is no pad): duplicate ids inside one batch
    would make the node->slot scatter order-dependent and corrupt the
    counts arithmetic of ``refresh_assignment``.
    """
    perm = np.asarray(perm)
    n = len(perm)
    batch_size = min(batch_size, n)
    if n == 0:
        return (np.zeros((0, 0), np.int64), np.zeros((0, 0), np.float32))
    n_batches = -(-n // batch_size)
    pad = n_batches * batch_size - n
    ids = np.concatenate([perm, perm[:pad]]) if pad else perm
    slot_mask = np.ones(n_batches * batch_size, np.float32)
    slot_mask[n:] = 0.0
    return (ids.reshape(n_batches, batch_size),
            slot_mask.reshape(n_batches, batch_size))


def inference_slices(n: int,
                     batch_size: int) -> tuple[np.ndarray, np.ndarray]:
    """Static-shape inference batches: :func:`epoch_slices` over the
    identity permutation (inference traverses every node once, in order --
    shuffling buys nothing without a loss).  Shared by ``vq_inference``,
    the serving warm pass, and the inference benchmark so every consumer
    inherits the wrap-padded tail-batch contract instead of re-inventing a
    ragged tail (the pre-executor path recompiled per layer whenever
    ``n % batch_size != 0``)."""
    return epoch_slices(np.arange(n), batch_size)


def minibatch_stream(g: Graph, batch_size: int, rng: np.random.Generator,
                     idx_pool: np.ndarray | None = None,
                     deg_cap: int | None = None) -> Iterator[MinibatchPack]:
    """Random-node mini-batches covering the pool once per epoch (the
    paper's default sampling strategy; App. G shows edge/RW sampling give
    the same accuracy).  The tail batch is wrap-padded to the static batch
    size with loss-masked slots (``epoch_slices``) so every node of the
    pool is traversed every epoch -- the freshness contract of
    ``node_loss``'s docstring."""
    pool = idx_pool if idx_pool is not None else np.arange(g.n)
    ids, slot_mask = epoch_slices(rng.permutation(pool), batch_size)
    for s in range(ids.shape[0]):
        yield make_pack(g, ids[s], deg_cap, slot_mask=slot_mask[s])


# ---------------------------------------------------------------------------
# device-resident epoch plans (DESIGN.md section 9)
# ---------------------------------------------------------------------------

class EpochPlan(NamedTuple):
    """Pack-once, device-resident neighbor tables for the epoch executor.

    Built ONCE per (graph, deg_cap) by :func:`build_epoch_plan`; holds the
    padded in-/out-edge lists of EVERY node as [n, D] device arrays.  An
    epoch's S stacked batches (logically [S, b, D]) are materialized lazily
    inside jit by :func:`plan_batch`: gather the rows of a batch's node ids
    and recompute ``nbr_pos``/``rev_pos`` with a node->slot scatter.  A
    reshuffle therefore costs one device gather per batch -- zero host-side
    pack work inside the epoch loop.
    """
    nbr_ids: jnp.ndarray    # [n, D]   in-neighbor global ids (0 on padding)
    nbr_mask: jnp.ndarray   # [n, D]   1.0 on real in-edges
    rev_ids: jnp.ndarray    # [n, Dr]  out-edge target global ids
    rev_mask: jnp.ndarray   # [n, Dr]

    @property
    def n(self) -> int:
        return self.nbr_ids.shape[0]


def build_epoch_plan(g: Graph, deg_cap: int | None = None, *,
                     full_ops: Optional[FullGraphOperands] = None
                     ) -> EpochPlan:
    """One-time whole-graph pack (vectorized CSR slicing) -> device tables.

    O(n * D) device bytes -- the same order as the ``full_operands`` the
    trainer already keeps resident for evaluation.  Pass those as
    ``full_ops`` and the plan ALIASES their in-edge tables (when the
    deg_cap matches) instead of packing and storing the [n, D] forward
    tables a second time; only the reverse tables are new.
    """
    deg_cap = deg_cap or g.max_degree()
    ids = np.arange(g.n)
    # no inv: positions are recomputed in-jit by plan_batch per batch
    if full_ops is not None and tuple(full_ops.nbr_ids.shape) == \
            (g.n, deg_cap):
        nbr_d, nmask_d = full_ops.nbr_ids, full_ops.nbr_mask
    else:
        nbr, nmask, _ = _pack_rows(g.in_csr, ids, deg_cap)
        nbr_d, nmask_d = jnp.asarray(nbr), jnp.asarray(nmask)
    rev, rmask, _ = _pack_rows(g.out_csr, ids, deg_cap)
    return EpochPlan(nbr_ids=nbr_d, nbr_mask=nmask_d,
                     rev_ids=jnp.asarray(rev), rev_mask=jnp.asarray(rmask))


def plan_batch(plan: EpochPlan, batch_ids: jnp.ndarray,
               slot_mask: Optional[jnp.ndarray] = None) -> MinibatchPack:
    """In-jit MinibatchPack for one batch of a permutation (node->slot
    scatter + row gather; bit-identical to ``make_pack`` on the same
    ids).  A duplicated id (serve requests) points at its first slot, as
    in :func:`_inbatch_positions`: the slot fixes where a message enters
    the kernels' one-hot sums, so every path must pick the same one."""
    b = batch_ids.shape[0]
    batch_ids = batch_ids.astype(jnp.int32)
    slot = jnp.full((plan.n,), b, jnp.int32).at[batch_ids].min(
        jnp.arange(b, dtype=jnp.int32))
    slot = jnp.where(slot == b, -1, slot)
    nbr = plan.nbr_ids[batch_ids]
    nmask = plan.nbr_mask[batch_ids]
    rev = plan.rev_ids[batch_ids]
    rmask = plan.rev_mask[batch_ids]
    npos = jnp.where(nmask != 0, slot[nbr], -1).astype(jnp.int32)
    rpos = jnp.where(rmask != 0, slot[rev], -1).astype(jnp.int32)
    return MinibatchPack(
        batch_ids=batch_ids, nbr_ids=nbr, nbr_mask=nmask, nbr_pos=npos,
        rev_ids=rev, rev_mask=rmask, rev_pos=rpos, slot_mask=slot_mask)


def _inbatch_positions(batch_ids: jnp.ndarray, ids: jnp.ndarray,
                       mask: jnp.ndarray) -> jnp.ndarray:
    """node id -> in-batch position (-1 when absent/masked) via
    argsort+searchsorted over the b batch ids instead of ``plan_batch``'s
    O(n) node->slot scatter.  The sharded executor uses this because a
    transient [n] slot array would reintroduce the per-device O(n) memory
    the row sharding just removed.  Identical to the scatter, duplicate
    ids included: the stable argsort puts a duplicated id's slots in
    order, so ``searchsorted`` finds its first slot."""
    b = batch_ids.shape[0]
    order = jnp.argsort(batch_ids)
    sb = batch_ids[order]
    j = jnp.clip(jnp.searchsorted(sb, ids), 0, b - 1)
    hit = (sb[j] == ids) & (mask != 0)
    return jnp.where(hit, order[j], -1).astype(jnp.int32)


def plan_batch_sharded(plan: EpochPlan, batch_ids: jnp.ndarray,
                       axis_name: str,
                       slot_mask: Optional[jnp.ndarray] = None
                       ) -> MinibatchPack:
    """:func:`plan_batch` against a ROW-SHARDED EpochPlan, inside
    shard_map: ``plan``'s tables are each shard's contiguous
    [n_local, D] row block of the padded global tables, and the row
    gathers go cross-shard through
    :func:`repro.distributed.collectives.gather_from_shards`.  The id
    and mask tables are concatenated to [n_local, D+Dr] before the
    gather so one batch costs two cross-shard gathers (one int, one
    float) instead of four.  Positions come from
    :func:`_inbatch_positions` (no O(n) transient).  Value-identical to
    ``plan_batch`` on the unsharded plan for the same batch."""
    from repro.distributed.collectives import gather_from_shards

    d = plan.nbr_ids.shape[1]
    batch_ids = batch_ids.astype(jnp.int32)
    ids_tab = jnp.concatenate([plan.nbr_ids, plan.rev_ids], axis=1)
    mask_tab = jnp.concatenate([plan.nbr_mask, plan.rev_mask], axis=1)
    ids_rows = gather_from_shards(ids_tab, batch_ids, axis_name)
    mask_rows = gather_from_shards(mask_tab, batch_ids, axis_name)
    nbr, rev = ids_rows[:, :d], ids_rows[:, d:]
    nmask, rmask = mask_rows[:, :d], mask_rows[:, d:]
    npos = _inbatch_positions(batch_ids, nbr, nmask)
    rpos = _inbatch_positions(batch_ids, rev, rmask)
    return MinibatchPack(
        batch_ids=batch_ids, nbr_ids=nbr, nbr_mask=nmask, nbr_pos=npos,
        rev_ids=rev, rev_mask=rmask, rev_pos=rpos, slot_mask=slot_mask)


# ---------------------------------------------------------------------------
# sampler epoch plans (DESIGN.md section 12)
# ---------------------------------------------------------------------------

class SamplerEpochPlan(NamedTuple):
    """An epoch of pre-sampled induced subgraphs, stacked to static shape.

    Built once per epoch by :func:`pack_sampler_epoch` from a sampler's
    batch list; holds every batch's padded-ELL subgraph operands as
    [S, P, ...] device tables so ``models.gnn.sampler_train_epoch`` can run
    the whole epoch as ONE ``lax.scan`` -- the same pack-once/scan regime
    VQ training rides (section 9), applied to the sampling baselines so the
    Table 2/4 comparison is executor-vs-executor instead of
    executor-vs-host-loop.

    ``nbr_ids`` are LOCAL subgraph positions (the per-step scan body treats
    each [P, D] slice as a self-contained ``FullGraphOperands``); padding
    rows have empty neighbor lists, zero degree, ``node_ids`` 0 and
    ``loss_mask`` 0, so they feed nothing into real rows and contribute
    nothing to the masked loss.
    """
    node_ids: jnp.ndarray    # [S, P]    global node ids (0 on padding rows)
    nbr_ids: jnp.ndarray     # [S, P, D] in-neighbor LOCAL positions
    nbr_mask: jnp.ndarray    # [S, P, D] 1.0 on real in-edges
    degrees: jnp.ndarray     # [S, P]    in-degree within the subgraph
    loss_mask: jnp.ndarray   # [S, P]    seed weight (0 on padding/non-seed)

    @property
    def s(self) -> int:
        return self.node_ids.shape[0]

    @property
    def p(self) -> int:
        return self.node_ids.shape[1]


def pack_sampler_epoch(batches: list[tuple], deg_cap: int,
                       n_pad: Optional[int] = None) -> SamplerEpochPlan:
    """Stack one epoch of sampler 5-tuples into a :class:`SamplerEpochPlan`.

    batches: list of ``(src, dst, nodes, seed_pos, seed_weight)`` (the
    ``repro.graph.sampling`` contract).  All subgraphs are padded to one
    shared width -- ``n_pad`` or the power-of-two bucket of the epoch's
    largest subgraph (:func:`pad_bucket`, so the bucket rarely moves across
    epochs and the scanned executable is reused) -- and neighbor lists to
    ``deg_cap`` (within-subgraph degree is bounded by the graph's, so the
    global cap is always safe).
    """
    from repro.graph.structure import csr_from_coo
    if not batches:
        raise ValueError("pack_sampler_epoch needs at least one batch")
    sizes = [len(nodes) for _, _, nodes, _, _ in batches]
    p = n_pad if n_pad is not None else pad_bucket(max(sizes))
    if max(sizes) > p:
        raise ValueError(f"subgraph of {max(sizes)} nodes exceeds "
                         f"n_pad={p}")
    s = len(batches)
    node_ids = np.zeros((s, p), np.int64)
    nbr = np.zeros((s, p, deg_cap), np.int32)
    mask = np.zeros((s, p, deg_cap), np.float32)
    degs = np.zeros((s, p), np.float32)
    loss = np.zeros((s, p), np.float32)
    for i, (src, dst, nodes, seed_pos, seed_w) in enumerate(batches):
        csr = csr_from_coo(np.asarray(src, np.int64),
                           np.asarray(dst, np.int64), p)
        nbr[i], mask[i], _ = _pack_rows(csr, np.arange(p), deg_cap)
        degs[i] = csr.degrees()
        node_ids[i, :len(nodes)] = nodes
        loss[i, np.asarray(seed_pos)] = np.asarray(seed_w, np.float32)
    return SamplerEpochPlan(
        node_ids=jnp.asarray(node_ids), nbr_ids=jnp.asarray(nbr),
        nbr_mask=jnp.asarray(mask), degrees=jnp.asarray(degs),
        loss_mask=jnp.asarray(loss))
