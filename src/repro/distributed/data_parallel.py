"""shard_map data parallelism for the VQ epoch executor (DESIGN.md sec. 9).

Shards the BATCH axis of the stacked epoch arrays over the 1-axis "data"
mesh: each device runs the full ``lax.scan`` over the S steps on its b/ndev
rows of every batch, treating its rows as a VQ mini-batch of their own
(cross-device in-batch neighbors ride the codeword context, exactly the
paper's out-of-batch approximation).  The per-replica body IS
``models.gnn._vq_epoch_body`` -- the same implementation the single-device
executor jits -- with ``axis_name="data"``, which turns on three
collectives per step:

  * param grads          -- ``collectives.psum_tree`` (uncompressed; exact),
  * codebook statistics  -- the fused ``vq_assign_update`` (counts, sums)
    and the whitening batch moments, psum'd INSIDE ``codebook.update`` via
    its ``axis_name`` hook, so every replica computes the same EMA step as
    a single device seeing the pooled batch;
  * assignment sync      -- each device's refreshed rows are all-gathered
    and scattered into the (replicated) global assignment table, so tables
    never diverge.

The ndev=1 instantiation is numerically identical to
``models.gnn.vq_train_epoch``; the multi-device run is identical to the
same body under ``jax.vmap(axis_name=...)`` over the sub-batch axis (the
parity oracles in tests/test_epoch_executor.py).

Eq. 7 backward under DP: the injection's residuals are *lazy*
(``core/message_passing.py`` / DESIGN.md section 10) -- each replica's
scan carry holds only its [b/ndev, Dr] reverse-edge operands plus the
replicated O(k * f) codebook and assignment tables it keeps anyway, and
the backward streams the phantom term through the fused
``kops.context_ell`` dispatch per replica with no collective (the
codeword tables are replica-identical by the psum rule above).  Nothing
per-replica scales as [b/ndev, Dr, f_grad].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.distributed.sharding import epoch_batch_spec, graph_dp_mesh, \
    scan_shard_spec, shard_padded_rows, shard_rows, shard_rows_spec
from repro.graph.batching import EpochPlan
from repro.models.gnn import GNNConfig, _vq_epoch_body, \
    _vq_infer_layer_sharded, _vq_serve_body_sharded
from repro.train.optimizer import Optimizer

__all__ = ["graph_dp_mesh", "vq_train_epoch_dp", "ShardedGraphState",
           "vq_train_epoch_sharded", "vq_infer_epoch_sharded",
           "vq_serve_batch_sharded"]


@functools.partial(jax.jit, static_argnames=("mesh", "cfg", "opt"),
                   donate_argnums=(0, 1, 2))
def _dp_epoch_jit(params, vq_states, opt_state, plan, perm, slot_mask,
                  x, labels, train_mask, degrees, *, mesh: Mesh,
                  cfg: GNNConfig, opt: Optimizer):
    # the shard_map wrapper is rebuilt per trace (cheap); caching lives in
    # jit's executable cache keyed on the static (mesh, cfg, opt) -- the
    # same convention as vq_train_step's static opt, and no extra
    # permanently-retained closure cache
    body = functools.partial(_vq_epoch_body, cfg=cfg, opt=opt,
                             axis_name="data")
    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), epoch_batch_spec(),
                  epoch_batch_spec(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False)
    return sharded(params, vq_states, opt_state, plan, perm, slot_mask,
                   x, labels, train_mask, degrees)


def vq_train_epoch_dp(mesh: Mesh, params, vq_states, opt_state,
                      plan: EpochPlan, perm, slot_mask, x, labels,
                      train_mask, degrees, cfg: GNNConfig, opt: Optimizer):
    """Data-parallel ``vq_train_epoch``: one jit'd shard_map call per epoch.

    Same signature/returns as the single-device executor plus the leading
    ``mesh`` (1-axis "data", e.g. ``graph_dp_mesh()``); the batch axis of
    ``perm``/``slot_mask`` [S, b] must divide by the mesh size.
    """
    nd = mesh.shape["data"]
    if perm.shape[1] % nd != 0:
        raise ValueError(
            f"batch size {perm.shape[1]} not divisible by the data mesh "
            f"size {nd}")
    return _dp_epoch_jit(params, vq_states, opt_state, plan, perm,
                         slot_mask, x, labels, train_mask, degrees,
                         mesh=mesh, cfg=cfg, opt=opt)


# ---------------------------------------------------------------------------
# Row-sharded graph state executors (DESIGN.md section 14)
# ---------------------------------------------------------------------------

class ShardedGraphState:
    """Every node-indexed table of a graph, row-sharded over ``mesh``.

    Host-side, built once per graph: pads each [n, ...] table to
    ``shard_padded_rows(n, ndev)`` rows (one sacrificial row for the
    inference scatter's wrap-pad writes, then round up to equal
    contiguous blocks) and places it with a :func:`shard_rows_spec`
    NamedSharding, so shard_map receives the per-device blocks without
    any resharding transfer.  ``degrees`` stays REPLICATED by design:
    ``fixed_edge_values`` indexes it by arbitrary neighbor ids on the
    per-batch hot path and it costs only 4 bytes/node -- same reasoning
    as the replicated [k, f] codebooks and [nb, n] assignment tables.
    """

    def __init__(self, mesh: Mesh, plan: EpochPlan, x, degrees,
                 labels=None, train_mask=None):
        self.mesh = mesh
        self.ndev = int(mesh.shape["data"])
        self.n = int(plan.n)
        self.n_pad = shard_padded_rows(self.n, self.ndev)
        self.n_local = self.n_pad // self.ndev
        put = functools.partial(shard_rows, mesh=mesh, n_pad=self.n_pad)
        self.plan = EpochPlan(
            nbr_ids=put(plan.nbr_ids), nbr_mask=put(plan.nbr_mask),
            rev_ids=put(plan.rev_ids), rev_mask=put(plan.rev_mask))
        self.x = put(jnp.asarray(x))
        self.degrees = jax.device_put(
            jnp.asarray(degrees), shd.replicated(mesh))
        self.labels = None if labels is None else put(jnp.asarray(labels))
        self.train_mask = None if train_mask is None \
            else put(jnp.asarray(train_mask))

    def per_device_bytes(self) -> int:
        """Peak per-device bytes of the held graph state (the bench's
        capacity metric; ~1/ndev of the replicated footprint plus the
        replicated [n] degree vector)."""
        return shd.per_device_bytes(
            [self.plan, self.x, self.degrees, self.labels, self.train_mask])

    def unshard(self, table) -> np.ndarray:
        """Host copy of a row-sharded [n_pad, ...] output with the pad
        rows stripped -- the parity-test / eval convenience."""
        return np.asarray(table)[: self.n]


def _pad_scan_axis(perm, slot_mask, ndev: int):
    """Pad the scan axis of the stacked [S, b] inference arrays to a
    multiple of ``ndev`` with all-masked batches (ids 0, mask 0), so the
    scan-axis shards run equal step counts and the per-step collectives
    stay lockstep.  The padding batches write only the sacrificial row."""
    s = perm.shape[0]
    s_pad = -(-s // ndev) * ndev
    if s_pad == s:
        return jnp.asarray(perm), jnp.asarray(slot_mask)
    perm = jnp.asarray(perm)
    slot_mask = jnp.asarray(slot_mask)
    zp = jnp.zeros((s_pad - s,) + perm.shape[1:], perm.dtype)
    zm = jnp.zeros((s_pad - s,) + slot_mask.shape[1:], slot_mask.dtype)
    return jnp.concatenate([perm, zp]), jnp.concatenate([slot_mask, zm])


@functools.partial(jax.jit,
                   static_argnames=("mesh", "cfg", "opt", "compress"),
                   donate_argnums=(0, 1, 2))
def _sharded_epoch_jit(params, vq_states, opt_state, plan, perm, slot_mask,
                       x, labels, train_mask, degrees, *, mesh: Mesh,
                       cfg: GNNConfig, opt: Optimizer, compress: bool):
    body = functools.partial(_vq_epoch_body, cfg=cfg, opt=opt,
                             axis_name="data", sharded_state=True,
                             compress=compress)
    rows = shard_rows_spec()
    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), rows, epoch_batch_spec(),
                  epoch_batch_spec(), rows, rows, rows, P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False)
    return sharded(params, vq_states, opt_state, plan, perm, slot_mask,
                   x, labels, train_mask, degrees)


def vq_train_epoch_sharded(state: ShardedGraphState, params, vq_states,
                           opt_state, perm, slot_mask, cfg: GNNConfig,
                           opt: Optimizer, *, compress: bool = False):
    """``vq_train_epoch_dp`` against row-sharded graph state: the batch
    axis still splits over "data" (each shard trains on its b/ndev rows)
    but the EpochPlan / feature / label / mask tables are per-shard row
    blocks and every per-batch row access goes cross-shard.  Value-
    identical to the replicated DP executor at the same mesh size (the
    gathers reassemble the exact same batches); per-device graph-state
    bytes drop ~1/ndev.  Same returns as ``vq_train_epoch``."""
    nd = state.ndev
    if perm.shape[1] % nd != 0:
        raise ValueError(
            f"batch size {perm.shape[1]} not divisible by the data mesh "
            f"size {nd} -- the sharded-state executor splits each batch "
            f"over the mesh; pick b as a multiple of {nd} (the trainer "
            f"clamps batch_size to the {state.n}-node pool first)")
    if state.labels is None or state.train_mask is None:
        raise ValueError(
            "ShardedGraphState built without labels/train_mask cannot "
            "train -- pass them at construction")
    return _sharded_epoch_jit(params, vq_states, opt_state, state.plan,
                              jnp.asarray(perm), jnp.asarray(slot_mask),
                              state.x, state.labels, state.train_mask,
                              state.degrees, mesh=state.mesh, cfg=cfg,
                              opt=opt, compress=compress)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "cfg", "layer", "inductive",
                                    "n_global", "compress"))
def _sharded_infer_layer_jit(params_l, vq_state, plan, perm, slot_mask,
                             acts, degrees, *, mesh: Mesh, cfg: GNNConfig,
                             layer: int, inductive: bool, n_global: int,
                             compress: bool):
    body = functools.partial(_vq_infer_layer_sharded, cfg=cfg, layer=layer,
                             axis_name="data", n_global=n_global,
                             inductive=inductive, compress=compress)
    rows = shard_rows_spec()
    scan = scan_shard_spec()
    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), rows, scan, scan, rows, P()),
        out_specs=(rows, P()),
        check_vma=False)
    return sharded(params_l, vq_state, plan, perm, slot_mask, acts,
                   degrees)


def vq_infer_epoch_sharded(state: ShardedGraphState, params, vq_states,
                           perm, slot_mask, cfg: GNNConfig, *,
                           inductive: bool = False,
                           compress: bool = False):
    """``vq_infer_epoch`` against row-sharded graph state: n_layers jit'd
    shard_map calls, each sweeping the S batches with the SCAN axis split
    over the mesh (S/ndev full batches per shard -- exact full-batch
    positions, so the result is bit-identical to the replicated ndev=1
    executor) and the [n_pad, f] activation tables row-sharded
    throughout.  Returns (acts, states) with ``acts`` the row-sharded
    [n_pad, f_out] table -- ``state.unshard(acts)`` for the [n, f_out]
    host view."""
    perm, slot_mask = _pad_scan_axis(perm, slot_mask, state.ndev)
    acts = state.x
    states = list(vq_states)
    for l in range(cfg.n_layers):
        acts, states[l] = _sharded_infer_layer_jit(
            params[l], states[l], state.plan, perm, slot_mask, acts,
            state.degrees, mesh=state.mesh, cfg=cfg, layer=l,
            inductive=inductive, n_global=state.n, compress=compress)
    return acts, states


@functools.partial(jax.jit, static_argnames=("mesh", "cfg", "compress"))
def _sharded_serve_jit(params, vq_states, plan, bids, x, degrees, *,
                       mesh: Mesh, cfg: GNNConfig, compress: bool):
    body = functools.partial(_vq_serve_body_sharded, cfg=cfg,
                             axis_name="data", compress=compress)
    rows = shard_rows_spec()
    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), rows, P(), rows, P()),
        out_specs=P(),
        check_vma=False)
    return sharded(params, vq_states, plan, bids, x, degrees)


def vq_serve_batch_sharded(state: ShardedGraphState, params, vq_states,
                           bids, cfg: GNNConfig, *,
                           compress: bool = False):
    """``vq_serve_batch`` against row-sharded graph state: request ids
    replicated, plan/feature rows cross-shard-gathered, forward exact --
    the serve endpoint's capacity mode (``serve_gnn --mesh N`` with
    sharding on)."""
    return _sharded_serve_jit(params, vq_states, state.plan,
                              jnp.asarray(bids), state.x, state.degrees,
                              mesh=state.mesh, cfg=cfg, compress=compress)
