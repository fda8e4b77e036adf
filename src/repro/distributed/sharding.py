"""Mesh and sharding helpers of the GNN executors.

``graph_dp_mesh`` builds the one-axis "data" mesh that both the epoch
executor's data parallelism (DESIGN.md section 9) and the row-sharded graph
state (DESIGN.md section 14) run on.  The PartitionSpec helpers place the
stacked ``[S, b]`` epoch arrays, serving micro-batches and node tables on it,
and the row-ownership maps (``node_to_shard``, ``global_to_local``, ...)
translate node ids between the global table and its contiguous shards.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# GNN epoch-executor data parallelism (DESIGN.md section 9)
# ---------------------------------------------------------------------------

def graph_dp_mesh(n_devices: int | None = None) -> Mesh:
    """1-axis "data" mesh for the VQ epoch executor's shard_map data
    parallelism (params/codebooks replicated, batch axis sharded) and for
    the row-sharded graph state (node tables split over the same axis --
    :func:`shard_rows_spec`).  Raises when fewer devices exist than
    requested -- an explicit parallelism/capacity ask must never silently
    under-provision."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"requested a {n_devices}-device data mesh but only "
                f"{len(devs)} device(s) exist -- each mesh device owns a "
                f"1/{n_devices} contiguous row block of the sharded graph "
                f"state (node tables padded to a multiple of {n_devices} "
                f"rows, shard_padded_rows); on CPU hosts add "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n_devices} for virtual devices")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), ("data",))


def epoch_batch_spec() -> P:
    """PartitionSpec of the stacked [S, b] epoch arrays (perm / slot mask):
    scan axis replicated, batch axis split over "data"."""
    return P(None, "data")


def serve_batch_spec() -> P:
    """PartitionSpec of a serving request micro-batch [b] of node ids
    (``launch/serve_gnn.py``): the single batch axis split over "data" --
    :func:`epoch_batch_spec` minus the scan axis.  Placing the ids with
    this spec lets jit's SPMD partitioner split the O(b) serve step
    (gathers + codeword forward) across the mesh while the plan/codebook
    tables stay replicated."""
    return P("data")


# ---------------------------------------------------------------------------
# Row-sharded graph state (DESIGN.md section 14)
# ---------------------------------------------------------------------------
#
# Every node-indexed table (EpochPlan neighbor structures, node features,
# the [n+1, f] inference activation table) is split by node id into
# contiguous row blocks over the "data" mesh axis, so per-device graph
# state drops ~1/ndev and mesh size becomes a *capacity* knob.  The tiny
# [k, f] codebooks, their counts/sums/revival state, the [nb, n]
# assignment tables, and the [n] degree vector stay replicated: the
# context kernel and `out_of_batch_cluster_mass` need global random
# access to assignments, and degrees cost 4 bytes/node -- sharding them
# would trade O(1) lookups for collectives with no memory story.

def shard_padded_rows(n: int, ndev: int) -> int:
    """Padded global row count for an ``n``-row node table sharded over
    ``ndev`` devices.  One extra *sacrificial* row (global id ``n``)
    absorbs the wrap-pad / masked-slot writes of the inference scatter,
    then the total is rounded up so every shard owns an equal contiguous
    block.  Pad rows land on the last shard by construction ("wrap-pad
    rows pinned to the owning shard")."""
    if ndev <= 0:
        raise ValueError(f"ndev must be positive, got {ndev}")
    return -(-(n + 1) // ndev) * ndev


def shard_rows_spec(ndim: int = 1) -> P:
    """PartitionSpec splitting a node table's leading (row) axis over the
    "data" mesh axis; remaining axes replicated."""
    return P(*(("data",) + (None,) * (ndim - 1)))


def scan_shard_spec(ndim: int = 2) -> P:
    """PartitionSpec splitting the *scan* axis of the stacked [S, b]
    epoch/inference arrays over "data": each shard runs S/ndev full
    batches, which keeps every batch's in-batch positions exact (the
    sharded inference executor's parity-by-construction trick) while the
    per-layer compute still splits ndev ways."""
    return P(*(("data",) + (None,) * (ndim - 1)))


def node_to_shard(gid, n_local: int):
    """Owning shard of global node id(s) under contiguous-block
    ownership: shard ``s`` owns rows ``[s*n_local, (s+1)*n_local)``."""
    return gid // n_local


def global_to_local(gid, shard, n_local: int):
    """Local row of global id(s) on ``shard`` (meaningful only when
    ``node_to_shard(gid, n_local) == shard``)."""
    return gid - shard * n_local


def local_to_global(lid, shard, n_local: int):
    """Global node id of local row(s) ``lid`` on ``shard``."""
    return lid + shard * n_local


def pad_rows(x, n_pad: int, fill=0):
    """Pad a node table's leading axis to ``n_pad`` rows with ``fill``
    (numpy or jax input; returns the same kind)."""
    n = x.shape[0]
    if n > n_pad:
        raise ValueError(f"table has {n} rows > padded target {n_pad}")
    if n == n_pad:
        return x
    xp = jax.numpy if isinstance(x, jax.Array) else np
    pad = xp.full((n_pad - n,) + tuple(x.shape[1:]), fill, dtype=x.dtype)
    return xp.concatenate([x, pad], axis=0)


def shard_rows(x, mesh: Mesh, n_pad: int | None = None, fill=0):
    """Place a node table on ``mesh`` with its rows split over "data",
    padding to ``n_pad`` (default :func:`shard_padded_rows`) first."""
    ndev = mesh.shape["data"]
    if n_pad is None:
        n_pad = shard_padded_rows(x.shape[0] - 1, ndev) \
            if x.shape[0] % ndev else x.shape[0]
    x = pad_rows(x, n_pad, fill)
    return jax.device_put(
        x, NamedSharding(mesh, shard_rows_spec(x.ndim)))


def per_device_bytes(tree) -> int:
    """Peak per-device bytes of a pytree of placed arrays: max over
    devices of the sum of addressable shard sizes.  This is the honest
    capacity metric for the sharded-vs-replicated bench rows -- a
    replicated table counts fully on every device, a row-sharded one
    ~1/ndev."""
    per_dev: dict[Any, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        if not isinstance(leaf, jax.Array):
            continue
        for s in leaf.addressable_shards:
            per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
    return max(per_dev.values(), default=0)
