"""GNN serving endpoint: codeword-context inference as a traffic-shaped
service (the paper's Sec. 6 claim -- sampling baselines need the O(d^L)
L-hop neighborhood per request, VQ-GNN serves a request batch with O(b)
work -- finally exercised by an actual request loop).

    PYTHONPATH=src python -m repro.launch.serve_gnn --n 2000 --batch 256 \
        --requests 200 [--mesh 2] [--train-epochs 3] [--json out.json]

The server keeps params, per-layer VQ states, node features, and the
pack-once :class:`~repro.graph.batching.EpochPlan` device-resident.  Start
up = one `refresh` pass of the inference executor with feature-half
assignment (``vq_infer_epoch(inductive=True)``) so every node -- including
nodes unseen at train time -- holds a fresh codeword, then ONE compile of
the serve step (``models.gnn.vq_serve_batch``: in-jit ``plan_batch`` +
all-layer codeword forward).  After that the request loop never compiles:
requests are coalesced onto the static [batch] shape by the micro-batcher
(small requests share a step, large requests span several), and the report
gives nodes/s throughput plus p50/p99 step and request latency.

``--mesh N`` shards the micro-batch axis over a 1-axis "data" mesh
(``sharding.graph_dp_mesh`` + ``sharding.serve_batch_spec``): ids placed
with the serve spec let jit's SPMD partitioner split the per-request
gathers and forward across devices while plan/codebooks stay replicated.

``--mesh N --shard-graph`` flips the mesh from a throughput knob to a
CAPACITY knob (DESIGN.md section 14): the EpochPlan, feature table, and
per-layer activation tables are row-sharded over the mesh
(``ShardedGraphState``), per-batch rows are cross-shard-gathered, and
peak per-device graph-state bytes drop ~1/N -- the served graph can
outgrow a single device's HBM.  The report's
``graph_state_bytes_per_device`` records exactly that.
"""
from __future__ import annotations

import argparse
import json
import time
from collections import deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core.codebook import CodebookConfig
from repro.distributed import sharding as shd
from repro.distributed.data_parallel import (ShardedGraphState,
                                             vq_infer_epoch_sharded,
                                             vq_serve_batch_sharded)
from repro.distributed.quantization import tree_bytes
from repro.graph.batching import (build_epoch_plan, full_operands,
                                  inference_slices)
from repro.graph.structure import Graph
from repro.kernels import ops as kops
from repro import hostenv
from repro.models.gnn import (GNNConfig, _layer_out_dims, init_gnn,
                              init_vq_states, quantize_vq_states,
                              vq_infer_epoch, vq_serve_batch)


class GNNServer:
    """Device-resident serving state + the precompiled O(b) serve step."""

    def __init__(self, g: Graph, cfg: GNNConfig, params, vq_states,
                 batch: int, mesh: Optional[Mesh] = None,
                 shard_graph: bool = False):
        if batch > g.n:
            batch = g.n            # the id pool bounds a useful micro-batch
        if mesh is not None and not shard_graph \
                and batch % mesh.shape["data"] != 0:
            # SPMD throughput mode splits the batch axis; the sharded-
            # state mode replicates the request ids (rows go cross-shard
            # instead) so any batch size serves
            raise ValueError(
                f"serve micro-batch {batch} is not divisible by the "
                f"{mesh.shape['data']}-device data mesh")
        if shard_graph and mesh is None:
            raise ValueError(
                "shard_graph=True row-shards the graph state over a "
                "mesh -- pass mesh= (graph_dp_mesh) as well")
        self.g, self.cfg, self.batch = g, cfg, batch
        self.mesh = mesh
        self.ops = full_operands(g)
        self.plan = build_epoch_plan(g, full_ops=self.ops)
        self.x = jnp.asarray(g.features)
        self.params = params
        self.vq = list(vq_states)
        self.f_out = _layer_out_dims(cfg)[-1][1]
        self.sstate = None
        if shard_graph:
            self.sstate = ShardedGraphState(mesh, self.plan, self.x,
                                            self.ops.degrees)
            # the replicated copies exist only transiently at build time
            # on a real multi-host deployment; here they back _evaluate-
            # style offline use and the bench's replicated-vs-sharded
            # byte comparison
        self.ids_sharding = None if mesh is None or shard_graph else \
            NamedSharding(mesh, shd.serve_batch_spec())

    def graph_state_bytes_per_device(self) -> int:
        """Peak per-device bytes of the serving graph state (plan +
        features + degrees): the --mesh capacity metric."""
        if self.sstate is not None:
            return self.sstate.per_device_bytes()
        return int(sum(
            v.nbytes for v in (self.plan.nbr_ids, self.plan.nbr_mask,
                               self.plan.rev_ids, self.plan.rev_mask,
                               self.x, self.ops.degrees)))

    def refresh(self) -> float:
        """Refresh every layer's codeword assignment from the current
        features via the inference executor's in-jit feature-half
        assignment (paper Sec. 6 inductive machinery) -- the serving
        analogue of loading fresh historical embeddings.  Returns wall
        seconds (includes the executor's O(n_layers) compiles)."""
        t0 = time.time()
        ids, sm = inference_slices(self.g.n, self.batch)
        if self.sstate is not None:
            _, self.vq = vq_infer_epoch_sharded(
                self.sstate, self.params, self.vq,
                jnp.asarray(ids.astype(np.int32)), jnp.asarray(sm),
                self.cfg, inductive=True)
        else:
            _, self.vq = vq_infer_epoch(
                self.params, self.vq, self.plan,
                jnp.asarray(ids.astype(np.int32)), jnp.asarray(sm),
                self.x, self.ops.degrees, self.cfg, inductive=True)
        jax.block_until_ready(self.vq)
        return time.time() - t0

    def warmup(self) -> float:
        """Compile the serve step on the static batch shape; returns wall
        seconds of the (single) compile."""
        t0 = time.time()
        self.step(np.zeros(self.batch, np.int64))
        return time.time() - t0

    def step(self, bids: np.ndarray) -> np.ndarray:
        """One device step over exactly ``batch`` node-id slots."""
        if len(bids) != self.batch:
            # a hard error, not an assert: a wrong-sized id vector would
            # otherwise silently retrace the jitted step on the hot path
            # and defeat the warm single-compile contract
            raise ValueError(
                f"serve step needs exactly {self.batch} id slots, got "
                f"{len(bids)} (use serve() for arbitrary request sizes)")
        # three host spans that tile the step, on the profiler's clock (one
        # inactive TraceMe each when no trace runs): the id put, the
        # enqueue of the jitted step, and the wait for and copy of its rows
        with jax.profiler.TraceAnnotation("program.serve.put"):
            ids_d = jnp.asarray(bids.astype(np.int32))
            if self.ids_sharding is not None:
                ids_d = jax.device_put(ids_d, self.ids_sharding)
        with jax.profiler.TraceAnnotation("program.serve.dispatch"):
            if self.sstate is not None:
                y = vq_serve_batch_sharded(self.sstate, self.params,
                                           self.vq, ids_d, self.cfg)
            else:
                y = vq_serve_batch(self.params, self.vq, self.plan, ids_d,
                                   self.x, self.ops.degrees, self.cfg)
        with jax.profiler.TraceAnnotation("program.serve.fetch"):
            return np.asarray(y)

    def serve(self, node_ids: np.ndarray) -> np.ndarray:
        """Serve one request of arbitrary size (pads the tail step by
        repeating id 0; duplicate ids are safe, see ``vq_serve_batch``)."""
        node_ids = np.asarray(node_ids)
        if len(node_ids) == 0:
            return np.zeros((0, self.f_out), np.float32)
        outs = []
        for s in range(0, len(node_ids), self.batch):
            chunk = node_ids[s:s + self.batch]
            pad = self.batch - len(chunk)
            step_ids = np.concatenate(
                [chunk, np.zeros(pad, chunk.dtype)]) if pad else chunk
            outs.append(self.step(step_ids)[:len(chunk)])
        return np.concatenate(outs, axis=0)


def drain_requests(server: GNNServer, requests: Sequence[np.ndarray]
                   ) -> dict:
    """Closed-loop micro-batching drain: every queued request contributes
    slots to the next static [batch] step until the step is full (a small
    request shares its step with neighbors in the queue; a large request
    spills over several steps).  A request completes when its last slot's
    step returns; latency is measured against the drain start (all
    requests enqueued at t0 -- the worst-case, queueing-inclusive view).
    """
    b = server.batch
    pend = deque((i, np.asarray(r, np.int64)) for i, r in enumerate(requests))
    remaining = [len(np.asarray(r)) for r in requests]
    done = np.zeros(len(requests))
    step_lat: list[float] = []
    n_nodes = 0
    t0 = time.time()
    while pend:
        slots, members, filled = [], [], 0
        while pend and filled < b:
            i, ids = pend.popleft()
            take = min(b - filled, len(ids))
            slots.append(ids[:take])
            members.append((i, take))
            filled += take
            if take < len(ids):
                pend.appendleft((i, ids[take:]))
        flat = np.concatenate(slots)
        if filled < b:
            flat = np.concatenate([flat, np.zeros(b - filled, np.int64)])
        ts = time.time()
        server.step(flat)
        now = time.time()
        step_lat.append(now - ts)
        n_nodes += filled
        for i, take in members:               # O(1) completion tracking
            remaining[i] -= take
            if remaining[i] == 0:             # last spill completed
                done[i] = now - t0
    wall = time.time() - t0
    lat = np.sort(done)
    sl = np.sort(np.asarray(step_lat))

    def pct(a, q):
        return float(a[min(len(a) - 1, int(q * len(a)))]) if len(a) else 0.0
    return {
        "requests": len(requests), "steps": len(step_lat),
        "nodes": int(n_nodes), "wall_s": wall,
        "nodes_per_s": n_nodes / max(wall, 1e-9),
        "requests_per_s": len(requests) / max(wall, 1e-9),
        "step_p50_ms": pct(sl, 0.50) * 1e3,
        "step_p99_ms": pct(sl, 0.99) * 1e3,
        "request_p50_ms": pct(lat, 0.50) * 1e3,
        "request_p99_ms": pct(lat, 0.99) * 1e3,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--batch", type=int, default=256,
                    help="static serve micro-batch (node slots per step)")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--max-request", type=int, default=64,
                    help="request sizes ~ U[1, max-request] nodes")
    ap.add_argument("--backbone", default="gcn",
                    choices=["gcn", "sage", "gat", "gin", "transformer"])
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--train-epochs", type=int, default=0,
                    help="optional warm training before serving "
                    "(0 = serve from init + assignment refresh)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the micro-batch over an N-device data mesh")
    ap.add_argument("--shard-graph", action="store_true",
                    help="with --mesh N: row-shard the graph state over "
                    "the mesh (capacity mode -- per-device graph bytes "
                    "drop ~1/N, DESIGN.md section 14)")
    ap.add_argument("--precision", default="fp32",
                    choices=list(kops.PRECISIONS),
                    help="kernel operand precision tier: int8/fp8 serve "
                    "uint8 assignment tables + int8/fp8 codeword "
                    "snapshots; the '+a4' variants nibble-pack the "
                    "assignment tables (k <= 16, 2 ids/byte) "
                    "(DESIGN.md sections 13 and 15)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    hostenv.enable_compile_cache()

    from repro.graph.datasets import synthetic_arxiv
    g = synthetic_arxiv(n=args.n, seed=args.seed)
    cfg = GNNConfig(backbone=args.backbone, f_in=g.f, hidden=args.hidden,
                    n_out=g.num_classes, n_layers=args.layers,
                    codebook=CodebookConfig(k=args.k, f_prod=4))
    kops.configure_kernel_precision(args.precision)
    if args.train_epochs > 0:
        from repro.train.gnn_trainer import train_vq
        r = train_vq(g, cfg, epochs=args.train_epochs,
                     batch_size=args.batch, eval_every=args.train_epochs)
        params, vq = r["params"], r["vq_states"]
    else:
        params = init_gnn(jax.random.PRNGKey(args.seed), cfg)
        vq = init_vq_states(jax.random.PRNGKey(args.seed + 1), cfg, g.n)
    if args.precision != "fp32":
        vq = quantize_vq_states(vq, cfg, precision=args.precision)

    mesh = shd.graph_dp_mesh(args.mesh) if args.mesh else None
    server = GNNServer(g, cfg, params, vq, args.batch, mesh=mesh,
                       shard_graph=args.shard_graph)
    t_refresh = server.refresh()
    t_warm = server.warmup()

    rng = np.random.default_rng(args.seed)
    sizes = rng.integers(1, args.max_request + 1, args.requests)
    requests = [rng.integers(0, g.n, sz) for sz in sizes]
    report = drain_requests(server, requests)
    report.update({"graph_n": g.n, "batch": server.batch,
                   "backbone": args.backbone,
                   "mesh": args.mesh or 1,
                   "shard_graph": bool(args.shard_graph),
                   "graph_state_bytes_per_device":
                       server.graph_state_bytes_per_device(),
                   "precision": args.precision,
                   "vq_state_bytes": int(sum(
                       tree_bytes((s.assignment,) if s.qcw is None
                                  else (s.assignment, s.qcw))
                       for s in server.vq)),
                   "refresh_s": t_refresh, "warmup_s": t_warm})

    print(f"serve_gnn {args.backbone} n={g.n} batch={server.batch} "
          f"mesh={report['mesh']}"
          f"{' (row-sharded graph state)' if args.shard_graph else ''} "
          f"precision={args.precision} "
          f"(vq operand bytes {report['vq_state_bytes']}, graph state "
          f"{report['graph_state_bytes_per_device']} B/device): "
          f"refresh {t_refresh:.2f}s, warm compile {t_warm:.2f}s")
    print(f"  {report['nodes']} nodes / {report['requests']} requests in "
          f"{report['wall_s']:.3f}s -> {report['nodes_per_s']:.0f} nodes/s, "
          f"{report['requests_per_s']:.1f} req/s")
    print(f"  step   p50 {report['step_p50_ms']:.2f} ms   "
          f"p99 {report['step_p99_ms']:.2f} ms")
    print(f"  request p50 {report['request_p50_ms']:.2f} ms   "
          f"p99 {report['request_p99_ms']:.2f} ms")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
