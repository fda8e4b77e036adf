"""Bring-up check: the VQ-GNN main path on TPU chips at the paper's widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip mesh paths only

One chip: the paper configuration (``configs/vq_gnn_paper.paper_config``
with ``full_scale=True``: GCN, 3 layers, hidden 128, k = 1024, f_prod = 4)
on ``synthetic_arxiv(n=169343)`` (ogbn-arxiv's node count, f = 128, 40
classes, generated from ``--seed``), with the batch from
``paper_batch_size`` (~n/4).  Phases, all in this one process:

  compile    AOT-compiles the training epoch, the three inference layers
             and the serve step for the run's exact shapes; each compiled
             program must contain a ``tpu_custom_call`` (a Pallas kernel)
  train      ``train_scenario(..., "vq", epochs=2)``: finite, falling
             loss and validation accuracy above chance (1/40)
  infer      ``vq_inference`` over every node
  serve      ``GNNServer`` (batch 4096): refresh, warmup, a drain of a few
             hundred requests
  reference  the kernel path against the ``kernels/ref.py`` oracles on
             this chip under "highest" matmul precision: every layer's
             output for two mini-batches, the parameter gradient of one
             training step, and each main-path kernel alone

``--chips 4``: one training epoch data-parallel with the row-sharded graph
state (``train_vq(mesh=graph_dp_mesh(4), shard_graph=True)``) against the
same epoch's math on device 0 alone (the replica body under ``jax.vmap``,
within ``TRAIN_RTOL``; each replica's b/4 rows form its own mini-batch,
so plain single-device training on b rows is a different run), and the
same requests served from the trained state through a row-sharded
``GNNServer`` against the single-device server (bit-exact).

Any failed check or phase exits non-zero.  Without a TPU, or without the
repository's ``src/repro`` next to this file, it exits non-zero before
printing a result.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Timings and memory on earlier lines are bring-up readings, not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

N_ARXIV = 169343            # ogbn-arxiv's published node count
SERVE_BATCH = 4096
N_REQUESTS = 300
# kernel path vs oracle, both under "highest" precision: max |diff| over
# max(1, max |oracle|) -- f32 summation-order noise is ~1e-6
LAYER_TOL = 2e-3
GRAD_TOL = 2e-3
ASSIGN_AGREE = 0.99         # near-ties may flip between the two distance paths
# four chips: one DP epoch against its math on device 0 alone (psum'd
# gradients and codebook statistics sum in another order), relative to
# max(1, max |param|)
TRAIN_RTOL = 5e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class CheckFailed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


class Phases:
    """Runs named phases, logs wall time and device peak memory."""

    def __init__(self, devices):
        self.devices = devices

    def run(self, name, fn, *args, **kw):
        log(f"[{name}] start")
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        wall = time.perf_counter() - t0
        peaks = [peak_bytes(d) for d in self.devices]
        log(f"[{name}] ok  wall_s={wall:.3f}  peak_bytes_in_use={peaks}")
        return out


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def build(seed: int):
    from repro.configs.vq_gnn_paper import paper_batch_size, paper_config
    from repro.graph.datasets import synthetic_arxiv
    g = synthetic_arxiv(n=N_ARXIV, seed=seed)
    cfg = paper_config(g, backbone="gcn", full_scale=True)
    b = paper_batch_size(g)
    log(f"graph n={g.n} m={g.m} f={g.f} classes={g.num_classes} "
        f"max_degree={g.max_degree()}; config layers={cfg.n_layers} "
        f"hidden={cfg.hidden} k={cfg.codebook.k} "
        f"f_prod={cfg.codebook.f_prod}; batch={b}")
    return g, cfg, b


def compile_steps(g, cfg, b: int, seed: int) -> None:
    """AOT-compile the three jitted entry points for the run's shapes (the
    persistent cache hands them to the later phases) and require a Pallas
    kernel in each."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.graph.batching import EpochPlan, epoch_slices, \
        inference_slices
    from repro.models.gnn import (_layer_out_dims, init_gnn, init_vq_states,
                                  vq_infer_layer, vq_serve_batch,
                                  vq_train_epoch)
    from repro.train.optimizer import rmsprop

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    key = jax.random.PRNGKey(seed)
    params = jax.eval_shape(lambda k: init_gnn(k, cfg), key)
    vq = jax.eval_shape(lambda k: init_vq_states(k, cfg, g.n), key)
    opt = rmsprop(3e-3)
    ost = jax.eval_shape(opt.init, params)
    d = g.max_degree()
    plan = EpochPlan(sds((g.n, d), jnp.int32), sds((g.n, d), jnp.float32),
                     sds((g.n, d), jnp.int32), sds((g.n, d), jnp.float32))
    s_train = epoch_slices(np.arange(g.n), b)[0].shape
    s_inf = inference_slices(g.n, b)[0].shape
    x = sds((g.n, g.f), jnp.float32)
    deg = sds((g.n,), jnp.float32)
    progs = {
        "train_epoch": vq_train_epoch.lower(
            params, vq, ost, plan, sds(s_train, jnp.int32),
            sds(s_train, jnp.float32), x, sds((g.n,), jnp.int32),
            sds((g.n,), jnp.float32), deg, cfg, opt),
        "serve_step": vq_serve_batch.lower(
            params, vq, plan, sds((SERVE_BATCH,), jnp.int32), x, deg, cfg),
    }
    for l, (fi, _) in enumerate(_layer_out_dims(cfg)):
        progs[f"infer_layer{l}"] = vq_infer_layer.lower(
            params[l], vq[l], plan, sds(s_inf, jnp.int32),
            sds(s_inf, jnp.float32), sds((g.n, fi), jnp.float32), deg,
            cfg, l, False)
    for name, lowered in progs.items():
        t0 = time.perf_counter()
        text = lowered.compile().as_text()
        n_kern = text.count("tpu_custom_call")
        log(f"  compiled {name}: compile_s={time.perf_counter() - t0:.3f} "
            f"tpu_custom_call={n_kern}")
        check(n_kern > 0, f"{name}: no Pallas kernel in the compiled "
              f"program although the dispatch runs kernels on a TPU")


def train(g, cfg, b: int, seed: int):
    import numpy as np
    from repro.train.gnn_trainer import train_scenario
    r = train_scenario(g, cfg, "vq", epochs=2, batch_size=b, seed=seed,
                       eval_every=2)
    losses = [np.asarray(ep, np.float64) for ep in r["losses"]]
    for e, ep in enumerate(losses):
        log(f"  epoch {e + 1} step losses {np.round(ep, 4).tolist()}")
    flat = np.concatenate(losses)
    final = r["final"]
    log(f"  val_acc={final['val']:.4f} test_acc={final['test']:.4f} "
        f"vq_err={final.get('vq_err', float('nan')):.4f}")
    check(bool(np.all(np.isfinite(flat))), "training loss is not finite")
    check(losses[-1].mean() < losses[0].mean() and flat[-1] < flat[0],
          "training loss did not fall")
    check(final["val"] > 1.0 / g.num_classes,
          f"validation accuracy {final['val']:.4f} is not above chance")
    return r


def infer(g, cfg, b: int, r):
    import numpy as np
    from repro.train.gnn_trainer import vq_inference
    emb = vq_inference(r["params"], r["vq_states"], g, cfg, b)
    check(emb.shape == (g.n, g.num_classes), f"inference shape {emb.shape}")
    check(bool(np.all(np.isfinite(emb))), "inference output is not finite")
    acc = float((np.argmax(emb[g.test_idx], -1)
                 == g.labels[g.test_idx]).mean())
    log(f"  inference test_acc={acc:.4f}")
    return emb


def make_requests(g, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 65, N_REQUESTS)
    return [rng.integers(0, g.n, s) for s in sizes]


def serve(g, cfg, r, seed: int):
    import numpy as np
    from repro.launch.serve_gnn import GNNServer, drain_requests
    server = GNNServer(g, cfg, r["params"], r["vq_states"], SERVE_BATCH)
    log(f"  refresh_s={server.refresh():.3f} warmup_s={server.warmup():.3f}")
    reqs = make_requests(g, seed)
    report = drain_requests(server, reqs)
    log("  " + json.dumps({k: report[k] for k in (
        "requests", "steps", "nodes", "wall_s", "nodes_per_s",
        "step_p50_ms", "step_p99_ms", "request_p99_ms")}))
    out = server.serve(np.concatenate(reqs[:8]))
    check(bool(np.all(np.isfinite(out))), "served outputs are not finite")
    check(report["nodes"] == sum(len(q) for q in reqs),
          "drain lost request slots")


def oracle_path():
    """Context manager routing every ``kernels.ops`` dispatch to the
    ``ref.py`` oracles for the calls traced inside it."""
    from unittest import mock
    from repro.kernels import ops
    return mock.patch.object(ops, "_use_pallas", lambda: False)


def reference(g, cfg, b: int, r, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.graph.batching import build_epoch_plan, full_operands, \
        plan_batch
    from repro.kernels import ops, ref
    from repro.kernels.context_ell import context_ell_pallas
    from repro.kernels.spmm_ell import spmm_ell_pallas
    from repro.kernels.spmm_ell_hbm import spmm_ell_hbm_pallas
    from repro.kernels.vq_update import vq_assign_update_pallas
    from repro.core.conv import fixed_conv_operands, layer_codewords
    from repro.models.gnn import node_loss, probe_shapes, vq_forward

    params, vq = r["params"], r["vq_states"]
    ops_full = full_operands(g)
    plan = build_epoch_plan(g, full_ops=ops_full)
    x = jnp.asarray(g.features)
    labels = jnp.asarray(g.labels)
    perm = np.random.default_rng(seed + 7).permutation(g.n)
    # the graph tables are arguments, not closed-over constants a jit
    # would embed in the program
    tables = (x, labels, plan, vq, ops_full.degrees)

    def programs():
        """Fresh closures per path: jax caches traces by function
        identity, so the oracle path must not reuse the kernel path's."""
        def forward(p, ids, x, labels, plan, vq, deg):
            pack = plan_batch(plan, ids)
            out, acts = vq_forward(p, x[ids], None, pack, vq, deg, cfg,
                                   inject=False)
            return acts[1:] + [out]

        def loss(p, ids, x, labels, plan, vq, deg):
            pack = plan_batch(plan, ids)
            probes = [jnp.zeros(s, jnp.float32)
                      for s in probe_shapes(cfg, b)]
            out, _ = vq_forward(p, x[ids], probes, pack, vq, deg, cfg)
            return node_loss(out, labels[ids], cfg.multilabel)

        args = (params, batches[0]) + tables
        return (jax.jit(forward).lower(*args).compile(),
                jax.jit(jax.grad(loss)).lower(*args).compile())

    batches = [jnp.asarray(perm[i * b:(i + 1) * b], jnp.int32)
               for i in range(2)]
    with jax.default_matmul_precision("highest"):
        kern_fwd, kern_grad = programs()
        with oracle_path():
            ref_fwd, ref_grad = programs()
        n_kern = kern_fwd.as_text().count("tpu_custom_call")
        n_ref = ref_fwd.as_text().count("tpu_custom_call")
        log(f"  tpu_custom_call in the compiled forward: kernel path "
            f"{n_kern}, oracle path {n_ref}")
        check(n_ref == 0, "the oracle path compiled a Pallas kernel")
        check(n_kern > 0 or ops.interpret_mode(),
              "the kernel path compiled no Pallas kernel")
        for i, ids in enumerate(batches):
            errs = [rel_err(k, o) for k, o in zip(
                kern_fwd(params, ids, *tables), ref_fwd(params, ids, *tables))]
            log(f"  batch {i}: layer output max rel err "
                f"{[f'{e:.3e}' for e in errs]} (tol {LAYER_TOL})")
            check(max(errs) <= LAYER_TOL, f"batch {i} layer outputs differ "
                  f"from the oracle: {errs}")
        ids = batches[1]
        gk = jax.tree_util.tree_leaves(kern_grad(params, ids, *tables))
        go = jax.tree_util.tree_leaves(ref_grad(params, ids, *tables))
        gerr = max(rel_err(a, o) for a, o in zip(gk, go))
        log(f"  training-step param gradient max rel err {gerr:.3e} "
            f"(tol {GRAD_TOL})")
        check(gerr <= GRAD_TOL, f"kernel-path gradient differs: {gerr}")

        # each main-path kernel alone, on this batch's layer-0 operands
        pack = plan_batch(plan, ids)
        conv, _ = fixed_conv_operands("gcn", pack, ops_full.degrees)
        fcw, _ = layer_codewords(vq[0], g.f, cfg.layer_codebook_cfg())
        assign = vq[0].assignment
        xb = x[ids]
        interp = ops.interpret_mode()
        checks = {
            "spmm_ell_hbm": (spmm_ell_hbm_pallas(
                jnp.maximum(conv.in_pos, 0), conv.in_vals, xb,
                interpret=interp),
                ref.spmm_ell(jnp.maximum(conv.in_pos, 0), conv.in_vals, xb)),
            "spmm_ell_resident": (spmm_ell_pallas(
                jnp.maximum(conv.in_pos[:SERVE_BATCH], 0) % SERVE_BATCH,
                conv.in_vals[:SERVE_BATCH], xb[:SERVE_BATCH],
                interpret=interp),
                ref.spmm_ell(
                    jnp.maximum(conv.in_pos[:SERVE_BATCH], 0) % SERVE_BATCH,
                    conv.in_vals[:SERVE_BATCH], xb[:SERVE_BATCH])),
            "context_ell": (context_ell_pallas(
                conv.out_ids, conv.out_vals, assign, fcw,
                interpret=interp),
                ref.context_ell(conv.out_ids, conv.out_vals, assign, fcw)),
        }
        for name, (k, o) in checks.items():
            e = rel_err(k, o)
            log(f"  kernel {name}: max rel err {e:.3e} (tol {LAYER_TOL})")
            check(e <= LAYER_TOL, f"{name} differs from its oracle: {e}")
        v = jax.random.normal(jax.random.PRNGKey(seed), (b, 8))
        cw = vq[0].codebook.codewords_w[0]
        ka, kq, kc, ks = vq_assign_update_pallas(v, cw,
                                                 interpret=interp)
        oa, oq, oc, os_ = ref.vq_assign_update(v, cw)
        agree = float(jnp.mean(ka == oa))
        log(f"  kernel vq_assign_update: assignment agreement {agree:.5f} "
            f"(min {ASSIGN_AGREE}), qerr max rel err {rel_err(kq, oq):.3e}")
        check(agree >= ASSIGN_AGREE, f"vq_assign_update agreement {agree}")
        check(rel_err(kq, oq) <= LAYER_TOL, "vq_assign_update qerr differs")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def dp_on_device0(g, cfg, b: int, seed: int, nd: int):
    """The data-parallel epoch's math on device 0 alone: the per-replica
    step body under ``jax.vmap`` over ``nd`` lanes with the mesh's axis
    name (psums become lane sums), fed exactly what ``train_vq`` feeds
    the mesh for this seed.  Returns (lane-0 params, losses)."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.graph.batching import build_epoch_plan, epoch_slices, \
        full_operands
    from repro.models.gnn import _vq_epoch_body, init_gnn, init_vq_states
    from repro.train.optimizer import rmsprop

    ops_full = full_operands(g)
    params = init_gnn(jax.random.PRNGKey(seed), cfg)
    vq = init_vq_states(jax.random.PRNGKey(seed + 1), cfg, g.n)
    opt = rmsprop(3e-3)
    tm = np.zeros(g.n, np.float32)
    tm[g.train_idx] = 1.0
    ids, smask = epoch_slices(
        np.random.default_rng(seed).permutation(np.arange(g.n)), b)
    s_, bl = ids.shape[0], b // nd
    perm = jnp.asarray(ids.astype(np.int32)).reshape(s_, nd, bl)
    sm = jnp.asarray(smask).reshape(s_, nd, bl)
    body = functools.partial(_vq_epoch_body, cfg=cfg, opt=opt,
                             axis_name="data")
    run = jax.jit(jax.vmap(body, in_axes=(None,) * 4 + (0, 0) + (None,) * 4,
                           axis_name="data"))
    out = run(params, vq, opt.init(params),
              build_epoch_plan(g, full_ops=ops_full),
              perm.transpose(1, 0, 2), sm.transpose(1, 0, 2),
              jnp.asarray(g.features), jnp.asarray(g.labels),
              jnp.asarray(tm), ops_full.degrees)
    lane0 = jax.tree_util.tree_map(lambda a: a[0], out[0])
    return lane0, np.asarray(out[3][0])


def four_chips(g, cfg, b: int, seed: int) -> None:
    import jax
    import numpy as np
    from repro.distributed.sharding import graph_dp_mesh
    from repro.launch.serve_gnn import GNNServer
    from repro.train.gnn_trainer import train_vq

    nd = 4
    b = b - b % nd
    mesh = graph_dp_mesh(nd)
    log(f"  mesh {mesh.devices.tolist()}; dp batch {b}")
    dp = train_vq(g, cfg, epochs=1, batch_size=b, seed=seed, eval_every=1,
                  mesh=mesh, shard_graph=True)
    ref_params, ref_losses = dp_on_device0(g, cfg, b, seed, nd)
    pe = max(rel_err(a, o) for a, o in zip(
        jax.tree_util.tree_leaves(dp["params"]),
        jax.tree_util.tree_leaves(ref_params)))
    le = float(np.max(np.abs(dp["losses"][0] - ref_losses)))
    log(f"  train: sharded DP on {nd} chips vs the same epoch on device 0: "
        f"params max rel err {pe:.3e} (tol {TRAIN_RTOL}), loss max abs "
        f"diff {le:.3e}; losses {np.round(dp['losses'][0], 4).tolist()}; "
        f"val {dp['final']['val']:.4f}")
    check(pe <= TRAIN_RTOL, f"sharded DP training drifted from device 0: "
          f"{pe}")

    reqs = np.concatenate(make_requests(g, seed))
    dev0 = jax.devices()[0]
    state0 = jax.device_put((dp["params"], dp["vq_states"]), dev0)
    outs = {}
    for name, state, kw in (
            ("device0", state0, {}),
            ("sharded", (dp["params"], dp["vq_states"]),
             dict(mesh=mesh, shard_graph=True))):
        # no refresh: both serve the trained assignments, which keeps the
        # phase to one new program per server
        server = GNNServer(g, cfg, *state, SERVE_BATCH, **kw)
        t0 = time.perf_counter()
        outs[name] = server.serve(reqs)
        log(f"  serve {name}: {len(reqs)} nodes in "
            f"{time.perf_counter() - t0:.3f}s, compile included")
    same = np.array_equal(outs["device0"], outs["sharded"])
    log(f"  serve: sharded == device0 bit-exact: {same} (max abs diff "
        f"{float(np.max(np.abs(outs['device0'] - outs['sharded']))):.3e})")
    check(same, "sharded serving is not bit-exact against device 0")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1
    from repro import hostenv
    log(f"compile cache: {hostenv.enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}; "
        f"jax {jax.__version__}")

    used = devices[:args.chips]
    phases = Phases(used)
    try:
        g, cfg, b = phases.run("build", build, args.seed)
        if args.chips == 4:
            phases.run("four_chips", four_chips, g, cfg, b, args.seed)
        else:
            phases.run("compile", compile_steps, g, cfg, b, args.seed)
            r = phases.run("train", train, g, cfg, b, args.seed)
            phases.run("infer", infer, g, cfg, b, r)
            phases.run("serve", serve, g, cfg, r, args.seed)
            phases.run("reference", reference, g, cfg, b, r, args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
