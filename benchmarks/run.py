"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Scale with REPRO_BENCH_FAST=0
for the full (paper-sized) grids; default is the fast grid (CPU-friendly).

Machine-readable mode (the CI bench job):

    python -m benchmarks.run kernels --json BENCH_kernels.json --check

runs one suite, writes its structured rows (each {name, us_per_call,
metrics, tolerance, pass}) as JSON, and with ``--check`` exits non-zero
when any row with a tolerance is out of tolerance (kernel-vs-oracle parity
deltas).  Suites expose ``run_structured()`` for this; suites that only
have ``run()`` are wrapped with pass=True rows.

Baseline refresh (after a PR intentionally moves gated metrics):

    python -m benchmarks.run --update-baselines [suite ...]

re-runs each named suite (default: every suite with a committed snapshot
under ``benchmarks/baselines/``) and rewrites its BENCH_<suite>.json from
the fresh rows.  It REFUSES to run on a dirty git tree, so a refreshed
baseline always corresponds to an exact committed code state -- commit the
code first, regenerate, then commit the baselines on top.

  Table 2  -> bench_complexity
  Table 3  -> bench_memory
  Fig. 4   -> bench_convergence
  Table 4/7-> bench_performance
  Sec. 6   -> bench_inference
  App. G   -> bench_ablation (the scenario matrix: backbone x scale method
              x task with per-cell accuracy floors vs the full-graph
              oracle, + the CI-gated sampler-executor throughput row;
              the CI ``scenario-matrix`` job runs it with --check and
              uploads BENCH_ablation.json)
  (ours)   -> bench_roofline (from the multi-pod dry-run artifacts)
  (ours)   -> bench_kernels (Pallas kernels, interpret mode, vs oracles)
  (ours)   -> bench_context (fused VQ-context fwd/bwd vs per-branch loop)
  (ours)   -> bench_epoch (epoch executor: host loop vs scan vs shard_map)

Each suite runs in its own subprocess: a single long-lived process
accumulating hundreds of distinct jit executables eventually trips XLA's
CPU JIT ("Failed to materialize symbols"); per-suite isolation bounds that
state and also keeps wall-time numbers independent.  The parent never
imports jax and must not: on a TPU host the first process to initialise
the backend holds the chip, and each suite's child then could not reach it.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

SUITES = ["complexity", "memory", "kernels", "context", "epoch", "roofline",
          "inference", "convergence", "ablation", "performance"]


def run_suite_inline(name: str) -> None:
    import importlib
    mod = importlib.import_module(f"benchmarks.bench_{name}")
    for row in mod.run():
        print(",".join(str(x) for x in row))


def baseline_failures(rows, baseline: dict, *, rel: float = 1.2,
                      floor: float = 0.05, slack: float = 0.02) -> list[str]:
    """Gated metrics regressed >(rel - 1) against a committed baseline.

    The bench-trend gate: every *tolerance-bearing* metric (the CI-gated
    ratios/parity deltas, all "smaller is better" by the ``_entry``
    convention) is compared row-by-name against ``baseline`` (a prior
    BENCH_*.json).  A metric regresses iff the current value exceeds the
    baseline by BOTH the relative factor ``rel`` AND the absolute margin
    ``slack``, AND has consumed more than half its headroom to the hard
    tolerance -- timing ratios deep inside the safe region jitter ~2x
    run-to-run on shared CI hosts, so a trend alarm only means something
    once the metric is actually approaching its gate.  Baselines below
    ``floor`` are skipped for the same reason (any multiple of noise is
    still noise).  Rows absent from the baseline (new benches) never
    fail -- they start the trend.
    """
    base_rows = {r.get("name"): r for r in baseline.get("rows", [])}
    out = []
    for r in rows:
        tol = r.get("tolerance") or {}
        base = base_rows.get(r.get("name"))
        if not tol or base is None:
            continue
        bmet = base.get("metrics") or {}
        for m in tol:
            cur_v, base_v = (r.get("metrics") or {}).get(m), bmet.get(m)
            if cur_v is None or base_v is None:
                continue
            cur_v, base_v = float(cur_v), float(base_v)
            if base_v < floor:
                continue
            try:
                half_gate = float(tol[m]) / 2.0
            except (TypeError, ValueError):
                half_gate = 0.0
            if cur_v > base_v * rel and cur_v > base_v + slack \
                    and cur_v > half_gate:
                out.append(f"{r['name']}:{m} {base_v:.4g}->{cur_v:.4g}")
    return out


def run_suite_structured(name: str, json_path: str | None, check: bool,
                         baseline_path: str | None = None) -> None:
    import importlib
    mod = importlib.import_module(f"benchmarks.bench_{name}")
    if hasattr(mod, "run_structured"):
        rows = mod.run_structured()
    else:
        rows = [{"name": n, "us_per_call": us, "metrics": {"derived": d},
                 "tolerance": None, "pass": True} for n, us, d in mod.run()]
    failures = [r["name"] for r in rows if not r.get("pass", True)]
    trend = []
    if baseline_path:
        with open(baseline_path) as f:
            trend = baseline_failures(rows, json.load(f))
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"suite": name, "rows": rows, "failures": failures,
                       "trend_failures": trend}, f, indent=2)
            f.write("\n")
    for r in rows:
        status = "ok" if r.get("pass", True) else "PARITY_FAIL"
        print(f"{r['name']},{r['us_per_call']},{status}")
    if failures:
        sys.stderr.write(
            f"{len(failures)} row(s) out of tolerance: {failures}\n")
    if trend:
        # passing --baseline IS opting into the trend gate: fail even
        # without --check (gate flags must never fail open)
        sys.stderr.write(
            f"{len(trend)} gated metric(s) regressed >20% vs "
            f"{baseline_path}: {trend}\n")
        raise SystemExit(1)
    if failures and check:
        raise SystemExit(1)


def update_baselines(suites: list[str]) -> None:
    """Re-run ``suites`` and rewrite their committed baseline snapshots.

    Refuses on a dirty git tree (module docstring): the trend gate
    compares against "the metrics at commit X", which only means something
    when the snapshot was generated from exactly that tree.
    """
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_dir = os.path.join(here, "benchmarks", "baselines")
    dirty = subprocess.run(
        ["git", "status", "--porcelain"], capture_output=True, text=True,
        cwd=here).stdout.strip()
    if dirty:
        raise SystemExit(
            "--update-baselines refuses to run on a dirty git tree "
            "(baselines must snapshot a committed code state); commit or "
            f"stash first:\n{dirty}")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "src"), here, env.get("PYTHONPATH", "")])
    # same reasoning as the dirty-tree refusal: a baseline snapshotted
    # from a tree that fails its own static contracts (repro.analysis:
    # dispatch counts, VMEM budgets, lint rules) pins numbers the CI
    # gate would reject anyway
    checker = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--root", here],
        env=env, cwd=here, timeout=1800)
    if checker.returncode != 0:
        raise SystemExit(
            "--update-baselines refuses to run: repro.analysis reports "
            "findings (fix the tree before snapshotting baselines)")
    if not suites:
        suites = sorted(
            f[len("BENCH_"):-len(".json")]
            for f in os.listdir(base_dir)
            if f.startswith("BENCH_") and f.endswith(".json"))
    unknown = [s for s in suites if s not in SUITES]
    if unknown:
        raise SystemExit(f"unknown suite(s) {unknown}; want {SUITES}")
    for name in suites:
        path = os.path.join(base_dir, f"BENCH_{name}.json")
        print(f"regenerating {path} ...")
        sys.stdout.flush()
        # per-suite subprocess isolation, the run-all convention
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", name,
             "--json", path, "--check"],
            env=env, cwd=here, timeout=3600)
        if proc.returncode != 0:
            raise SystemExit(
                f"suite {name!r} failed its own tolerances; baseline NOT "
                f"to be committed in this state")


def main() -> None:
    argv = sys.argv[1:]
    if "--update-baselines" in argv:
        argv.remove("--update-baselines")
        update_baselines(argv)
        return
    json_path = None
    baseline_path = None
    check = False
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
            raise SystemExit("--json requires a path operand")
        json_path = argv[i + 1]
        del argv[i:i + 2]
    if "--baseline" in argv:
        i = argv.index("--baseline")
        if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
            raise SystemExit("--baseline requires a path operand")
        baseline_path = argv[i + 1]
        if not os.path.exists(baseline_path):
            # fail closed: a moved/renamed snapshot must not skip the gate
            raise SystemExit(f"--baseline {baseline_path}: no such file")
        del argv[i:i + 2]
    if "--check" in argv:
        check = True
        argv.remove("--check")
    if json_path or check or baseline_path:
        # gate flags must never fail open: a mistyped suite name has to be
        # a hard error, not a silent fall-through to the run-all path
        if len(argv) != 1 or argv[0] not in SUITES:
            raise SystemExit(
                f"--json/--check/--baseline require exactly one suite of "
                f"{SUITES}, got {argv!r}")
        run_suite_structured(argv[0], json_path, check, baseline_path)
        return
    if argv and argv[0] in SUITES:
        run_suite_inline(argv[0])
        return
    print("name,us_per_call,derived")
    sys.stdout.flush()
    failures = 0
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "src"), here,
         env.get("PYTHONPATH", "")])
    for name in SUITES:
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", name],
            capture_output=True, text=True, env=env, cwd=here,
            timeout=3600)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-2000:])
            print(f"{name}/SUITE_FAILED,0,error")
            failures += 1
        else:
            sys.stdout.write(proc.stdout)
        print(f"{name}/suite_wall,{(time.time()-t0)*1e6:.0f},ok")
        sys.stdout.flush()
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
