"""Runs one benchmark cell once on the accelerator it is started on.

    python bench/run.py --workload gcn-arxiv.train --seed 7 --seconds 20 \
        --trace 0

Set-up makes the cell's graph and weights from ``--seed``, builds the
program's state and runs every program the window uses once (from the
persistent compilation cache after the first run in a checkout).  The window
then drives the cell's traffic for ``--seconds``; with ``--trace 1`` under
the profiler, reporting the per-layer metrics instead of the end-to-end
ones.  Afterwards the program's state is freed and the plain reference
recomputes what the window's programs produced; each number compared is
printed beside its limit, on standard error and as the last key of the
result.  The last line of standard output is the result as one JSON object.

Without a TPU, with fewer chips than the cell asks for, or without the
program under ``src/``, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class NoResult(Exception):
    """The run cannot produce a result; the message says why."""


def _compile_counter():
    """Counts jax's tracing and compiling events from now on."""
    from jax import monitoring
    seen = {"n": 0, "names": []}

    def on_event(name, *_args, **_kw):
        if name.startswith("/jax/core/compile") or \
                name.startswith("/jax/compilation_cache/cache_misses"):
            seen["n"] += 1
            seen["names"].append(name)
    monitoring.register_event_duration_secs_listener(on_event)
    monitoring.register_event_listener(on_event)
    return seen


def configure_jax(config: dict) -> str:
    """Persistent compilation cache inside the checkout (or where the
    environment places it), cached from the first compile; the matmul
    precision the configuration states."""
    import jax
    from repro import hostenv
    cache = hostenv.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    prec = config.get("matmul_precision", "default")
    jax.config.update("jax_default_matmul_precision",
                      None if prec == "default" else prec)
    return cache


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_tpu: bool = True,
             precision: str | None = None, fault=None) -> dict:
    """One run of ``workload``; returns the result object.  ``fault``
    (tests only) is called with the runner after set-up and may break the
    timed path; ``precision`` overrides the reference's (calibration)."""
    from bench.harness import cell as cellmod, report, tracing

    cell = cellmod.resolve(root, workload)
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise NoResult(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise NoResult(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < cell.chips:
        raise NoResult(f"the cell needs {cell.chips} chips, JAX found "
                       f"{len(devices)}")
    configure_jax(cell.config)
    drv = cellmod.runner(root, cell.mix["kind"]).Run(cell, seed, seconds)
    drv.setup()
    if fault is not None:
        fault(drv)
    # what set-up made lives for the whole run: keep it out of the cyclic
    # collector, whose full passes over it stall the host mid-window
    gc.collect()
    gc.freeze()
    compiles = _compile_counter()
    setup_s = time.time() - T_START
    tracer = tracing.Tracer() if trace else None
    if tracer:
        tracer.start()
    e2e = drv.window(seconds)
    summary = tracer.stop(devices[:cell.chips]) if tracer else None
    if compiles["n"]:
        raise NoResult(f"{compiles['n']} compile events inside the window: "
                       f"{sorted(set(compiles['names']))}")
    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:cell.chips]) if stats else 0
    gc.unfreeze()
    drv.release()
    gc.collect()
    t_ref = time.time()
    readings = drv.readings(precision or cell.config["matmul_precision"])
    print(f"reference_s: {time.time() - t_ref:.3f}", file=sys.stderr)
    correct, lines = report.judge(readings, cell.limits)
    attempted, failed = drv.attempted()
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        metrics = report.per_layer(cell, drv, summary, devices[0])
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        metrics = report.end_to_end(cell, e2e, setup_s)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = summary.breakdown()
    result["checks"] = lines
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (NoResult, FileNotFoundError, KeyError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr, flush=True)
        return 1
    from bench.harness import report
    report.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
