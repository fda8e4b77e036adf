"""Measurements that set the benchmark's limits and rates; not part of a
run.  Everything runs in one process, so each program compiles once.

    python bench/calibrate.py readings --workload gcn-arxiv.train \
        --seeds 1,2,3 --seconds 3 [--control high]

prints, for each seed, the numbers the cell compares: the program against
the reference at the configuration's precision, and with ``--control`` the
reference computed at that lower precision in the program's place.  With
``--fault half_batch`` (training cells) the program's epoch leaves out
the second half of every batch, the loss a mean over the rest; with
``--fault upper_half`` (the serve cell) every serve step returns wrong rows
in its upper half of slots.  ``--oracle`` runs the program with its Pallas
kernels switched off (its ``kernels/ref.py`` oracle path), a second witness
beside the reference.

    python bench/calibrate.py knee --workload gcn-arxiv.serve \
        --rates 3000,4000,5000 --seconds 8 --seed 1

offers the serve cell's traffic at each rate and prints its latency
percentiles, step time, fill and how long the backlog took to drain.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(workload: str, require_tpu: bool = True):
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from bench import run
    from bench.harness import cell as cellmod
    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: no TPU")
    cell = cellmod.resolve(ROOT, workload)
    run.configure_jax(cell.config)
    return cell, cellmod.runner(ROOT, cell.mix["kind"])


def half_batch() -> None:
    """Plant a fault under the timed path: every batch's second half is
    masked out of the epoch executor's loss."""
    from repro.models import gnn
    real = gnn.vq_train_epoch

    def broken(params, vq, ost, plan, perm, smask, *rest):
        half = smask.shape[1] // 2
        return real(params, vq, ost, plan, perm,
                    smask.at[:, half:].set(0.0), *rest)
    gnn.vq_train_epoch = broken


def upper_half() -> None:
    """Plant a fault where serve answers are produced: the rows of the
    upper half of every step's slots come back shifted by one row."""
    from repro.launch.serve_gnn import GNNServer
    real = GNNServer.step

    def broken(self, ids):
        out = np.array(real(self, ids))
        half = out.shape[0] // 2
        out[half:] = np.roll(out[half:], 1, axis=0)
        return out
    GNNServer.step = broken


FAULTS = {"half_batch": half_batch, "upper_half": upper_half}


def readings(args) -> None:
    cell, kind = _setup(args.workload)
    prec = cell.config["matmul_precision"]
    if args.fault:
        FAULTS[args.fault]()
    if args.oracle:
        from repro.kernels import ops
        ops._use_pallas = lambda: False
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.time()
        drv = kind.Run(cell, seed, args.seconds)
        drv.setup()
        if args.seconds > 0:
            drv.window(args.seconds)
        drv.release()
        gc.collect()
        out = {"seed": seed, "program": drv.readings(prec)}
        if args.control:
            out["control"] = drv.readings(args.control)
        out["s"] = time.time() - t0
        print("READINGS " + json.dumps(out), flush=True)
        del drv
        gc.collect()


def knee(args) -> None:
    cell, kind = _setup(args.workload)
    from bench.harness import traffic
    drv = kind.Run(cell, args.seed, args.seconds)
    drv.setup()
    for rate in [float(r) for r in args.rates.split(",")
                 for _ in range(args.reps)]:
        mix = dict(cell.mix, rate_rps=rate)
        drv.req = traffic.open_loop(mix, drv.n, args.seconds, args.seed)
        drv.picked = np.zeros(0, np.int64)
        e2e = drv.window(args.seconds)
        lat = drv.latency_ms
        print("KNEE " + json.dumps({
            "rate_rps": rate, "requests": len(lat),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "steps": drv.steps, "fill": drv.real / max(1, drv.steps
                                                        * drv.slots),
            "step_ms_p50": float(np.median(drv.step_dur)),
            "step_ms_max": float(np.max(drv.step_dur)),
            "drain_s": drv.elapsed - args.seconds,
            "nodes_per_s": e2e["serve_nodes_per_s"]}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    r = sub.add_parser("readings")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--seconds", type=float, default=0.0)
    r.add_argument("--control", default="")
    r.add_argument("--fault", default="", choices=("",) + tuple(FAULTS))
    r.add_argument("--oracle", action="store_true")
    k = sub.add_parser("knee")
    k.add_argument("--workload", required=True)
    k.add_argument("--rates", required=True)
    k.add_argument("--seconds", type=float, default=8.0)
    k.add_argument("--seed", type=int, default=1)
    k.add_argument("--reps", type=int, default=1)
    args = ap.parse_args()
    {"readings": readings, "knee": knee}[args.what](args)


if __name__ == "__main__":
    main()
