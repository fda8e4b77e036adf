"""Times the HBM SpMM kernel (``spmm_ell_hbm_pallas``) alone on a TPU.

For each live share it prints one JSON line: the median of ``--reps``
calls in ms (the whole wrapper, slot compaction included), the compaction
ahead of the kernel timed alone (where the checkout has one), the live
share as ``live_slots`` counts it, the largest error against
``ref.spmm_ell`` over the largest magnitude of the answer, and a SHA-256
of the output's bytes, so that two checkouts' outputs can be compared bit
for bit.

Shares: ``S:scattered`` keeps each slot live with probability ``S``, as
the in-batch term does (a neighbour inside a random ~n/4 batch);
``S:packed`` gives each row a live prefix, as padding does in a
full-graph apply (``S`` is the mean share; rows draw their prefix
uniformly).  Values of dead slots are 0, their ids random.  Run on a chip,
from the repository root::

    PYTHONPATH=src python benchmarks/spmm_hbm_chip.py \\
        --shares 0.109:scattered,0.44:packed,1.0:scattered --out spmm.jsonl

``--interpret`` runs the kernel in interpret mode (a CPU smoke test at a
small ``--rows``); its times are not device times.
"""
from __future__ import annotations

import argparse
import functools
import hashlib
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref, spmm_ell_hbm


def _median_ms(fn, *args, reps: int) -> float:
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def _operands(share: float, layout: str, rows: int, deg: int, n_src: int,
              f: int, seed: int):
    rng = np.random.default_rng(seed)
    if layout == "scattered":
        live = rng.random((rows, deg)) < share
    elif layout == "packed":
        top = int(round(2 * share * deg))
        live = np.arange(deg)[None, :] < rng.integers(0, top + 1, (rows, 1))
    else:
        raise ValueError(f"unknown layout {layout!r}: scattered or packed")
    idx = rng.integers(0, n_src, (rows, deg)).astype(np.int32)
    val = np.where(live, rng.uniform(0.1, 1.0, (rows, deg)), 0.0)
    x = rng.normal(size=(n_src, f))
    return (jnp.asarray(idx), jnp.asarray(val, jnp.float32),
            jnp.asarray(x, jnp.float32))


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--shares", default="0.109:scattered,0.44:packed,"
                   "1.0:scattered",
                   help="comma-separated SHARE:LAYOUT, LAYOUT scattered or "
                        "packed")
    p.add_argument("--rows", type=int, default=42_336)
    p.add_argument("--deg", type=int, default=32)
    p.add_argument("--src", type=int, default=42_336,
                   help="rows of the source matrix")
    p.add_argument("--f", type=int, default=128)
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tag", default="")
    p.add_argument("--out", default=None, help="append JSON lines here")
    p.add_argument("--interpret", action="store_true")
    a = p.parse_args(argv)

    spmm = jax.jit(functools.partial(spmm_ell_hbm.spmm_ell_hbm_pallas,
                                     interpret=a.interpret))
    compact = getattr(spmm_ell_hbm, "compact_slots", None)
    compact = jax.jit(compact) if compact else None
    counts = getattr(spmm_ell_hbm, "live_slots", None)
    out = open(a.out, "a") if a.out else None
    for item in a.shares.split(","):
        share, layout = item.split(":")
        args = _operands(float(share), layout, a.rows, a.deg, a.src, a.f,
                         a.seed)
        t0 = time.perf_counter()
        got = jax.block_until_ready(spmm(*args))
        compile_s = time.perf_counter() - t0
        want = ref.spmm_ell(*args)
        row = {"tag": a.tag, "share": float(share), "layout": layout,
               "rows": a.rows, "deg": a.deg, "src": a.src, "f": a.f,
               "ms": _median_ms(spmm, *args, reps=a.reps),
               "compact_ms": None, "live_share": None,
               "compile_s": compile_s,
               "rel_err": float(jnp.abs(got - want).max()
                                / max(float(jnp.abs(want).max()), 1e-30)),
               "sha256": hashlib.sha256(
                   np.asarray(got).tobytes()).hexdigest()[:16],
               "device": jax.devices()[0].device_kind}
        if compact is not None:
            jax.block_until_ready(compact(args[0], args[1]))
            row["compact_ms"] = _median_ms(compact, args[0], args[1],
                                           reps=a.reps)
        if counts is not None:
            row["live_share"] = float(counts(args[1]).sum()
                                      / (a.rows * a.deg))
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()


if __name__ == "__main__":
    main()
