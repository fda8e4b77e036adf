"""Pallas kernel micro-benchmarks: interpret-mode correctness deltas vs the
jnp oracles + host-side call timings (TPU wall-times are N/A on this host).

Two entry points:

  run_structured() -> list of dicts {name, us_per_call, metrics, tolerance,
      pass} -- the machine-readable form ``benchmarks/run.py --json`` writes
      to BENCH_kernels.json; entries with a tolerance are PARITY GATES (CI
      fails the bench job when any is out of tolerance via ``--check``).
  run() -> the legacy (name, us, derived) tuples for the CSV printer.

The fused-vs-unfused comparison rows time the CPU execution paths of the
two codebook-update formulations (the dispatch layer's actual CPU code):
fused = one distance pass + scatter-add stats (ref.vq_assign_update);
baseline = assign, then one-hot + einsum stats + recomputed revival
distances (the pre-fusion math).  The fused pass must be no slower.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.distributed.quantization import (pack_nibbles, quantize_tensor,
                                            unpack_nibbles)
from repro.kernels import ops, ref
from repro.kernels.spmm_ell import spmm_ell_pallas
from repro.kernels.spmm_ell_hbm import spmm_ell_hbm_pallas
from repro.kernels.vq_assign import vq_assign_pallas
from repro.kernels.vq_update import vq_assign_update_pallas


def _time(fn, *args, reps=5):
    """Best-of-reps single-call wall time in us (min is the robust
    microbenchmark statistic on a noisy shared host)."""
    jax.block_until_ready(fn(*args))  # compile + warm caches
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        best = min(best, time.time() - t0)
    return best * 1e6


def time_best_s(fn, reps: int = 3) -> float:
    """Best-of-reps wall seconds of ``fn()`` after one warmup call (compile
    + caches) -- the ONE steady-state measurement policy shared by the
    CI-gated whole-loop benches (epoch executor, inference executor):
    gates compare serving regimes, not cold starts, and must not drift
    apart on warmup/reps/clock handling."""
    fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        fn()
        best = min(best, time.time() - t0)
    return best


def _entry(rows, name, us, metrics, tolerance=None):
    ok = True
    if tolerance:
        ok = all(float(metrics[k]) <= float(v) for k, v in tolerance.items())
    rows.append({"name": name, "us_per_call": us, "metrics": metrics,
                 "tolerance": tolerance, "pass": bool(ok)})


def _unfused_update_baseline(x, c):
    """The pre-fusion per-branch update math: assign, then one-hot einsum
    stats, then the revival qerr as a recomputed reconstruction distance."""
    a = ref.vq_assign(x, c)
    onehot = jax.nn.one_hot(a, c.shape[0], dtype=jnp.float32)     # [b, k]
    counts = jnp.sum(onehot, axis=0)
    sums = onehot.T @ x.astype(jnp.float32)
    sel = x.astype(jnp.float32) - c.astype(jnp.float32)[a]
    qerr = jnp.sum(sel * sel, axis=-1)
    return a, qerr, counts, sums


def run_structured() -> list[dict]:
    key = jax.random.PRNGKey(0)
    rows: list[dict] = []

    # --- vq_assign: interpret kernel vs oracle (tie-tolerant) ---
    x = jax.random.normal(key, (512, 8))
    c = jax.random.normal(jax.random.PRNGKey(1), (256, 8))
    got = vq_assign_pallas(x, c, interpret=True)
    want = ref.vq_assign(x, c)
    d = ((x[:, None] - c[None]) ** 2).sum(-1)
    delta = float(jnp.abs(
        jnp.take_along_axis(d, got[:, None].astype(jnp.int32), 1)
        - jnp.take_along_axis(d, want[:, None].astype(jnp.int32), 1)).max())
    us = _time(lambda a, b: vq_assign_pallas(a, b, interpret=True), x, c)
    _entry(rows, "kernel/vq_assign/512x256x8", us,
           {"match": float((got == want).mean()), "dist_delta": delta},
           tolerance={"dist_delta": 1e-5})

    # --- fused vq_update: interpret kernel vs oracle parity.  The gate is
    # tie-tolerant like the vq_assign row: chosen-distance delta + qerr are
    # always gated; counts/sums are gated strictly only when the argmins
    # agree exactly (a legitimate tie-break divergence shifts integer
    # counts, which must not redden CI) ---
    gi, gq, gc, gs = vq_assign_update_pallas(x, c, interpret=True)
    wi, wq, wc, ws = ref.vq_assign_update(x, c)
    delta = float(jnp.abs(
        jnp.take_along_axis(d, gi[:, None].astype(jnp.int32), 1)
        - jnp.take_along_axis(d, wi[:, None].astype(jnp.int32), 1)).max())
    us = _time(lambda a, b: vq_assign_update_pallas(a, b, interpret=True),
               x, c)
    tol = {"dist_delta": 1e-5, "qerr_maxerr": 1e-4}
    if bool((gi == wi).all()):
        tol.update({"counts_maxerr": 0.0, "sums_maxerr": 1e-4})
    _entry(rows, "kernel/vq_update/512x256x8", us,
           {"idx_match": float((gi == wi).mean()), "dist_delta": delta,
            "qerr_maxerr": float(jnp.abs(gq - wq).max()),
            "counts_maxerr": float(jnp.abs(gc - wc).max()),
            "sums_maxerr": float(jnp.abs(gs - ws).max())},
           tolerance=tol)

    # --- fused assign+stats vs unfused assign-then-einsum (CPU paths) at
    # the paper-scale codebook (k=256, f_blk=8) and production batch sizes.
    # The expectation is fused no slower than baseline (typically 1.3-2x
    # faster); the gate is a loose gross-inversion tripwire (2x) rather
    # than a tight bar, because a wall-clock ratio on shared CI runners
    # must not redden the build on scheduling noise ---
    fused = jax.jit(ref.vq_assign_update)
    baseline = jax.jit(_unfused_update_baseline)
    for b in (4096, 65536):
        kx = jax.random.PRNGKey(b)
        xb = jax.random.normal(kx, (b, 8))
        cb = jax.random.normal(jax.random.PRNGKey(b + 1), (256, 8))
        us_fused = _time(fused, xb, cb)
        us_base = _time(baseline, xb, cb)
        _entry(rows, f"kernel/vq_update_fused_vs_unfused/b{b}_k256_f8",
               us_fused,
               {"us_fused": us_fused, "us_baseline": us_base,
                "slowdown": us_fused / max(us_base, 1e-9)},
               tolerance={"slowdown": 2.0})

    # --- spmm_ell resident vs HBM variant sweep over source-matrix sizes.
    # The last shapes exceed the default 8 MiB resident VMEM envelope (the
    # dispatch in kernels/ops.py would pick 'hbm' for them); both variants
    # report so the crossover is visible in one run ---
    for (b, deg, n, f) in [(256, 16, 512, 64),       # resident regime
                           (256, 16, 4096, 128),     # 2 MiB source
                           (512, 16, 16384, 128),    # 8 MiB boundary
                           (512, 16, 32768, 128)]:   # 16 MiB -> HBM regime
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(n + f), 3)
        idx = jax.random.randint(k1, (b, deg), 0, n)
        val = jax.random.normal(k2, (b, deg))
        xs = jax.random.normal(k3, (n, f))
        variant = ops.spmm_ell_variant(n, f, 4)
        got_r = spmm_ell_pallas(idx, val, xs, interpret=True)
        got_h = spmm_ell_hbm_pallas(idx, val, xs, interpret=True)
        want = ref.spmm_ell(idx, val, xs)
        us_r = _time(lambda a, cc, x_: spmm_ell_pallas(
            a, cc, x_, interpret=True), idx, val, xs)
        us_h = _time(lambda a, cc, x_: spmm_ell_hbm_pallas(
            a, cc, x_, interpret=True), idx, val, xs)
        tag = f"{b}x{deg}_src{n}x{f}"
        _entry(rows, f"kernel/spmm_ell_resident/{tag}", us_r,
               {"maxerr": float(jnp.abs(got_r - want).max())},
               tolerance={"maxerr": 1e-3})
        _entry(rows, f"kernel/spmm_ell_hbm/{tag}", us_h,
               {"maxerr": float(jnp.abs(got_h - want).max()),
                "dispatch": variant},
               tolerance={"maxerr": 1e-3})

    # --- fp8 operand tier: both spmm variants on float8_e4m3fn source rows
    # + f32 per-channel scales must match the oracle on the DEQUANTIZED
    # rows (the int8-parity convention: upcast-in-kernel + one f32 dequant
    # epilogue reproduce the quantization grid exactly) ---
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(77), 3)
    idx = jax.random.randint(k1, (256, 16), 0, 4096)
    val = jax.random.normal(k2, (256, 16))
    xs = jax.random.normal(k3, (4096, 64))
    qt = quantize_tensor(xs, dtype=jnp.float8_e4m3fn)
    deq = qt.q.astype(jnp.float32) * qt.scale
    want = ref.spmm_ell(idx, val, deq)
    got_r = spmm_ell_pallas(idx, val, qt.q, x_scale=qt.scale, interpret=True)
    got_h = spmm_ell_hbm_pallas(idx, val, qt.q, x_scale=qt.scale,
                                interpret=True)
    us_r = _time(lambda a, cc, x_, s: spmm_ell_pallas(
        a, cc, x_, x_scale=s, interpret=True), idx, val, qt.q, qt.scale)
    _entry(rows, "kernel/spmm_ell_fp8_resident/256x16_src4096x64", us_r,
           {"maxerr": float(jnp.abs(got_r - want).max())},
           tolerance={"maxerr": 1e-3})
    _entry(rows, "kernel/spmm_ell_fp8_hbm/256x16_src4096x64", 0.0,
           {"maxerr": float(jnp.abs(got_h - want).max())},
           tolerance={"maxerr": 1e-3})

    # --- uint4 assignment emission (the +a4 tiers): the kernel's narrow
    # emit must agree with the int32 emit id-for-id at k <= 16, and the
    # packed table must round-trip through pack/unpack bit-exactly ---
    xq = jax.random.normal(jax.random.PRNGKey(78), (512, 8))
    cq = jax.random.normal(jax.random.PRNGKey(79), (16, 8))
    i32, _, _, _ = vq_assign_update_pallas(xq, cq, interpret=True)
    i4, _, _, _ = vq_assign_update_pallas(xq, cq, interpret=True,
                                          emit_dtype=jnp.uint4)
    packed = pack_nibbles(i4[None].astype(jnp.uint8))
    round_trip = unpack_nibbles(packed, i4.shape[0])[0]
    us4 = _time(lambda a, b_: vq_assign_update_pallas(
        a, b_, interpret=True, emit_dtype=jnp.uint4), xq, cq)
    _entry(rows, "kernel/vq_update_emit_uint4/512x16x8", us4,
           {"idx_match": float((i4.astype(jnp.int32) == i32).mean()),
            "pack_roundtrip_match":
                float((round_trip.astype(jnp.int32) == i32).mean()),
            "idx_mismatches": float((i4.astype(jnp.int32) != i32).sum())},
           tolerance={"idx_mismatches": 0.0})

    return rows


def run() -> list[tuple]:
    out = []
    for e in run_structured():
        derived = ";".join(
            f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in e["metrics"].items())
        if not e["pass"]:
            derived += ";PARITY_FAIL"
        out.append((e["name"], e["us_per_call"], derived))
    return out


if __name__ == "__main__":
    for row in run():
        print(",".join(str(x) for x in row))
