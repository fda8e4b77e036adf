"""Fused VQ-context bench (DESIGN.md section 10): the one-pass multi-branch
codeword SpMM forward vs the pre-fusion per-branch loop, and the streaming
Eq. 7 backward vs the materialized-residual injection.

Two entry points (the ``benchmarks/run.py`` convention):

  run_structured() -> rows for BENCH_context.json.  Gated rows:
      * ``context/fused_vs_loop/nb4_k256_b4096`` -- the fused forward
        (ONE dispatch: ``ops.context_ell``) must be >= 1.5x the pre-fusion
        per-branch path at the OP-DISPATCH level: a Python loop issuing one
        SpMM dispatch per product-VQ branch + concat, eagerly -- which is
        how the pre-PR mini-batched inference path (``vq_inference``:
        un-jitted per-layer ``vq_apply`` calls) actually paid for it, and
        the CPU analogue of the nb-kernel-launch cost a TPU pays even
        inside jit (pallas_call boundaries don't fuse).
        ``fused_over_loop <= 1/1.5`` (ISSUE 4 acceptance).  The companion
        ``.../jit`` row reports the ratio with BOTH forms compiled into
        one XLA program (the jitted-train-step regime, where the two
        necessarily converge on CPU because XLA fuses the loop's ops
        itself) -- reported ungated so a within-jit regression stays
        visible in the artifact without a wall-clock-noise gate on a ~1x
        ratio.
      * ``context/bwd_residual/...`` -- the measured vjp residual bytes of
        the streaming backward must be <= 0.5x the materialized form's
        (deterministic: counted from the residual arrays jax actually
        saves, no wall-clock noise).
      * ``context/a4_*`` -- the nibble-packed assignment tier (DESIGN.md
        section 15): fused-kernel parity on a packed table + fp8
        codewords, exact packed-table bytes (<= 0.5x uint8, <= 0.125x
        int32), and the fused/loop crossover: the table is gathered in
        XLA and never enters VMEM, so the smallest VMEM budget at which a
        term dispatches fused (bisected on the traced dispatch) must be
        the same for int32, uint8 and packed tables at any node count
        (``crossover_spread`` == 0).
      * interpret-mode kernel parity vs the oracle (maxerr), the
        bench_kernels convention.
  run() -> legacy (name, us, derived) tuples for the CSV printer.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.bench_kernels import _entry, _time
from repro.core.message_passing import (ConvOperands, approx_message_passing,
                                        context_messages_reconstruct,
                                        inject_context_grad_materialized,
                                        intra_messages, reconstruct)
from repro.distributed.quantization import (PackedAssignment,
                                            quantize_codewords, tree_bytes)
from repro.analysis.trace_count import CONTEXT_TRACE_COUNT
from repro.kernels import ops, ref
from repro.kernels.context_ell import context_ell_pallas

_FWD_GATE = {"fused_over_loop": 1.0 / 1.5}   # fused must be >= 1.5x
_RES_GATE = {"residual_ratio": 0.5}          # streaming residual <= 0.5x
_MEM_GATE = {"int8_operand_ratio": 0.5}      # int8 operand bytes <= 0.5x
_A4_MEM_GATE = {"a4_over_uint8_bytes": 0.5,  # packed table <= 0.5x uint8
                "a4_over_int32_bytes": 0.125}    # ... <= 0.125x int32
_A4_CROSS_GATE = {"crossover_spread": 0.0}   # one crossover for all tables


def _context_case(b, deg, n, nb, k, f_blk, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    ids = jax.random.randint(ks[0], (b, deg), 0, n).astype(jnp.int32)
    val = jax.random.normal(ks[1], (b, deg))
    assign = jax.random.randint(ks[2], (nb, n), 0, k).astype(jnp.int32)
    cw = jax.random.normal(ks[3], (nb, k, f_blk))
    return ids, val, assign, cw


def _legacy_loop(out_ids, out_vals, assignment, codewords):
    """The pre-fusion context forward: a Python loop issuing one SpMM per
    branch after materializing the [nb, b, D] gathered-assignment tensor,
    then a concat -- exactly ``ops._context_ell_loop``, the shipped 'loop'
    dispatch fallback, so the baseline can never drift from the code path
    it represents.  Timed eagerly it reproduces the pre-PR
    ``vq_inference`` dispatch cost; under ``jax.jit`` it reproduces the
    pre-PR train-step regime (module docstring)."""
    return ops._context_ell_loop(out_ids, out_vals, assignment, codewords,
                                 None)


def _legacy_amp(ops_, x_b, fcw, gcw, assignment, w):
    """Pre-PR approx_message_passing: the Eq. 7 injection materializes the
    reconstructed [b, Dr, f_grad] gradient-codeword tensor in the forward
    pass and carries it as the vjp residual."""
    grad_hat = jax.lax.stop_gradient(
        reconstruct(gcw, assignment, ops_.rev_ids))
    x_b = inject_context_grad_materialized(x_b, ops_.rev_vals, grad_hat, w)
    m = intra_messages(ops_.in_pos, ops_.in_vals, x_b)
    return m + context_messages_reconstruct(
        ops_.out_vals, ops_.out_ids, fcw, assignment)


def _residual_bytes(vjp_fn) -> int:
    """Bytes of the residual arrays jax saved for this vjp."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(vjp_fn):
        if leaf.dtype == jax.dtypes.float0:
            continue
        total += leaf.size * leaf.dtype.itemsize
    return total


def _amp_case(b, deg, dr, n, nb, k, f_blk, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    f_in = nb * f_blk
    in_pos = jax.random.randint(ks[0], (b, deg), -1, b).astype(jnp.int32)
    in_vals = jnp.where(in_pos >= 0, jax.random.normal(ks[1], (b, deg)), 0.0)
    out_ids = jax.random.randint(ks[2], (b, deg), 0, n).astype(jnp.int32)
    out_vals = jnp.where(in_pos < 0,
                         jax.random.normal(ks[3], (b, deg)), 0.0)
    rev_ids = jax.random.randint(ks[4], (b, dr), 0, n).astype(jnp.int32)
    rev_vals = jax.random.normal(ks[5], (b, dr))
    fcw = jax.random.normal(ks[6], (nb, k, f_blk))
    gcw = jax.random.normal(ks[7], (nb, k, f_blk))
    assign = jax.random.randint(ks[8], (nb, n), 0, k).astype(jnp.int32)
    x_b = jax.random.normal(ks[9], (b, f_in))
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (f_in, nb * f_blk))
    ops_ = ConvOperands(in_pos, in_vals, out_ids, out_vals,
                        rev_ids, rev_vals)
    return ops_, x_b, fcw, gcw, assign, w


def run_structured() -> list[dict]:
    fast = os.environ.get("REPRO_BENCH_FAST", "1") != "0"
    rows: list[dict] = []

    # --- interpret-mode kernel parity vs oracle (small shape: interpret
    # execution is the sanctioned CPU validation path, not a speed path) ---
    ids, val, assign, cw = _context_case(512, 8, 5000, 4, 256, 8)
    got = context_ell_pallas(ids, val, assign, cw, interpret=True)
    want = ref.context_ell(ids, val, assign, cw)
    us = _time(lambda a, b_, c, d: context_ell_pallas(
        a, b_, c, d, interpret=True), ids, val, assign, cw)
    _entry(rows, "context/kernel_parity/512x8_nb4_k256", us,
           {"maxerr": float(jnp.abs(got - want).max())},
           tolerance={"maxerr": 1e-3})
    w_t = jax.random.normal(jax.random.PRNGKey(9), (4 * 8, 32))
    got = context_ell_pallas(ids, val, assign, cw, w_t=w_t, interpret=True)
    want = ref.context_ell(ids, val, assign, cw, w_t)
    _entry(rows, "context/kernel_parity_wt/512x8_nb4_k256", 0.0,
           {"maxerr": float(jnp.abs(got - want).max())},
           tolerance={"maxerr": 1e-3})

    # --- fused forward vs the per-branch loop.  The gate shape is the
    # ISSUE 4 acceptance shape (nb=4, k=256, b=4096); the loop baseline is
    # the pre-PR dispatch sequence (one SpMM dispatch per branch from
    # Python, eager -- the pre-PR vq_inference regime), the fused path is
    # the ONE ``ops.context_ell`` dispatch.  The jit-vs-jit companion row
    # is reported ungated (module docstring) ---
    grids = [(4096, 16, 100_000, 4, 256, 8, True),
             (1024, 16, 100_000, 2, 256, 8, False)]
    if not fast:
        grids.append((16384, 16, 500_000, 4, 256, 8, False))
    loop_jit = jax.jit(_legacy_loop)
    for b, deg, n, nb, k, f_blk, gated in grids:
        ids, val, assign, cw = _context_case(b, deg, n, nb, k, f_blk)
        us_loop = _time(_legacy_loop, ids, val, assign, cw)
        us_fused = _time(ops.context_ell, ids, val, assign, cw)
        _entry(rows, f"context/fused_vs_loop/nb{nb}_k{k}_b{b}", us_fused,
               {"us_fused": us_fused, "us_loop": us_loop,
                "speedup": us_loop / max(us_fused, 1e-9),
                "fused_over_loop": us_fused / max(us_loop, 1e-9)},
               tolerance=_FWD_GATE if gated else None)
        if gated:
            us_loop_jit = _time(loop_jit, ids, val, assign, cw)
            _entry(rows, f"context/fused_vs_loop/nb{nb}_k{k}_b{b}/jit",
                   us_fused,
                   {"us_fused": us_fused, "us_loop_jit": us_loop_jit,
                    "fused_over_loop_jit":
                        us_fused / max(us_loop_jit, 1e-9)})

    # --- int8 operand path (DESIGN.md section 13).  Parity first: the
    # int8 fused kernel (uint8 assignment + int8 codewords + epilogue
    # dequant) vs the oracle on the DEQUANTIZED tables -- the kernel must
    # reproduce its own quantization grid exactly, so the gate is a tight
    # kernel-correctness bound, not a loose quantization-error bound ---
    ids, val, assign, cw = _context_case(512, 8, 5000, 4, 256, 8)
    qcw = quantize_codewords(cw)
    deq = qcw.q.astype(jnp.float32) * qcw.scale
    ua = assign.astype(jnp.uint8)
    got = context_ell_pallas(ids, val, ua, qcw.q, cw_scale=qcw.scale,
                             interpret=True)
    want = ref.context_ell(ids, val, assign, deq)
    us = _time(lambda a, b_, c, d, e: context_ell_pallas(
        a, b_, c, d, cw_scale=e, interpret=True), ids, val, ua, qcw.q,
        qcw.scale)
    _entry(rows, "context/int8_kernel_parity/512x8_nb4_k256", us,
           {"maxerr": float(jnp.abs(got - want).max())},
           tolerance={"maxerr": 1e-3})
    w_t8 = jax.random.normal(jax.random.PRNGKey(9), (4 * 8, 32))
    got = context_ell_pallas(ids, val, ua, qcw.q, cw_scale=qcw.scale,
                             w_t=w_t8, interpret=True)
    want = ref.context_ell(ids, val, assign, deq, w_t8)
    _entry(rows, "context/int8_kernel_parity_wt/512x8_nb4_k256", 0.0,
           {"maxerr": float(jnp.abs(got - want).max())},
           tolerance={"maxerr": 1e-3})

    # --- int8 operand bytes against the fp32 tables at a serving shape ---
    b, deg, n, nb, k, f_blk = 4096, 16, 100_000, 4, 256, 8
    ids, val, assign, cw = _context_case(b, deg, n, nb, k, f_blk)
    qcw = quantize_codewords(cw)
    ua = assign.astype(jnp.uint8)
    fp32_bytes = assign.size * 4 + cw.size * 4
    int8_bytes = ua.size + qcw.q.size + qcw.scale.size * 4
    _entry(rows, f"context/int8_operand_bytes/nb{nb}_k{k}_n100k", 0.0,
           {"fp32_mb": fp32_bytes / 2**20, "int8_mb": int8_bytes / 2**20,
            "int8_operand_ratio": int8_bytes / fp32_bytes},
           tolerance=_MEM_GATE)

    # --- nibble-packed int4 assignment tables + fp8 codewords (the +a4 /
    # fp8 tiers, DESIGN.md section 15).  Parity first, the int8 convention:
    # the fused kernel on a PACKED uint4 table (unpacked by its XLA-side
    # gather) + fp8 codewords must reproduce the oracle on the
    # dequantized tables exactly ---
    ids, val, assign, cw = _context_case(512, 8, 5000, 4, 16, 8)
    qcw8 = quantize_codewords(cw, dtype=jnp.float8_e4m3fn)
    deq8 = qcw8.q.astype(jnp.float32) * qcw8.scale
    pa = PackedAssignment.pack(assign.astype(jnp.uint8))
    got = context_ell_pallas(ids, val, pa, qcw8.q, cw_scale=qcw8.scale,
                             interpret=True)
    want = ref.context_ell(ids, val, assign, deq8)
    us = _time(lambda a, b_, c, d, e: context_ell_pallas(
        a, b_, c, d, cw_scale=e, interpret=True), ids, val, pa, qcw8.q,
        qcw8.scale)
    _entry(rows, "context/a4_fp8_kernel_parity/512x8_nb4_k16", us,
           {"maxerr": float(jnp.abs(got - want).max())},
           tolerance={"maxerr": 1e-3})

    # --- table bytes: 2 ids/byte halves the uint8 tier's table (8x vs
    # int32); exact sub-byte accounting via the shared tree_bytes ---
    b, deg, n, nb, k, f_blk = 4096, 16, 200_000, 4, 16, 8
    ids, val, assign, cw = _context_case(b, deg, n, nb, k, f_blk)
    qcw = quantize_codewords(cw)
    ua = assign.astype(jnp.uint8)
    pa = PackedAssignment.pack(ua)
    a4_bytes = tree_bytes((pa,))
    u8_bytes = tree_bytes((ua,))
    i32_bytes = tree_bytes((assign,))
    _entry(rows, f"context/a4_table_bytes/nb{nb}_k{k}_n200k", 0.0,
           {"int32_mb": i32_bytes / 2**20, "uint8_mb": u8_bytes / 2**20,
            "a4_mb": a4_bytes / 2**20,
            "a4_over_uint8_bytes": a4_bytes / u8_bytes,
            "a4_over_int32_bytes": a4_bytes / i32_bytes},
           tolerance=_A4_MEM_GATE)

    # --- the crossover gate: the dispatch charges what the fused kernel
    # holds (codebook tables, id and value blocks), never the [nb, n]
    # assignment table.  The smallest budget at which a term of 4 x [16, 8]
    # codewords over 16 slots dispatches fused, bisected on the traced
    # dispatch itself (so the gate cannot drift from the shipped rule),
    # must not depend on the table's storage or its node count ---
    sds = jax.ShapeDtypeStruct
    cnb, ck, cf, cdeg = 4, 16, 8, 16

    def _fused_at(table, budget_mb):
        ops.configure_context_dispatch(reset=True, vmem_budget_mb=budget_mb)
        before = CONTEXT_TRACE_COUNT.snapshot()
        # a fresh function per probe: a trace of the same one is cached
        jax.make_jaxpr(lambda *a: ops._context_ell_kernel(*a))(
            sds((256, cdeg), jnp.int32), sds((256, cdeg), jnp.float32),
            table, sds((cnb, ck, cf), jnp.float32), None, None)
        return CONTEXT_TRACE_COUNT.delta(before)["context.fused"] > 0

    def _crossover_mb(table):
        lo, hi = 2.0 ** -10, 64.0        # loop at lo, fused at hi (MiB)
        for _ in range(24):
            mid = (lo * hi) ** 0.5
            lo, hi = (lo, mid) if _fused_at(table, mid) else (mid, hi)
        return hi

    cross = {}
    try:
        for cn in (1_000, 10_000_000):
            for tag, table in (
                    ("int32", sds((cnb, cn), jnp.int32)),
                    ("uint8", sds((cnb, cn), jnp.uint8)),
                    ("a4", PackedAssignment(sds((cnb, cn // 2), jnp.uint8),
                                            cn))):
                cross[f"crossover_mb_{tag}_n{cn}"] = _crossover_mb(table)
    finally:
        ops.configure_context_dispatch(reset=True)
    _entry(rows, f"context/a4_crossover/nb{cnb}_k{ck}", 0.0,
           {**cross, "crossover_spread":
            max(cross.values()) / min(cross.values()) - 1.0},
           tolerance=_A4_CROSS_GATE)

    # --- streaming vs materialized Eq. 7 backward: wall time of the full
    # jitted value_and_grad, plus the MEASURED vjp residual bytes (what the
    # forward pass actually keeps alive until the backward runs) ---
    b, deg, dr, n, nb, k, f_blk = 4096, 16, 16, 100_000, 4, 256, 8
    ops_, x_b, fcw, gcw, assign, w = _amp_case(b, deg, dr, n, nb, k, f_blk)

    def loss_stream(x):
        return jnp.sum(approx_message_passing(ops_, x, fcw, gcw, assign, w))

    def loss_mat(x):
        return jnp.sum(_legacy_amp(ops_, x, fcw, gcw, assign, w))

    us_stream = _time(jax.jit(jax.value_and_grad(loss_stream)), x_b)
    us_mat = _time(jax.jit(jax.value_and_grad(loss_mat)), x_b)
    _, vjp_stream = jax.vjp(loss_stream, x_b)
    _, vjp_mat = jax.vjp(loss_mat, x_b)
    res_stream = _residual_bytes(vjp_stream)
    res_mat = _residual_bytes(vjp_mat)
    tag = f"b{b}_dr{dr}_nb{nb}_k{k}"
    _entry(rows, f"context/bwd_stream_vs_materialized/{tag}", us_stream,
           {"us_streaming": us_stream, "us_materialized": us_mat,
            "speedup": us_mat / max(us_stream, 1e-9)})
    _entry(rows, f"context/bwd_residual/{tag}", 0.0,
           {"residual_mb_streaming": res_stream / 2**20,
            "residual_mb_materialized": res_mat / 2**20,
            "materialized_tensor_mb": b * dr * nb * f_blk * 4 / 2**20,
            "residual_ratio": res_stream / max(res_mat, 1)},
           tolerance=_RES_GATE)
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
