"""Pass 1: jaxpr-level contract checks (REPRO10x) over the entry registry.

Every registered entry point is traced abstractly (tiny
ShapeDtypeStruct specs, no device work) and its ClosedJaxpr inspected:

  REPRO101  exact ``pallas_call`` dispatch count under forced kernels --
            in particular ONE fused context dispatch per layer regardless
            of the product-VQ branch count (the registry traces a second
            branch width to prove invariance).
  REPRO102  no host callbacks (``pure_callback`` / ``debug_callback`` /
            ``io_callback``) anywhere in a jitted hot body -- a callback
            inside the epoch scan would fence the device per batch.
  REPRO103  quantized dtype flow: every storage dtype present in the
            entry's operands (int8 / float8_e4m3fn) must reach some
            ``pallas_call`` input, and no ``convert_element_type`` OUTSIDE
            a kernel body upcasts a storage dtype to float -- i.e. no
            host-level dequantization before the kernel (the in-kernel
            f32 epilogue is the only sanctioned upcast).
  REPRO104  donation realized: the AOT-lowered module of each donating
            entry must carry input/output aliasing (``tf.aliasing_output``
            in the StableHLO text) -- a dropped ``donate_argnames`` still
            traces fine but silently doubles peak state memory.
  REPRO105  scan-carry bytes bounded: each ``lax.scan`` carry must fit
            the entry's budget (the donated model/VQ/opt state for the
            epoch executors, one activation table for the inference
            sweep) -- a stowaway [n, D] table in the carry is how O(n)
            leaks into the per-step working set.
  REPRO106  gradient-injection residuals: the saved vjp residuals of
            ``inject_context_grad`` must stay O(b*Dr + k*f) -- no leaf as
            large as the dense [b, Dr, f_grad] reconstruction the lazy
            Eq. 7 form exists to avoid.
  REPRO107  trace-counter contract: entries that promise compile-count
            telemetry must bump their counter exactly once per trace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.analysis import Finding
from repro.analysis import registry
from repro.analysis.trace_count import INFER_TRACE_COUNT
from repro.distributed.quantization import dtype_nbits

_STORAGE = (jnp.dtype(jnp.int8), jnp.dtype(jnp.float8_e4m3fn))


def _sub_jaxprs(eqn):
    """(closed)jaxprs nested in an equation's params."""
    subs = []
    for v in eqn.params.values():
        leaves = jax.tree_util.tree_leaves(
            v, is_leaf=lambda x: isinstance(x, (Jaxpr, ClosedJaxpr)))
        for leaf in leaves:
            if isinstance(leaf, ClosedJaxpr):
                subs.append(leaf.jaxpr)
            elif isinstance(leaf, Jaxpr):
                subs.append(leaf)
    return subs


def iter_eqns(jaxpr, in_kernel: bool = False):
    """Yield ``(eqn, in_kernel)`` over a jaxpr and all nested jaxprs;
    ``in_kernel`` marks equations inside a ``pallas_call`` body."""
    for eqn in jaxpr.eqns:
        yield eqn, in_kernel
        inner = in_kernel or eqn.primitive.name == "pallas_call"
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, inner)


def pallas_calls(closed_jaxpr):
    return [eqn for eqn, ink in iter_eqns(closed_jaxpr.jaxpr)
            if eqn.primitive.name == "pallas_call" and not ink]


def kernel_name(eqn) -> str:
    """A ``pallas_call``'s kernel function name, from the traced body's
    debug info."""
    src = eqn.params["jaxpr"].debug_info.func_src_info or "?"
    return src.split(" ")[0]


def _aval_bytes(aval) -> int:
    size = 1
    for d in aval.shape:
        size *= int(d)
    return (size * dtype_nbits(aval.dtype) + 7) // 8


def check_entry(entry) -> list[Finding]:
    findings: list[Finding] = []
    loc = f"<entry:{entry.name}>"

    # REPRO107 -- counter bump, observable only on a fresh (uncached)
    # trace, so snapshot around the first .jaxpr() call
    check_counter = entry.counter is not None and entry._jaxpr is None
    before = INFER_TRACE_COUNT.snapshot() if check_counter else None
    try:
        cj = entry.jaxpr()
    except Exception as exc:  # a broken entry is itself a finding
        return [Finding("REPRO101", loc, 0,
                        f"entry failed to trace: {type(exc).__name__}: "
                        f"{exc}")]
    if check_counter:
        delta = INFER_TRACE_COUNT.delta(before)
        if delta.get(entry.counter, 0) != 1:
            findings.append(Finding(
                "REPRO107", loc, 0,
                f"expected exactly one '{entry.counter}' trace-counter "
                f"bump per trace, saw {delta.get(entry.counter, 0)} "
                f"(delta {delta})"))

    # REPRO101 -- exact dispatch count
    calls = pallas_calls(cj)
    if entry.pallas_count is not None and len(calls) != entry.pallas_count:
        names = [kernel_name(e) for e in calls]
        findings.append(Finding(
            "REPRO101", loc, 0,
            f"expected exactly {entry.pallas_count} pallas_call "
            f"dispatches, traced {len(calls)}: {names}"))

    # REPRO102 -- no host callbacks anywhere in the body
    for eqn, _ in iter_eqns(cj.jaxpr):
        if "callback" in eqn.primitive.name:
            findings.append(Finding(
                "REPRO102", loc, 0,
                f"host callback '{eqn.primitive.name}' inside the jitted "
                f"body (fences the device every step)"))

    # REPRO103 -- quantized dtype flow
    for dt in entry.quantized_dtypes:
        reaches = any(
            jnp.dtype(v.aval.dtype) == dt
            for eqn in calls for v in eqn.invars
            if hasattr(v, "aval") and hasattr(v.aval, "dtype"))
        if calls and not reaches:
            findings.append(Finding(
                "REPRO103", loc, 0,
                f"quantized operand dtype {dt} never reaches a "
                f"pallas_call input (dequantized upstream?)"))
    if entry.quantized_dtypes:
        for eqn, ink in iter_eqns(cj.jaxpr):
            if ink or eqn.primitive.name != "convert_element_type":
                continue
            src = jnp.dtype(eqn.invars[0].aval.dtype)
            dst = jnp.dtype(eqn.params["new_dtype"])
            if src in _STORAGE and jnp.issubdtype(dst, jnp.floating):
                findings.append(Finding(
                    "REPRO103", loc, 0,
                    f"host-level dequantization {src} -> {dst} outside "
                    f"a kernel body: quantized operands must stay in "
                    f"storage dtype until the in-kernel epilogue"))

    # REPRO104 -- donation aliasing in the lowered module
    if entry.donated_min and entry.lower is not None:
        text = entry.lower().as_text()
        aliased = text.count("tf.aliasing_output")
        if aliased < entry.donated_min:
            findings.append(Finding(
                "REPRO104", loc, 0,
                f"donation not realized: {aliased} aliased outputs in "
                f"the lowered module (expected >= {entry.donated_min}); "
                f"donate_argnames dropped or shapes/dtypes mismatched?"))

    # REPRO105 -- scan carry byte budget
    if entry.carry_budget is not None:
        for eqn, ink in iter_eqns(cj.jaxpr):
            if ink or eqn.primitive.name != "scan":
                continue
            inner = eqn.params["jaxpr"].jaxpr
            nc, ncarry = eqn.params["num_consts"], eqn.params["num_carry"]
            carry = [v.aval for v in inner.invars[nc:nc + ncarry]]
            total = sum(_aval_bytes(a) for a in carry)
            if total > entry.carry_budget:
                findings.append(Finding(
                    "REPRO105", loc, 0,
                    f"scan carry is {total} bytes, over the entry's "
                    f"{entry.carry_budget}-byte budget (a node-indexed "
                    f"table riding the carry?)"))

    return findings


def residual_findings() -> list[Finding]:
    """REPRO106: concrete tiny vjp of the lazy Eq. 7 injection."""
    from repro.core.message_passing import inject_context_grad
    b, dr, nb, k, f_blk, f, n = 16, 8, 4, 8, 4, 8, 40
    f_grad = nb * f_blk
    key = jax.random.PRNGKey(0)
    x_b = jnp.zeros((b, f), jnp.float32)
    rv = jnp.ones((b, dr), jnp.float32)
    ri = jax.random.randint(key, (b, dr), 0, n, jnp.int32)
    gcw = jnp.ones((nb, k, f_blk), jnp.float32)
    asg = jnp.zeros((nb, n), jnp.int32)
    w = jnp.ones((f_grad, f), jnp.float32)

    _, vjp_fn = jax.vjp(
        lambda xb: inject_context_grad(xb, rv, ri, gcw, asg, w), x_b)
    dense = b * dr * f_grad * 4  # the [b, Dr, f_grad] reconstruction
    return residual_leaf_findings(vjp_fn, dense,
                                  "<vjp:inject_context_grad>")


def residual_leaf_findings(vjp_fn, dense_bytes: int,
                           where: str) -> list[Finding]:
    """Flag vjp residuals that reach ``dense_bytes`` (singly or summed)."""
    leaves = jax.tree_util.tree_leaves(vjp_fn)
    sizes = [int(a.size) * dtype_nbits(a.dtype) // 8 for a in leaves
             if hasattr(a, "size")]
    findings = []
    if any(sz >= dense_bytes for sz in sizes):
        findings.append(Finding(
            "REPRO106", where, 0,
            f"a saved vjp residual is as large as the dense [b, Dr, "
            f"f_grad] reconstruction ({max(sizes)} >= {dense_bytes} "
            f"bytes): the lazy Eq. 7 form must save only the "
            f"O(b*Dr + k*f) operands"))
    if sum(sizes) >= dense_bytes:
        findings.append(Finding(
            "REPRO106", where, 0,
            f"total saved vjp residuals ({sum(sizes)} bytes) reach the "
            f"dense reconstruction size ({dense_bytes} bytes)"))
    return findings


def run(root: str | None = None) -> list[Finding]:
    del root  # jaxpr contracts are registry-driven, not path-driven
    findings: list[Finding] = []
    for entry in registry.entries():
        findings.extend(check_entry(entry))
    findings.extend(residual_findings())
    return findings
