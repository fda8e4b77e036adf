"""Pass 2: Pallas VMEM static analysis (REPRO20x).

Walks every ``pallas_call`` equation of every registered entry's traced
jaxpr and computes its per-dispatch VMEM working set from the grid
mapping itself -- the sum of BlockSpec block bytes over the operands the
kernel actually holds in VMEM (operands in the ``any`` memory space are
HBM-resident and DMA'd manually; they charge their scratch buffers, not
their array bytes).

  REPRO201  a dispatch's computed VMEM working set exceeds the per-core
            envelope (2x the dispatch-heuristic budget: the heuristic
            reserves half of the ~16 MiB core VMEM, so any BLOCK footprint
            beyond the full envelope cannot be double-buffered at all).
  REPRO202  a BlockSpec that does not tile its operand evenly (array dim
            not divisible by block dim): the kernels pad their operands
            before dispatch, so a ragged block in a traced jaxpr means a
            padding path was dropped.
  REPRO203  dispatch-crossover cross-check: probe ``kernels/ops.py`` just
            below and just above its size heuristics and verify the
            heuristic agrees with the computed footprints -- below the
            SpMM crossover the resident kernel's working set must fit the
            envelope, above it the whole-matrix-in-VMEM kernel must NOT
            be chosen.  The context probes grow the codebook (k), the
            quantity the fused lookup kernel holds in VMEM: below its
            crossover "one fused dispatch" within the envelope, whose
            traced footprint the rule's charge (``context_ell.vmem_bytes``)
            covers; above it no fused dispatch.

The crossover probes re-derive their shapes from the LIVE budgets
(``_vmem_budget_mb``), so a deployment that overrides
``REPRO_*_VMEM_BUDGET_MB`` is checked against its own configuration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis import Finding
from repro.analysis import registry
from repro.analysis.jaxpr_checks import kernel_name, pallas_calls
from repro.distributed.quantization import dtype_nbits
from repro.kernels.context_ell import vmem_bytes as context_vmem_bytes


def _block_dims(bm) -> tuple:
    """Block extents as ints (``Blocked(block_size=n)`` -> n; squeezed or
    unsized dims -> 1)."""
    dims = []
    for d in bm.block_shape:
        d = getattr(d, "block_size", d)
        dims.append(int(d) if isinstance(d, int) else 1)
    return tuple(dims)


def _block_bytes(bm) -> int:
    total = 1
    for d in _block_dims(bm):
        total *= d
    try:
        nbits = dtype_nbits(bm.array_aval.dtype)
    except (KeyError, TypeError):
        return 0
    return (total * nbits + 7) // 8


def _is_vmem(bm) -> bool:
    """Default-space blocks live in VMEM; 'any' means HBM-resident and
    'smem' the scalar memory (ids and values that drive row reads)."""
    space = getattr(bm.block_aval, "memory_space", None)
    return space is None or "vmem" in str(space).lower()


def _scratch_bytes(eqn) -> int:
    gm = eqn.params["grid_mapping"]
    ns = gm.num_scratch_operands
    if not ns:
        return 0
    body = eqn.params["jaxpr"]
    total = 0
    for var in body.invars[len(body.invars) - ns:]:
        aval = getattr(var.aval, "inner_aval", var.aval)
        shape = getattr(aval, "shape", ())
        try:
            nbits = dtype_nbits(getattr(aval, "dtype", None))
        except (KeyError, TypeError):
            continue  # semaphores and other unsized scratch
        size = 1
        for d in shape:
            size *= int(d)
        total += (size * nbits + 7) // 8
    return total


def dispatch_footprint(eqn) -> int:
    """Computed VMEM bytes of one pallas_call dispatch."""
    gm = eqn.params["grid_mapping"]
    blocks = sum(_block_bytes(bm) for bm in gm.block_mappings
                 if _is_vmem(bm))
    return blocks + _scratch_bytes(eqn)


def _envelope_bytes(kops) -> int:
    budget = max(
        kops._vmem_budget_mb(kops._dispatch_overrides,
                             "REPRO_SPMM_VMEM_BUDGET_MB"),
        kops._vmem_budget_mb(kops._context_overrides,
                             "REPRO_CONTEXT_VMEM_BUDGET_MB"))
    return int(budget * 2 * 2 ** 20)


def check_dispatches(closed_jaxpr, where: str,
                     envelope: int) -> list[Finding]:
    """REPRO201/202 over every pallas_call of one traced jaxpr."""
    findings = []
    for eqn in pallas_calls(closed_jaxpr):
        name = kernel_name(eqn)
        fp = dispatch_footprint(eqn)
        if fp > envelope:
            findings.append(Finding(
                "REPRO201", where, 0,
                f"pallas dispatch '{name}' holds {fp} bytes in VMEM, "
                f"over the {envelope}-byte per-dispatch envelope"))
        for bm in eqn.params["grid_mapping"].block_mappings:
            if not _is_vmem(bm):
                continue
            arr = bm.array_aval.shape
            blk = _block_dims(bm)
            for a, b in zip(arr, blk):
                if b > 0 and int(a) % b != 0:
                    findings.append(Finding(
                        "REPRO202", where, 0,
                        f"'{name}' BlockSpec {tuple(blk)} does not tile "
                        f"operand {tuple(arr)} evenly (pad before "
                        f"dispatch)"))
                    break
    return findings


def _crossover_findings() -> list[Finding]:
    """REPRO203: ops.py heuristics vs computed footprints."""
    from repro.kernels import ops as kops
    findings: list[Finding] = []
    sds = jax.ShapeDtypeStruct
    envelope = _envelope_bytes(kops)
    b, deg = 32, 8

    def spmm_probe(n_src, f):
        args = (sds((b, deg), jnp.int32), sds((b, deg), jnp.float32),
                sds((n_src, f), jnp.float32))
        with registry.forced_pallas():
            # fresh lambda per probe: make_jaxpr caches traces on the
            # (function object, avals) pair, and the dispatch decision
            # must be re-evaluated under the CURRENT overrides
            return jax.make_jaxpr(lambda *a: kops.spmm_ell(*a))(*args)

    budget = int(kops._vmem_budget_mb(
        kops._dispatch_overrides, "REPRO_SPMM_VMEM_BUDGET_MB") * 2 ** 20)
    f = 16
    n_below = int(budget * 0.9) // (f * 4)
    n_above = int(budget * 1.2) // (f * 4)
    below = pallas_calls(spmm_probe(n_below, f))
    if len(below) != 1 or dispatch_footprint(below[0]) > envelope:
        findings.append(Finding(
            "REPRO203", "<crossover:spmm_ell>", 0,
            f"below the SpMM crossover ([{n_below}, {f}] f32) the "
            f"resident dispatch's computed footprint "
            f"{[dispatch_footprint(e) for e in below]} exceeds the "
            f"{envelope}-byte envelope (heuristic admits over-budget "
            f"dispatches)"))
    above = pallas_calls(spmm_probe(n_above, f))
    resident_x = [
        e for e in above
        if any(_is_vmem(bm) and _block_dims(bm) == (  # whole x in VMEM
            tuple(bm.array_aval.shape)) and        # (either orientation)
            max(bm.array_aval.shape) >= n_above
            for bm in e.params["grid_mapping"].block_mappings)]
    if resident_x:
        findings.append(Finding(
            "REPRO203", "<crossover:spmm_ell>", 0,
            f"above the SpMM crossover ([{n_above}, {f}] f32) the "
            f"dispatcher still VMEM-blocks the whole source matrix "
            f"({[kernel_name(e) for e in resident_x]})"))

    nb, fb = 4, 4

    def ctx_probe(k):
        args = (sds((b, deg), jnp.int32), sds((b, deg), jnp.float32),
                sds((nb, 1000), jnp.int32), sds((nb, k, fb), jnp.float32))
        with registry.forced_pallas():
            return jax.make_jaxpr(lambda *a: kops.context_ell(*a))(*args)

    cbudget = int(kops._vmem_budget_mb(
        kops._context_overrides,
        "REPRO_CONTEXT_VMEM_BUDGET_MB") * 2 ** 20)
    # the rule's charge: the double-buffered lookup tables, 2 * nb * fb
    # f32 per codeword, on top of the k-independent id, value and output
    # blocks
    per_k = 2 * nb * fb * 4
    fixed = context_vmem_bytes(nb, 128, fb, deg) - 128 * per_k
    k_below = max(1, (int(cbudget * 0.9) - fixed) // per_k)
    k_above = (int(cbudget * 1.2) - fixed) // per_k + 1
    below = pallas_calls(ctx_probe(k_below))
    charged = context_vmem_bytes(nb, k_below, fb, deg)
    if (len(below) != 1 or "context" not in kernel_name(below[0])
            or dispatch_footprint(below[0]) > min(envelope, charged)):
        findings.append(Finding(
            "REPRO203", "<crossover:context_ell>", 0,
            f"below the context crossover ([{nb}, {k_below}, {fb}] "
            f"codewords) expected ONE fused dispatch within the envelope "
            f"and within the rule's {charged}-byte charge, traced "
            f"{[(kernel_name(e), dispatch_footprint(e)) for e in below]}"
        ))
    above = pallas_calls(ctx_probe(k_above))
    if any("context" in kernel_name(e) for e in above):
        findings.append(Finding(
            "REPRO203", "<crossover:context_ell>", 0,
            f"above the context crossover ([{nb}, {k_above}, {fb}] "
            f"codewords) the fused kernel is still dispatched"))
    return findings


def run(root: str | None = None) -> list[Finding]:
    del root
    from repro.kernels import ops as kops
    envelope = _envelope_bytes(kops)
    findings: list[Finding] = []
    for entry in registry.entries():
        try:
            cj = entry.jaxpr()
        except Exception:
            continue  # the jaxpr pass reports trace failures
        findings.extend(
            check_dispatches(cj, f"<entry:{entry.name}>", envelope))
    findings.extend(_crossover_findings())
    return findings
