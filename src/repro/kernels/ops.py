"""Dispatching wrappers around the Pallas kernels.

On a TPU backend the Pallas kernels run compiled; on the CPU host the system
executes the pure-jnp oracles from ref.py (numerically identical -- the
kernels are validated against them in interpret mode by tests/test_kernels.py,
tests/test_context_ell.py, tests/test_spmm_hbm.py, tests/test_vq_update.py
and the precision sweeps in tests/test_int8.py / tests/test_fp8_int4.py,
and compiled for v5e by tests/test_tpu_compile.py).
Set REPRO_FORCE_PALLAS=1 to route every call through the kernels on the CPU
too, in interpret mode (used by the kernel test sweeps and CI).
``interpret_mode`` is the one place that decides compiled vs interpreted.

Production notes (TPU):
  * ``spmm_ell`` has two variants (DESIGN.md section 3, resident vs HBM):
    the resident kernel holds the full source matrix in VMEM and gathers
    with a one-hot MXU product; for n_src * f beyond the VMEM budget the
    HBM variant keeps it in memory_space=ANY and DMAs one source row per
    neighbor slot.  The size-based dispatch below picks the variant;
    override with
    REPRO_SPMM_VARIANT / REPRO_SPMM_VMEM_BUDGET_MB or
    ``configure_spmm_dispatch``.  The kernel path carries a custom VJP
    whose backward is the XLA-compiled VJP of ``ref.spmm_ell`` (a
    ``pallas_call`` has no transpose rule), so training differentiates
    through it.
  * ``context_ell`` (DESIGN.md section 10) fuses the multi-branch
    VQ-context term -- Eq. 6 forward and the streaming Eq. 7 backward --
    into ONE kernel dispatch regardless of n_branches, a codeword lookup
    in registers (a one-hot for wide branches); dispatch falls back to the
    per-branch loop only when what that kernel holds in VMEM (the
    codebook's tables plus the double-buffered id and value blocks,
    ``context_ell.vmem_bytes``) exceeds the VMEM budget.  The ``[n_branches, n]`` assignment table is
    gathered in XLA and never charged (REPRO_CONTEXT_VARIANT /
    REPRO_CONTEXT_VMEM_BUDGET_MB or ``configure_context_dispatch``).  It
    carries the same oracle-VJP custom rule; ``CONTEXT_TRACE_COUNT``
    counts the terms traced per variant.
  * operand precision tiers (DESIGN.md sections 13/15): codewords may be
    int8 or float8_e4m3fn ``QTensor`` snapshots and assignment tables
    uint8 (k <= 256) or nibble-packed ``PackedAssignment`` (k <= 16);
    every wrapper dispatches on the operand's type/dtype, never on the
    environment, so the tier choice happens once at state construction.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import hostenv
from repro.analysis.trace_count import CONTEXT_TRACE_COUNT
from repro.kernels import autotune, ref
from repro.kernels.vq_assign import vq_assign_pallas
from repro.kernels.vq_update import vq_assign_update_pallas
from repro.kernels.context_ell import context_ell_pallas, vmem_bytes
from repro.kernels.spmm_ell import LANES, lane_tile, spmm_ell_pallas
from repro.kernels.spmm_ell_hbm import spmm_ell_hbm_pallas
from repro.distributed.quantization import PackedAssignment, QTensor


def interpret_mode() -> bool:
    """True when the Pallas kernels must run in the interpreter: on any
    backend but a TPU.  The one place this is decided (the autotuner's
    timing runs ask here too)."""
    return jax.default_backend() != "tpu"


def _use_pallas() -> bool:
    # env knobs resolve through the hostenv snapshot: this runs inside jit
    # traces, where a live os.environ read would desynchronize from jax's
    # executable cache (the env-read-once contract, DESIGN.md section 16)
    if hostenv.env_knob("REPRO_FORCE_PALLAS", "0") == "1":
        return True
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# kernel operand precision tiers (fp32 / int8 / fp8 / +a4 packing)
# ---------------------------------------------------------------------------

# The kernels themselves dispatch on OPERAND TYPE (QTensor codewords, uint8
# or PackedAssignment tables) so jitted callers never read the environment
# inside a trace; this knob only steers the host-side state-construction
# sites (core/conv.py init, models/gnn.py serving, launch/serve_gnn.py) that
# decide which storage dtype to build.  The tier ladder (DESIGN.md section
# 15): 'fp32' (dense), 'int8' (int8 codewords + uint8 assignments, k <= 256),
# 'fp8' (float8_e4m3fn codewords, same uint8 assignments), and the '+a4'
# suffix tiers that additionally nibble-pack the assignment table for
# k <= 16 product branches (two ids per byte, 8x vs int32).
PRECISIONS = ("fp32", "int8", "fp8", "int8+a4", "fp8+a4")
_PRECISIONS = PRECISIONS  # backwards-compat alias
_precision_override: list[str] = []


def _check_precision(p: str, source: str) -> str:
    if p not in PRECISIONS:
        raise ValueError(
            f"{source}={p!r}: unknown kernel precision tier; valid tiers "
            f"are {', '.join(PRECISIONS)}")
    return p


def configure_kernel_precision(precision: Optional[str] = None, *,
                               reset: bool = False) -> None:
    """Programmatic override of REPRO_KERNEL_PRECISION.

    Valid tiers are ``PRECISIONS``; anything else raises (listing them) so
    an unrecognized string can never silently behave like fp32.
    """
    if reset:
        _precision_override.clear()
    if precision is not None:
        _check_precision(precision, "kernel precision")
        _precision_override[:] = [precision]


def kernel_precision() -> str:
    """Active operand-storage precision tier ('fp32' default)."""
    if _precision_override:
        return _precision_override[0]
    return _check_precision(
        hostenv.env_knob("REPRO_KERNEL_PRECISION", "fp32"),
        "REPRO_KERNEL_PRECISION")


def precision_codeword_dtype(precision: Optional[str] = None):
    """Codeword storage dtype of a tier: None (dense f32), int8, or fp8."""
    p = _check_precision(precision if precision is not None
                         else kernel_precision(), "kernel precision")
    if p == "fp32":
        return None
    return jnp.float8_e4m3fn if p.startswith("fp8") else jnp.int8


def precision_packs_assignment(precision: Optional[str] = None) -> bool:
    """True for the '+a4' tiers that nibble-pack assignment tables."""
    p = _check_precision(precision if precision is not None
                         else kernel_precision(), "kernel precision")
    return p.endswith("+a4")


def vq_assign(x: jax.Array, codewords: jax.Array) -> jax.Array:
    if _use_pallas():
        return vq_assign_pallas(x, codewords, interpret=interpret_mode())
    return ref.vq_assign(x, codewords)


def vq_assign_update(x: jax.Array, codewords: jax.Array, *,
                     emit_dtype=jnp.int32
                     ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused assign + cluster stats + per-row quantization error.

    The one-pass primitive of the streaming codebook update (Alg. 2):
    returns (assignment [b], qerr [b], counts [k], sums [k, f]) from a
    single distance computation.  TPU: kernels/vq_update.py (revisited
    VMEM accumulator blocks, no one-hot); CPU: scatter-add oracle.
    ``emit_dtype=jnp.uint8`` (k <= 256) emits the assignment in the
    int8/fp8 tiers' storage dtype straight from the kernel;
    ``emit_dtype=jnp.uint4`` (k <= 16) narrows for the +a4 tiers' nibble
    packing (the kernel block stays uint8 -- no sub-byte output windows).
    """
    if _use_pallas():
        bb, kb = 256, 512
        tuned = autotune.tuned_vq_update(x.shape[0], codewords.shape[0],
                                         x.shape[1])
        if tuned is not None:
            bb, kb = tuned["bb"], tuned["kb"]
        return vq_assign_update_pallas(
            x, codewords, bb=bb, kb=kb, emit_dtype=emit_dtype,
            interpret=interpret_mode())
    idx, qerr, counts, sums = ref.vq_assign_update(x, codewords)
    return idx.astype(emit_dtype), qerr, counts, sums


# ---------------------------------------------------------------------------
# spmm_ell resident-vs-HBM dispatch
# ---------------------------------------------------------------------------

# Per-core VMEM is ~16 MiB; the resident kernel also holds idx/val/out tiles
# and the compiler wants double-buffering headroom for the streamed blocks,
# so by default the source matrix gets half.
_DEFAULT_VMEM_BUDGET_MB = 8.0

# Programmatic overrides (take precedence over the environment) -- the
# config-file hook for deployments that cannot set env vars per-process.
_dispatch_overrides: dict[str, object] = {}


def _vmem_budget_mb(overrides: dict, env_name: str) -> float:
    """Resolve a dispatch VMEM budget: programmatic override > env > default.

    The one shared parse/validate path for the SpMM dispatch, the context
    dispatch, and the autotuner's heuristic fallback (previously copy-pasted
    per consumer).
    """
    raw = overrides.get("vmem_budget_mb",
                        hostenv.env_knob(env_name, _DEFAULT_VMEM_BUDGET_MB))
    try:
        budget = float(raw)  # type: ignore[arg-type]
    except (TypeError, ValueError):
        raise ValueError(
            f"{env_name}={raw!r}: want a positive float (MiB)") from None
    if budget <= 0.0:
        raise ValueError(f"{env_name}={raw!r}: want a positive float (MiB)")
    return budget


def _budget_forced(overrides: dict, env_name: str) -> bool:
    """True when the budget was explicitly configured -- the autotuner then
    stands down (env vars stay authoritative, DESIGN.md section 13)."""
    return "vmem_budget_mb" in overrides or hostenv.env_knob_set(env_name)


def configure_spmm_dispatch(variant: Optional[str] = None,
                            vmem_budget_mb: Optional[float] = None, *,
                            reset: bool = False) -> None:
    """Override spmm_ell dispatch: variant in {'auto', 'resident', 'hbm'}.

    Passing None leaves a setting untouched; 'auto' clears a forced variant.
    ``reset=True`` drops every programmatic override first (back to the
    environment/defaults) -- tests and benchmarks use it so one case's
    overrides never leak into the next.
    """
    if reset:
        _dispatch_overrides.clear()
    if variant is not None:
        if variant not in ("auto", "resident", "hbm"):
            raise ValueError(f"unknown spmm variant: {variant!r}")
        _dispatch_overrides["variant"] = variant
    if vmem_budget_mb is not None:
        _dispatch_overrides["vmem_budget_mb"] = float(vmem_budget_mb)


def spmm_ell_variant(n_src: int, f: int, itemsize: int = 4) -> str:
    """'resident' or 'hbm' for a [n_src, f] source matrix of `itemsize`.

    Precedence: forced variant (programmatic/env) > explicitly configured
    VMEM budget > autotuner measurement (opt-in) > size heuristic against
    the default budget.
    """
    forced = _dispatch_overrides.get(
        "variant", hostenv.env_knob("REPRO_SPMM_VARIANT", "auto"))
    if forced not in ("auto", "resident", "hbm"):
        raise ValueError(
            f"REPRO_SPMM_VARIANT={forced!r}: want auto, resident or hbm")
    if forced in ("resident", "hbm"):
        return str(forced)
    if not _budget_forced(_dispatch_overrides, "REPRO_SPMM_VMEM_BUDGET_MB"):
        tuned = autotune.tuned_spmm(n_src, f, itemsize)
        if tuned is not None:
            return str(tuned["variant"])
    budget_mb = _vmem_budget_mb(_dispatch_overrides,
                                "REPRO_SPMM_VMEM_BUDGET_MB")
    return "hbm" if n_src * f * itemsize > budget_mb * 2 ** 20 \
        else "resident"


def spmm_ell(nbr_idx: jax.Array, nbr_val: jax.Array, x: jax.Array, *,
             x_scale: Optional[jax.Array] = None) -> jax.Array:
    """ELLPACK SpMM with size-based resident/HBM variant dispatch.

    ``x`` may be a ``QTensor`` of int8 or float8_e4m3fn rows (or pass
    ``x_scale`` [1, f] explicitly with a quantized ``x``): the resident
    variant keeps the rows in storage dtype in VMEM, the HBM variant and
    the CPU oracle widen them up front; all accumulate in f32 with one
    dequant epilogue (DESIGN.md sections 13/15).  The autotuner's measured
    ``bb`` flows into whichever variant dispatch picks.
    """
    if isinstance(x, QTensor):
        x, x_scale = x.q, x.scale
    if _use_pallas():
        return _spmm_ell_kernel(nbr_idx, nbr_val, x, x_scale)
    return ref.spmm_ell(nbr_idx, nbr_val, x, x_scale)


def _differentiable(a) -> bool:
    """Float operands of at least 16 bits carry cotangents; ids and
    1-byte quantized storage do not."""
    return a is not None and jnp.issubdtype(a.dtype, jnp.floating) \
        and a.dtype.itemsize >= 2


def _oracle_cotangents(oracle, args: tuple, g) -> list:
    """Cotangents of ``oracle(*args)`` for the differentiable entries of
    ``args`` (None for the rest): the backward of every kernel-path custom
    VJP, compiled by XLA."""
    pos = [i for i, a in enumerate(args)
           if not isinstance(a, PackedAssignment)
           and _differentiable(a)]

    def fn(*diff):
        full = list(args)
        for i, a in zip(pos, diff):
            full[i] = a
        return oracle(*full)

    _, vjp = jax.vjp(fn, *[args[i] for i in pos])
    cts = [None] * len(args)
    for i, ct in zip(pos, vjp(g.astype(jnp.float32))):
        cts[i] = ct
    return cts


@jax.custom_vjp
def _spmm_ell_kernel(nbr_idx, nbr_val, x, x_scale):
    n_src, f = x.shape
    bb = 128
    # key the tuner on the storage dtype, not just itemsize: int8 and
    # fp8 sources share itemsize 1 but are distinct operand regimes
    tuned = autotune.tuned_spmm(n_src, f, x.dtype.itemsize, dtype=x.dtype)
    if tuned is not None:
        bb = int(tuned.get("bb", bb))
    if spmm_ell_variant(n_src, f, x.dtype.itemsize) == "hbm":
        return spmm_ell_hbm_pallas(nbr_idx, nbr_val, x, x_scale=x_scale,
                                   bb=bb, interpret=interpret_mode())
    return spmm_ell_pallas(nbr_idx, nbr_val, x, x_scale=x_scale,
                           bb=bb, interpret=interpret_mode())


def _spmm_ell_fwd(nbr_idx, nbr_val, x, x_scale):
    return (_spmm_ell_kernel(nbr_idx, nbr_val, x, x_scale),
            (nbr_idx, nbr_val, x, x_scale))


def _spmm_ell_bwd(res, g):
    return tuple(_oracle_cotangents(ref.spmm_ell, res, g))


_spmm_ell_kernel.defvjp(_spmm_ell_fwd, _spmm_ell_bwd)


# ---------------------------------------------------------------------------
# fused VQ-context (multi-branch codeword SpMM) dispatch
# ---------------------------------------------------------------------------

# Programmatic overrides for the context dispatch, mirroring the SpMM ones.
_context_overrides: dict[str, object] = {}


def configure_context_dispatch(variant: Optional[str] = None,
                               vmem_budget_mb: Optional[float] = None, *,
                               reset: bool = False) -> None:
    """Override context_ell dispatch: variant in {'auto', 'fused', 'loop'}.

    'fused' forces the one-pass multi-branch lookup kernel; 'loop' forces
    the per-branch SpMM fallback (the pre-fusion path, kept for codebooks
    whose lookup tables exceed the VMEM budget and for benchmarking).
    ``reset=True`` clears all programmatic overrides first.
    """
    if reset:
        _context_overrides.clear()
    if variant is not None:
        if variant not in ("auto", "fused", "loop"):
            raise ValueError(f"unknown context variant: {variant!r}")
        _context_overrides["variant"] = variant
    if vmem_budget_mb is not None:
        _context_overrides["vmem_budget_mb"] = float(vmem_budget_mb)


def context_ell_variant(n_branches: int, k: int, f_blk: int, deg: int, *,
                        bl: int = LANES, f_out: Optional[int] = None,
                        scaled: bool = False, cw_dtype=jnp.float32) -> str:
    """'fused' or 'loop' for a term of ``n_branches`` ``[k, f_blk]``
    codeword tables in ``cw_dtype`` read through ``deg`` slots per row.

    The rule charges what the fused kernel holds in VMEM
    (``context_ell.vmem_bytes``: the codebook's tables, tile-padded, the
    double-buffered id, value and output blocks of ``bl`` rows, the
    accumulator, the ``f_out``-wide ``w_t`` epilogue's matrix, and the
    f32 copy of a codebook stored narrower), not the ``[n_branches, n]``
    assignment table, which XLA gathers before the kernel.  A tuned
    entry (``autotune.tuned_context``) is keyed on the same shape.
    """
    forced = _context_overrides.get(
        "variant", hostenv.env_knob("REPRO_CONTEXT_VARIANT", "auto"))
    if forced not in ("auto", "fused", "loop"):
        raise ValueError(
            f"REPRO_CONTEXT_VARIANT={forced!r}: want auto, fused or loop")
    if forced in ("fused", "loop"):
        return str(forced)
    if not _budget_forced(_context_overrides, "REPRO_CONTEXT_VMEM_BUDGET_MB"):
        tuned = autotune.tuned_context(n_branches, k, f_blk, deg, cw_dtype)
        if tuned is not None:
            return str(tuned["variant"])
    budget_mb = _vmem_budget_mb(_context_overrides,
                                "REPRO_CONTEXT_VMEM_BUDGET_MB")
    held = vmem_bytes(n_branches, k, f_blk, deg, bl, f_out=f_out,
                      scaled=scaled,
                      cw_itemsize=jnp.dtype(cw_dtype).itemsize)
    return "loop" if held > budget_mb * 2 ** 20 else "fused"


def _context_ell_loop(out_ids, out_vals, assignment, codewords, w_t,
                      cw_scale=None):
    """Per-branch fallback: assignment gather + one SpMM per branch.

    Used when the fused kernel's lookup tables exceed the VMEM budget, or
    when forced -- each branch's gather source is its own [k, f_blk]
    codeword table, and the per-branch SpMM picks its resident or HBM
    variant by that table's size.  int8/fp8 codewords ride into each
    branch's SpMM with their [1, f_blk] scale row (per-branch dequant
    before the concat == the fused kernel's flat epilogue).  Nibble-packed
    tables unpack here (outside the kernels), as the fused kernel's XLA
    gather reads them packed: packing buys storage, not a crossover.
    """
    if isinstance(assignment, PackedAssignment):
        assignment = assignment.unpack()
    branch_ids = assignment.astype(jnp.int32)[:, out_ids]  # [nb, b, D]
    per_branch = [
        spmm_ell(branch_ids[i], out_vals, codewords[i],
                 x_scale=None if cw_scale is None else cw_scale[i])
        for i in range(codewords.shape[0])]
    out = jnp.concatenate(per_branch, axis=-1)
    if w_t is not None:
        out = out.astype(jnp.float32) @ w_t.astype(jnp.float32)
    return out


# The CPU execution path is the oracle jitted as ONE fused XLA computation
# (the dispatch-level analogue of the single kernel launch: the pre-fusion
# code issued one gather + SpMM + concat dispatch chain per branch).
_context_ell_ref = jax.jit(ref.context_ell)


def context_ell(out_ids: jax.Array, out_vals: jax.Array,
                assignment: jax.Array, codewords,
                w_t: Optional[jax.Array] = None) -> jax.Array:
    """Fused multi-branch VQ-context SpMM with size-based variant dispatch.

    One dispatch regardless of n_branches: the Eq. 6 context forward
    (feature codewords) and, with reverse-edge operands + gradient
    codewords (+ optional fused ``w_t`` epilogue), the streaming Eq. 7
    backward of ``inject_context_grad`` (DESIGN.md section 10).

    The quantized tiers are data-driven (no env read under jit): pass
    ``codewords`` as a ``QTensor`` ([nb, k, f_blk] int8 or float8_e4m3fn +
    [nb, 1, f_blk] f32 scales) and an ``assignment`` that is uint8
    (k <= 256) or a nibble-packed ``PackedAssignment`` (k <= 16) -- the
    tables stay in storage dtype in HBM; the fused kernel widens the
    tiny codebook to f32 lookup tables and applies one f32 dequant
    epilogue.
    """
    cw_scale = None
    if isinstance(codewords, QTensor):
        codewords, cw_scale = codewords.q, codewords.scale
    if _use_pallas():
        return _context_ell_kernel(out_ids, out_vals, assignment, codewords,
                                   w_t, cw_scale)
    return _context_ell_ref(out_ids, out_vals, assignment, codewords, w_t,
                            cw_scale)


@jax.custom_vjp
def _context_ell_kernel(out_ids, out_vals, assignment, codewords, w_t,
                        cw_scale):
    b, deg = out_ids.shape
    nb, k, f_blk = codewords.shape
    bb = 128
    tuned = autotune.tuned_context(nb, k, f_blk, deg, codewords.dtype)
    if tuned is not None:
        bb = int(tuned.get("bb", bb))
    variant = context_ell_variant(
        nb, k, f_blk, deg, bl=lane_tile(b, bb),
        f_out=None if w_t is None else w_t.shape[1],
        scaled=cw_scale is not None, cw_dtype=codewords.dtype)
    CONTEXT_TRACE_COUNT.bump(f"context.{variant}")
    if variant == "fused":
        return context_ell_pallas(out_ids, out_vals, assignment,
                                  codewords, cw_scale=cw_scale, w_t=w_t,
                                  bb=bb, interpret=interpret_mode())
    return _context_ell_loop(out_ids, out_vals, assignment, codewords,
                             w_t, cw_scale)


def _context_ell_fwd(out_ids, out_vals, assignment, codewords, w_t,
                     cw_scale):
    args = (out_ids, out_vals, assignment, codewords, w_t, cw_scale)
    return _context_ell_kernel(*args), args


def _context_ell_bwd(args, g):
    return tuple(_oracle_cotangents(ref.context_ell, args, g))


_context_ell_kernel.defvjp(_context_ell_fwd, _context_ell_bwd)
