"""Entry-point registry for the jaxpr / VMEM analysis passes.

Builds ONE tiny setup (a 40-node SBM graph, a 2-layer GCN, an epoch plan)
and registers every hot jitted entry point of the serving stack against
it: the single-device / DP / row-sharded epoch executors, the sampler
baseline executor, the layer-locked inference sweep and the one-compile
serve step (the latter two across all five precision tiers).  Each
:class:`Entry` bundles

  * a thunk that traces the entry on ``ShapeDtypeStruct`` specs
    (``jax.make_jaxpr`` -- abstract, no FLOPs, no device buffers), and a
    thunk that AOT-lowers it (for the donation/aliasing check);
  * its contracts: exact ``pallas_call`` dispatch count under forced
    kernels, donation aliasing, scan-carry byte budget, the quantized
    storage dtypes that must reach the kernels, and the trace-counter key
    the entry must bump exactly once per trace.

Kernel-forcing note: on CPU the dispatchers route to the jnp oracles, so
every entry traces under ``REPRO_FORCE_PALLAS=1`` (set host-side around
the trace; ``repro.hostenv`` snapshots it).  The training entries
differentiate through the kernel path: the SpMM and context dispatches
carry custom VJPs whose backward is the oracle's VJP (kernels/ops.py).

Traced jaxprs are cached per entry so the jaxpr pass and the VMEM pass
share one trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import hostenv
from repro.distributed.quantization import tree_bytes

# Tiny-but-ragged: S = ceil(40/16) = 3 batches with a wrap-padded tail, so
# every trace exercises the slot-mask path.
_N, _B = 40, 16
_F, _CLASSES = 16, 4


def _sds(tree):
    """Pytree of concrete arrays -> same tree of ShapeDtypeStructs."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
        tree)


@contextlib.contextmanager
def forced_pallas():
    """Host-side REPRO_FORCE_PALLAS=1 around a trace.

    Mutating ``os.environ`` here is legitimate: this runs host-side (no
    trace active when the snapshot refreshes), exactly the configuration
    path the env-read-once contract sanctions."""
    prev = os.environ.get("REPRO_FORCE_PALLAS")
    os.environ["REPRO_FORCE_PALLAS"] = "1"
    hostenv.reset_env_snapshot()
    try:
        yield
    finally:
        if prev is None:
            del os.environ["REPRO_FORCE_PALLAS"]
        else:
            os.environ["REPRO_FORCE_PALLAS"] = prev
        hostenv.reset_env_snapshot()


@dataclasses.dataclass
class Entry:
    """One registered entry point plus its static contracts."""
    name: str
    trace: Callable[[], Any]            # () -> ClosedJaxpr
    lower: Optional[Callable[[], Any]]  # () -> jax.stages.Lowered
    force_pallas: bool = False
    # exact pallas_call dispatch count (None = count not pinned)
    pallas_count: Optional[int] = None
    # minimum "tf.aliasing_output" occurrences in the lowered text
    donated_min: int = 0
    # max bytes of any scan carry in the jaxpr (None = entry has no scan)
    carry_budget: Optional[int] = None
    # trace_count key this entry bumps exactly once per trace
    counter: Optional[str] = None
    # storage dtypes of quantized input leaves that must reach a
    # pallas_call without an intervening host-level float upcast
    quantized_dtypes: tuple = ()

    _jaxpr: Any = None

    def jaxpr(self):
        """The entry's ClosedJaxpr, traced once and cached."""
        if self._jaxpr is None:
            if self.force_pallas:
                with forced_pallas():
                    self._jaxpr = self.trace()
            else:
                self._jaxpr = self.trace()
        return self._jaxpr


def fresh_jaxpr(jit_fn, call, *args):
    """``jax.make_jaxpr(call)(*args)`` with ``jit_fn``'s trace cache
    dropped first.  The pjit cache is keyed on avals + statics ONLY --
    never on dispatch overrides or env knobs -- so an analysis trace that
    hit a stale cache entry would (a) skip the Python body (no trace-
    counter bump) and (b) reflect whatever dispatch config was active at
    the original trace.  The checker wants the CURRENT tree's behavior,
    so it always retraces."""
    if hasattr(jit_fn, "clear_cache"):
        jit_fn.clear_cache()
    return jax.make_jaxpr(call)(*args)


@functools.lru_cache(maxsize=None)
def tiny_setup(f_prod: int = 4):
    """The shared tiny problem instance, built once per branch width."""
    from repro.core.codebook import CodebookConfig
    from repro.graph.batching import (build_epoch_plan, epoch_slices,
                                      full_operands)
    from repro.graph.datasets import _node_classification
    from repro.models.gnn import GNNConfig, init_gnn, init_vq_states
    from repro.train.optimizer import rmsprop

    g = _node_classification("analysis-tiny", _N, _F, _CLASSES, 3.0,
                             0.6, 0.5, 0.5, 8, 0)
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=8,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=8, f_prod=f_prod))
    tm = np.zeros(g.n, np.float32)
    tm[g.train_idx] = 1.0
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    vq = init_vq_states(jax.random.PRNGKey(1), cfg, g.n)
    opt = rmsprop(3e-3)
    bids, smask = epoch_slices(np.arange(g.n), _B)
    return dict(
        g=g, cfg=cfg, opt=opt, params=params, vq=vq,
        ost=opt.init(params), plan=build_epoch_plan(g),
        degrees=full_operands(g).degrees,
        x=jnp.asarray(g.features), labels=jnp.asarray(g.labels),
        tm=jnp.asarray(tm),
        perm=jnp.asarray(bids.astype(np.int32)),
        smask=jnp.asarray(smask))


def _quantized_leaf_dtypes(tree) -> tuple:
    """Storage dtypes of the sub-f32 leaves of a quantized state tree."""
    storage = {jnp.dtype(jnp.int8), jnp.dtype(jnp.float8_e4m3fn)}
    found = {jnp.result_type(a) for a in jax.tree_util.tree_leaves(tree)
             if jnp.result_type(a) in storage}
    return tuple(sorted(found, key=str))


def _epoch_args(s):
    return (_sds(s["params"]), _sds(s["vq"]), _sds(s["ost"]),
            _sds(s["plan"]), _sds(s["perm"]), _sds(s["smask"]),
            _sds(s["x"]), _sds(s["labels"]), _sds(s["tm"]),
            _sds(s["degrees"]))


def _epoch_carry_budget(s) -> int:
    # the scan carries exactly the donated (params, vq, opt) state; pad
    # with a small absolute slack for scalar step counters and the like
    return tree_bytes((s["params"], s["vq"], s["ost"])) + 4096


# Dispatch counts under forced kernels, pinned per entry (and per tier
# where the operand dtypes change the kernel choice).  The registry pins
# them exactly: a new dispatch in the hot path must update this table in
# the same PR, which is precisely the review surface the checker exists
# to create.  Branch-count (nb) invariance is checked separately by
# tracing two branch widths -- the counts here must hold for BOTH.
PALLAS_COUNTS = {
    # per layer: ONE fused context dispatch (regardless of nb) + ONE
    # intra-batch SpMM; the non-inductive inference path runs no
    # assignment-refresh kernel.  Serve = the same two per layer x 2.
    "vq_infer_layer": 2,
    "vq_serve_batch": 4,
    # per layer of a training step: the intra SpMM and the context forward,
    # the Eq. 7 context backward (the injection's custom VJP runs the
    # kernel), and the fused assign+stats update; the SpMM backward is
    # the oracle's XLA VJP.  2 layers.
    "train_step": 8,
    # per layer: the full-(sub)graph SpMM forward; its backward is XLA
    "sampler_step": 2,
}


def _infer_entry(tier: Optional[str], f_prod: int = 4) -> Entry:
    from repro.models.gnn import quantize_vq_states, vq_infer_layer
    s = tiny_setup(f_prod)
    vq = (s["vq"] if tier in (None, "fp32")
          else quantize_vq_states(s["vq"], s["cfg"], precision=tier))
    st = vq[0]
    acts = jnp.zeros((s["g"].n, s["cfg"].f_in), jnp.float32)
    args = (_sds(s["params"][0]), _sds(st), _sds(s["plan"]),
            _sds(s["perm"]), _sds(s["smask"]), _sds(acts),
            _sds(s["degrees"]))
    cfg = s["cfg"]
    fn = functools.partial(vq_infer_layer, cfg=cfg, layer=0,
                           inductive=False)
    label = "fp32" if tier in (None, "fp32") else tier
    return Entry(
        name=f"vq_infer_layer[{label}]" + (
            f"@f_prod={f_prod}" if f_prod != 4 else ""),
        trace=lambda: fresh_jaxpr(vq_infer_layer, fn, *args),
        lower=None,
        force_pallas=True,
        pallas_count=PALLAS_COUNTS["vq_infer_layer"],
        carry_budget=(s["g"].n + 1) * cfg.f_in * 4 + 4096,
        counter="layer",
        quantized_dtypes=_quantized_leaf_dtypes(vq[0]))


def _serve_entry(tier: Optional[str], f_prod: int = 4) -> Entry:
    from repro.models.gnn import quantize_vq_states, vq_serve_batch
    s = tiny_setup(f_prod)
    vq = (s["vq"] if tier in (None, "fp32")
          else quantize_vq_states(s["vq"], s["cfg"], precision=tier))
    bids = jnp.zeros((_B,), jnp.int32)
    args = (_sds(s["params"]), _sds(vq), _sds(s["plan"]), _sds(bids),
            _sds(s["x"]), _sds(s["degrees"]))
    cfg = s["cfg"]
    fn = functools.partial(vq_serve_batch, cfg=cfg)
    label = "fp32" if tier in (None, "fp32") else tier
    return Entry(
        name=f"vq_serve_batch[{label}]" + (
            f"@f_prod={f_prod}" if f_prod != 4 else ""),
        trace=lambda: fresh_jaxpr(vq_serve_batch, fn, *args),
        lower=None,
        force_pallas=True,
        pallas_count=PALLAS_COUNTS["vq_serve_batch"],
        counter="serve",
        quantized_dtypes=_quantized_leaf_dtypes(vq))


def _train_entries() -> list[Entry]:
    from repro.distributed.data_parallel import _dp_epoch_jit, \
        _sharded_epoch_jit
    from repro.distributed.sharding import graph_dp_mesh
    from repro.graph.batching import SamplerEpochPlan
    from repro.models.gnn import sampler_train_epoch, vq_train_epoch

    s = tiny_setup()
    cfg, opt = s["cfg"], s["opt"]
    eargs = _epoch_args(s)
    budget = _epoch_carry_budget(s)

    entries = [Entry(
        name="vq_train_epoch",
        trace=lambda: fresh_jaxpr(
            vq_train_epoch,
            lambda *a: vq_train_epoch(*a, cfg, opt), *eargs),
        lower=lambda: vq_train_epoch.lower(*eargs, cfg, opt),
        force_pallas=True, pallas_count=PALLAS_COUNTS["train_step"],
        donated_min=1, carry_budget=budget)]

    # sampler baseline: S batches of P=16 padded subgraph rows, deg cap 8
    sp = SamplerEpochPlan(
        node_ids=jnp.zeros((3, _B), jnp.int32),
        nbr_ids=jnp.zeros((3, _B, 8), jnp.int32),
        nbr_mask=jnp.zeros((3, _B, 8), jnp.float32),
        degrees=jnp.zeros((3, _B), jnp.float32),
        loss_mask=jnp.zeros((3, _B), jnp.float32))
    sargs = (_sds(s["params"]), _sds(s["ost"]), _sds(sp), _sds(s["x"]),
             _sds(s["labels"]))
    entries.append(Entry(
        name="sampler_train_epoch",
        trace=lambda: fresh_jaxpr(
            sampler_train_epoch,
            lambda *a: sampler_train_epoch(*a, cfg, opt), *sargs),
        lower=lambda: sampler_train_epoch.lower(*sargs, cfg, opt),
        force_pallas=True, pallas_count=PALLAS_COUNTS["sampler_step"],
        donated_min=1,
        carry_budget=tree_bytes((s["params"], s["ost"])) + 4096))

    mesh = graph_dp_mesh()
    if int(mesh.shape["data"]) == 1:
        # the DP / row-sharded executors divide the batch axis over the
        # mesh; at ndev=1 the shard is the whole table, so the replicated
        # tiny operands trace both bodies unchanged
        entries.append(Entry(
            name="dp_epoch",
            trace=lambda: fresh_jaxpr(
                _dp_epoch_jit,
                lambda *a: _dp_epoch_jit(*a, mesh=mesh, cfg=cfg,
                                         opt=opt), *eargs),
            lower=lambda: _dp_epoch_jit.lower(*eargs, mesh=mesh, cfg=cfg,
                                              opt=opt),
            force_pallas=True, pallas_count=PALLAS_COUNTS["train_step"],
            donated_min=1, carry_budget=budget))
        entries.append(Entry(
            name="sharded_epoch",
            trace=lambda: fresh_jaxpr(
                _sharded_epoch_jit,
                lambda *a: _sharded_epoch_jit(*a, mesh=mesh, cfg=cfg,
                                              opt=opt,
                                              compress=False), *eargs),
            lower=lambda: _sharded_epoch_jit.lower(
                *eargs, mesh=mesh, cfg=cfg, opt=opt, compress=False),
            force_pallas=True, pallas_count=PALLAS_COUNTS["train_step"],
            donated_min=1, carry_budget=budget))
    return entries


@functools.lru_cache(maxsize=None)
def entries() -> tuple:
    """All registered entries (tuple: cached, iteration-stable)."""
    from repro.kernels import ops as kops
    out = _train_entries()
    for tier in kops.PRECISIONS:
        out.append(_infer_entry(tier))
        out.append(_serve_entry(tier))
    # branch-count invariance probes: same dispatch-count contract must
    # hold at a different product-VQ width (f_prod=2 -> more branches)
    out.append(_infer_entry("fp32", f_prod=2))
    out.append(_serve_entry("int8+a4", f_prod=2))
    return tuple(out)
