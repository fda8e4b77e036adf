"""Trace-time compile counters (the compile-count telemetry).

A :class:`TraceCounter` is a plain dict of named counters bumped at TRACE
time inside jitted bodies: retracing is the expensive event the executors
promise to bound (one inference pass costs n_layers layer traces, a serve
step one trace -- independent of the batch count S and of n % b), so the
counter deltas ARE the compile-count contract.  dict subclassing keeps the
historical ``INFER_TRACE_COUNT["layer"]`` indexing working everywhere.

Shared by the inference-executor entry points (``models/gnn.py``), their
tests, and the ``repro.analysis`` jaxpr pass (which asserts the deltas
while tracing the registered entry points on tiny specs).  The kernel
dispatch (``kernels/ops.py``) counts the context terms it traces per
variant the same way.
"""
from __future__ import annotations


class TraceCounter(dict):
    """Named monotonic counters with snapshot/delta helpers."""

    def bump(self, key: str) -> None:
        """Increment ``key`` (call at trace time inside the jitted body)."""
        self[key] = self.get(key, 0) + 1

    def snapshot(self) -> dict:
        return dict(self)

    def delta(self, before: dict) -> dict:
        """Per-key increments since ``before`` (a :meth:`snapshot`)."""
        keys = set(self) | set(before)
        return {k: self.get(k, 0) - before.get(k, 0) for k in keys}


# The inference executors' counters: "layer" bumps once per trace of the
# per-layer scan body (replicated + row-sharded), "serve" once per trace
# of the one-compile serving step.
INFER_TRACE_COUNT = TraceCounter(layer=0, serve=0)

# The context dispatch's counters: "context.fused" or "context.loop" bumps
# once per context term traced on the kernel path (kernels/ops.py).
CONTEXT_TRACE_COUNT = TraceCounter({"context.fused": 0, "context.loop": 0})
