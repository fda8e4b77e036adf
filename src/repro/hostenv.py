"""Host-snapshot environment knobs (the env-read-once contract).

Every ``REPRO_*`` knob that steers code reachable from a jit trace MUST be
read through :func:`env_knob` instead of ``os.environ`` directly.  The
contract (DESIGN.md section 16): dispatch decisions made while tracing a
jitted body must not depend on the live environment, because jax's
executable cache is keyed on (function, shapes, statics) only -- an env
var mutated between two calls of the same shape would silently NOT take
effect on the cached executable but WOULD take effect on the next new
shape, leaving one epoch running a mix of regimes.

:func:`env_knob` therefore reads ``os.environ`` only while no trace is
active (``jax._src.core.trace_state_clean()``): host-side calls -- tests
monkeypatching ``REPRO_SPMM_VARIANT``, the trainer choosing an executor,
an eager kernel call -- always see the live environment, while calls made
during jit tracing reuse the most recent host-side snapshot.  The one
deliberate exception is the cold-start bootstrap: a knob whose very first
read in the process happens under a trace is snapshotted there (there is
no earlier host-side value to prefer, and refusing would break
``python -c "jax.jit(train)(...)"`` one-liners).

``repro.hostenv`` is the single module in the package allowed to touch
``os.environ`` from jit-reachable code; the ``repro.analysis`` REPRO001
lint rule enforces exactly that.
"""
from __future__ import annotations

import os
from typing import Optional

from jax._src.core import trace_state_clean as _trace_state_clean

# name -> raw value (None records "unset"); refreshed on every host-side
# read, frozen while a trace is active
_snapshot: dict[str, Optional[str]] = {}


def _refresh(name: str) -> None:
    if name not in _snapshot or _trace_state_clean():
        _snapshot[name] = os.environ.get(name)


def env_knob(name: str, default=None):
    """``os.environ.get(name, default)`` with trace-frozen semantics.

    Host-side: a live read (and the snapshot refreshes).  Under a jax
    trace: the last host-side snapshot, so the traced computation is a
    pure function of its operands plus the host-side configuration state.
    """
    _refresh(name)
    val = _snapshot[name]
    return default if val is None else val


def env_knob_set(name: str) -> bool:
    """``name in os.environ`` under the same trace-frozen semantics."""
    _refresh(name)
    return _snapshot[name] is not None


def reset_env_snapshot() -> None:
    """Drop every snapshotted knob (tests; forces fresh host-side reads)."""
    _snapshot.clear()


# Fixed in-checkout home of JAX's persistent compilation cache when the
# environment does not place it (the path is part of the cache key, so it
# never comes from a temporary name, a pid or the time).  Listed in
# .gitignore.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing else is configured; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Entry points (``chip_smoke.py``, the
    ``main()`` of ``repro.launch.serve_gnn`` and of the examples) call
    this; importing a module never does.
    """
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
