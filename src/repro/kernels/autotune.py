"""Measure-and-cache autotuner for kernel block sizes and dispatch variants.

The dispatch layer (``ops.py``) picks block sizes (``bb``, ``kb``) and the
resident/HBM + fused/loop variants from fixed defaults and a VMEM-budget
heuristic behind ``REPRO_*_VMEM_BUDGET_MB`` env vars.  Those numbers encode
one machine's tradeoffs; this module replaces them with measurements when
the user opts in (``REPRO_AUTOTUNE=1``):

  * each (kind, shape bucket, dtype, backend) key is timed ONCE -- candidate
    configs race on a clamped synthetic problem (rows <= 512, few reps) so a
    cold cache costs milliseconds, not a benchmark run;
  * winners persist to a JSON cache (``REPRO_AUTOTUNE_CACHE``, default
    ``~/.cache/repro/autotune.json``) keyed on next-power-of-two shape
    buckets so one measurement covers a whole size regime and jit caches
    stay warm across nearby shapes;
  * the env vars stay authoritative: ops.py only consults the autotuner
    when no forced variant and no explicit budget override is in effect
    (precedence: programmatic override > env var > autotuner > heuristic).

Measurements call the kernel entry points directly (``spmm_ell_pallas``,
``context_ell_pallas``, ...) rather than going through ops.py dispatch --
the dispatcher consults this module, so routing timings back through it
would recurse.  On CPU the kernels run in interpret mode, making the
timings a proxy for relative launch/gather overheads rather than real MXU
throughput; production TPU deployments get true measurements for free from
the same code path.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import hostenv

_ROW_CLAMP = 512      # measured problems never exceed this many batch rows
_SRC_CLAMP = 8192     # ... nor this many gather-source rows
_REPS = 2             # best-of reps after one warmup (jit compile) call

# in-memory cache: key -> config dict; None until the file is first read
_cache: Optional[dict[str, Any]] = None


def enabled() -> bool:
    """Autotuning is opt-in: measurements only run under REPRO_AUTOTUNE=1.

    Read through the hostenv snapshot -- the tuners are consulted by the
    ops.py dispatchers inside jit traces (env-read-once contract)."""
    return hostenv.env_knob("REPRO_AUTOTUNE", "0") == "1"


def cache_path() -> str:
    return hostenv.env_knob(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "autotune.json"))


def shape_bucket(v: int) -> int:
    """Next power of two (0 -> 0): the shape-key granularity."""
    v = int(v)
    return 0 if v <= 0 else 1 << (v - 1).bit_length()


def cache_key(kind: str, shape: tuple[int, ...], dtype) -> str:
    buckets = "x".join(str(shape_bucket(s)) for s in shape)
    return f"{kind}|{buckets}|{jnp.dtype(dtype).name}|{jax.default_backend()}"


def _load() -> dict[str, Any]:
    global _cache
    if _cache is None:
        try:
            with open(cache_path()) as fh:
                _cache = dict(json.load(fh))
        except (OSError, ValueError):
            _cache = {}
    return _cache


def lookup(key: str) -> Optional[dict[str, Any]]:
    hit = _load().get(key)
    return dict(hit) if isinstance(hit, dict) else None


def record(key: str, cfg: dict[str, Any]) -> None:
    cache = _load()
    cache[key] = dict(cfg)
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            json.dump(cache, fh, indent=1, sort_keys=True)
    except OSError:
        pass  # cache stays in-memory for this process


def clear(*, memory_only: bool = False) -> None:
    """Drop the in-memory cache (tests); optionally keep the file."""
    global _cache
    _cache = None
    if not memory_only:
        try:
            os.remove(cache_path())
        except OSError:
            pass


def _time(fn, *args) -> float:
    out = fn(*args)                       # warmup: compile + first run
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# per-kernel tuners (ops.py consumers)
# ---------------------------------------------------------------------------

def tuned_spmm(n_src: int, f: int, itemsize: int = 4, dtype=None
               ) -> Optional[dict[str, Any]]:
    """{'variant': 'resident'|'hbm', 'bb': int} for a [n_src, f] source
    matrix of ``itemsize``-byte elements, or None when autotuning is off.

    ``dtype`` is the storage dtype of the source rows and keys the cache
    entry -- int8 and float8_e4m3fn share itemsize 1 but are distinct
    operand regimes, so they must not share a winner (ISSUE 9).  When
    omitted it falls back to the itemsize-derived legacy key."""
    if not enabled():
        return None
    if dtype is None:
        dtype = jnp.int8 if itemsize == 1 else jnp.float32
    key = cache_key("spmm", (n_src, f, itemsize), dtype)
    hit = lookup(key)
    if hit is not None:
        return hit

    from repro.kernels.spmm_ell import spmm_ell_pallas
    from repro.kernels.spmm_ell_hbm import spmm_ell_hbm_pallas
    from repro.kernels import ops
    b, deg = min(_ROW_CLAMP, 256), 16
    ns = min(int(n_src), _SRC_CLAMP)
    fm = min(int(f), 128)
    key_rng = jax.random.PRNGKey(0)
    ki, kv, kx = jax.random.split(key_rng, 3)
    idx = jax.random.randint(ki, (b, deg), 0, ns, jnp.int32)
    val = jax.random.uniform(kv, (b, deg), jnp.float32)
    x = jax.random.normal(kx, (ns, fm), jnp.float32)
    interp = ops.interpret_mode()

    timings: dict[tuple[str, int], float] = {}
    for bb in (128, 256):     # resident rows ride the lanes: multiples of 128
        timings[("resident", bb)] = _time(
            lambda i, v, s, _bb=bb: spmm_ell_pallas(
                i, v, s, bb=_bb, interpret=interp), idx, val, x)
    for bb in (64, 128, 256):
        timings[("hbm", bb)] = _time(
            lambda i, v, s, _bb=bb: spmm_ell_hbm_pallas(
                i, v, s, bb=_bb, interpret=interp), idx, val, x)
    (variant, bb), _ = min(timings.items(), key=lambda kv_: kv_[1])
    cfg = {"variant": variant, "bb": int(bb)}
    record(key, cfg)
    return cfg


def tuned_context(n_branches: int, k: int, f_blk: int, deg: int,
                  dtype=jnp.float32) -> Optional[dict[str, Any]]:
    """{'variant': 'fused'|'loop', 'bb': int} for a context term of
    ``n_branches`` ``[k, f_blk]`` codeword tables read through ``deg``
    slots per row -- the shape the dispatch rule charges -- or None when
    autotuning is off.

    ``dtype`` keys the cache entry by the codebook's storage dtype (f32,
    int8 or float8_e4m3fn); a narrower codebook races with its per-channel
    scales, as dispatch would run it.  The assignment table is not part of
    the key: XLA gathers it ahead of either variant."""
    if not enabled():
        return None
    dtype = jnp.dtype(dtype)
    key = cache_key("context", (n_branches, k, f_blk, deg), dtype)
    hit = lookup(key)
    if hit is not None:
        return hit

    from repro.distributed.quantization import quantize_codewords
    from repro.kernels.context_ell import context_ell_pallas
    from repro.kernels import ops
    from repro.kernels.spmm_ell import spmm_ell_pallas
    b, n = min(_ROW_CLAMP, 256), 1000
    nb, k, f_blk, deg = int(n_branches), int(k), int(f_blk), int(deg)
    rng = jax.random.PRNGKey(0)
    ki, kv, ka, kc = jax.random.split(rng, 4)
    ids = jax.random.randint(ki, (b, deg), 0, n, jnp.int32)
    val = jax.random.uniform(kv, (b, deg), jnp.float32)
    assign = jax.random.randint(ka, (nb, n), 0, k, jnp.int32)
    cw = jax.random.normal(kc, (nb, k, f_blk), jnp.float32)
    scale = None
    if dtype != jnp.dtype(jnp.float32):
        qt = quantize_codewords(cw, dtype=dtype)
        cw, scale = qt.q, qt.scale
    interp = ops.interpret_mode()

    def loop(i, v, a, c):
        # the per-branch fallback, built on the kernel directly (module doc)
        bi = a[:, i]
        return jnp.concatenate(
            [spmm_ell_pallas(bi[j], v, c[j], interpret=interp,
                             x_scale=None if scale is None else scale[j])
             for j in range(nb)], axis=-1)

    timings: dict[tuple[str, int], float] = {}
    for bb in (64, 128, 256):
        timings[("fused", bb)] = _time(
            lambda i, v, a, c, _bb=bb: context_ell_pallas(
                i, v, a, c, cw_scale=scale, bb=_bb, interpret=interp),
            ids, val, assign, cw)
    timings[("loop", 128)] = _time(loop, ids, val, assign, cw)
    (variant, bb), _ = min(timings.items(), key=lambda kv_: kv_[1])
    cfg = {"variant": variant, "bb": int(bb)}
    record(key, cfg)
    return cfg


def tuned_vq_update(b: int, k: int, f: int) -> Optional[dict[str, Any]]:
    """{'bb': int, 'kb': int} block sizes for the fused assign+stats kernel,
    or None when autotuning is off."""
    if not enabled():
        return None
    key = cache_key("vq_update", (b, k, f), jnp.float32)
    hit = lookup(key)
    if hit is not None:
        return hit

    from repro.kernels.vq_update import vq_assign_update_pallas
    from repro.kernels import ops
    bm = min(int(b), _ROW_CLAMP)
    km, fm = min(int(k), 512), min(int(f), 128)
    rng = jax.random.PRNGKey(0)
    kx, kc = jax.random.split(rng)
    x = jax.random.normal(kx, (bm, fm), jnp.float32)
    cw = jax.random.normal(kc, (km, fm), jnp.float32)
    interp = ops.interpret_mode()

    timings: dict[tuple[int, int], float] = {}
    for bb in (128, 256):
        for kb in (256, 512):
            timings[(bb, kb)] = _time(
                lambda xx, cc, _bb=bb, _kb=kb: vq_assign_update_pallas(
                    xx, cc, bb=_bb, kb=_kb, interpret=interp), x, cw)
    (bb, kb), _ = min(timings.items(), key=lambda kv_: kv_[1])
    cfg = {"bb": int(bb), "kb": int(kb)}
    record(key, cfg)
    return cfg
