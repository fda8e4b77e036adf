"""Block-size/dispatch autotuner (kernels/autotune.py): cache keying and
persistence, the opt-in gate (disabled -> None everywhere), tuner
round-trips producing valid configs that hit the cache on re-query, and
the ops.py dispatch precedence -- forced variant > explicitly configured
VMEM budget > autotuner measurement > size heuristic.
"""
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from numpy.testing import assert_allclose

from repro.kernels import autotune, ops, ref


@pytest.fixture
def tuner_cache(tmp_path, monkeypatch):
    """Route the cache to a temp file, enable tuning, reset state."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear()
    yield path
    autotune.clear()


# ---------------------------------------------------------------------------
# cache machinery
# ---------------------------------------------------------------------------

def test_shape_bucket():
    assert autotune.shape_bucket(0) == 0
    assert autotune.shape_bucket(1) == 1
    assert autotune.shape_bucket(100) == 128
    assert autotune.shape_bucket(128) == 128
    assert autotune.shape_bucket(129) == 256


def test_cache_key_buckets_and_backend():
    k = autotune.cache_key("spmm", (100, 16, 4), jnp.float32)
    assert k == f"spmm|128x16x4|float32|{jax.default_backend()}"
    # nearby shapes share a key; different dtypes do not
    assert autotune.cache_key("spmm", (65, 16, 4), jnp.float32) == k
    assert autotune.cache_key("spmm", (100, 16, 4), jnp.int8) != k


def test_record_lookup_roundtrip(tuner_cache):
    autotune.record("k1", {"variant": "fused", "bb": 64})
    assert autotune.lookup("k1") == {"variant": "fused", "bb": 64}
    assert autotune.lookup("nope") is None
    # persisted: a fresh in-memory cache reloads from the file
    autotune.clear(memory_only=True)
    assert autotune.lookup("k1") == {"variant": "fused", "bb": 64}
    on_disk = json.loads(tuner_cache.read_text())
    assert on_disk["k1"]["bb"] == 64


def test_corrupt_cache_file_is_ignored(tuner_cache):
    tuner_cache.write_text("{not json")
    autotune.clear(memory_only=True)
    assert autotune.lookup("anything") is None
    autotune.record("k", {"bb": 128})     # recovers by rewriting
    autotune.clear(memory_only=True)
    assert autotune.lookup("k") == {"bb": 128}


# ---------------------------------------------------------------------------
# opt-in gate
# ---------------------------------------------------------------------------

def test_disabled_returns_none(monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    assert not autotune.enabled()
    assert autotune.tuned_spmm(1000, 16) is None
    assert autotune.tuned_context(4, 64, 8, 16) is None
    assert autotune.tuned_vq_update(256, 64, 8) is None


# ---------------------------------------------------------------------------
# tuner round-trips (measure once, then cache hits)
# ---------------------------------------------------------------------------

def test_tuned_spmm_measures_and_caches(tuner_cache):
    cfg = autotune.tuned_spmm(500, 16)
    assert cfg["variant"] in ("resident", "hbm")
    assert cfg["bb"] in (64, 128, 256)
    # second query must be a pure cache hit: break measurement to prove it
    def boom(*a, **k):
        raise AssertionError("re-measured a cached key")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(autotune, "_time", boom)
        assert autotune.tuned_spmm(500, 16) == cfg
        # same bucket (next pow2 of 500 == of 512) -> still a hit
        assert autotune.tuned_spmm(512, 16) == cfg


def test_tuned_context_and_vq_update(tuner_cache):
    ctx = autotune.tuned_context(4, 64, 8, 16)
    assert ctx["variant"] in ("fused", "loop")
    vq = autotune.tuned_vq_update(256, 64, 8)
    assert vq["bb"] in (128, 256) and vq["kb"] in (256, 512)
    # f32 and int8 codebooks tune independently
    ctx8 = autotune.tuned_context(4, 64, 8, 16, dtype=jnp.int8)
    assert ctx8["variant"] in ("fused", "loop")
    keys = set(json.loads(tuner_cache.read_text()))
    assert len([k for k in keys if k.startswith("context|")]) == 2


# ---------------------------------------------------------------------------
# dispatch precedence in ops.py
# ---------------------------------------------------------------------------

def test_dispatch_prefers_tuned_variant(tuner_cache, monkeypatch):
    # seed the cache with a deliberately contrarian winner: the heuristic
    # at the default budget would say "resident" for this tiny shape
    key = autotune.cache_key("spmm", (512, 16, 4), jnp.float32)
    autotune.record(key, {"variant": "hbm", "bb": 128})
    ops.configure_spmm_dispatch(reset=True)
    assert ops.spmm_ell_variant(512, 16) == "hbm"
    # ... but a forced variant out-ranks the tuner
    ops.configure_spmm_dispatch(variant="resident")
    try:
        assert ops.spmm_ell_variant(512, 16) == "resident"
    finally:
        ops.configure_spmm_dispatch(reset=True)
    # ... and an explicitly configured budget also silences the tuner
    ops.configure_spmm_dispatch(vmem_budget_mb=64.0)
    try:
        assert ops.spmm_ell_variant(512, 16) == "resident"
    finally:
        ops.configure_spmm_dispatch(reset=True)


def test_context_dispatch_budget_silences_tuner(tuner_cache):
    # keyed on the shape the rule charges: nb, k, f_blk, D
    shape = (4, 64, 8, 16)
    key = autotune.cache_key("context", shape, jnp.float32)
    autotune.record(key, {"variant": "loop", "bb": 64})
    ops.configure_context_dispatch(reset=True)
    try:
        # 0.16 MiB held, so the heuristic says fused
        assert ops.context_ell_variant(*shape) == "loop"
        ops.configure_context_dispatch(vmem_budget_mb=64.0)
        assert ops.context_ell_variant(*shape) == "fused"
    finally:
        ops.configure_context_dispatch(reset=True)


def test_env_budget_silences_tuner(tuner_cache, monkeypatch):
    key = autotune.cache_key("spmm", (512, 16, 4), jnp.float32)
    autotune.record(key, {"variant": "hbm", "bb": 128})
    monkeypatch.setenv("REPRO_SPMM_VMEM_BUDGET_MB", "64")
    ops.configure_spmm_dispatch(reset=True)
    assert ops.spmm_ell_variant(512, 16) == "resident"


def test_tuned_spmm_races_both_variants(tuner_cache):
    """The spmm tuner races both variants' row tiles under one cache entry
    and reloads the same winner from the file."""
    cfg = autotune.tuned_spmm(500, 16)
    assert (cfg["variant"], cfg["bb"]) in {
        ("resident", 128), ("resident", 256),
        ("hbm", 64), ("hbm", 128), ("hbm", 256)}
    autotune.clear(memory_only=True)
    assert autotune.tuned_spmm(500, 16) == cfg


def test_tuned_hbm_entry_flows_into_hbm_call(tuner_cache, monkeypatch):
    """A tuned HBM entry reaches the HBM kernel through ops.spmm_ell, and
    an entry carrying an unknown key still dispatches fine."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    keyr = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(keyr, 3)
    ids = jax.random.randint(k1, (40, 4), 0, 200).astype(jnp.int32)
    val = jax.random.normal(k2, (40, 4), jnp.float32)
    x = jax.random.normal(k3, (200, 8), jnp.float32)
    want = np.asarray(ref.spmm_ell(ids, val, x))
    key = autotune.cache_key("spmm", (200, 8, 4), jnp.float32)
    ops.configure_spmm_dispatch(reset=True)
    autotune.record(key, {"variant": "hbm", "bb": 64, "stripe": 256})
    assert_allclose(np.asarray(ops.spmm_ell(ids, val, x)), want,
                    rtol=1e-5, atol=1e-5)
    autotune.record(key, {"variant": "hbm", "bb": 64})
    autotune.clear(memory_only=True)                    # reload from file
    assert_allclose(np.asarray(ops.spmm_ell(ids, val, x)), want,
                    rtol=1e-5, atol=1e-5)


def test_tuned_bb_flows_into_kernel_call(tuner_cache, monkeypatch):
    """ops.spmm_ell consumes the tuned block size end-to-end (forced
    Pallas interpret path) and stays parity-correct."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    keyr = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(keyr, 3)
    ids = jax.random.randint(k1, (40, 4), 0, 200).astype(jnp.int32)
    val = jax.random.normal(k2, (40, 4), jnp.float32)
    x = jax.random.normal(k3, (200, 8), jnp.float32)
    key = autotune.cache_key("spmm", (200, 8, 4), jnp.float32)
    autotune.record(key, {"variant": "resident", "bb": 64})
    ops.configure_spmm_dispatch(reset=True)
    got = ops.spmm_ell(ids, val, x)
    assert_allclose(np.asarray(got), np.asarray(ref.spmm_ell(ids, val, x)),
                    rtol=1e-5, atol=1e-5)
