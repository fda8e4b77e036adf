"""HBM-resident SpMM-ELL variant: parity vs the jnp oracle and the
VMEM-resident kernel (interpret mode), the row-DMA gather's edge cases, and
the resident/HBM dispatch heuristic in kernels/ops.py.

The size sweep deliberately includes ``n_src * f`` shapes above the resident
VMEM envelope used by the dispatch tests (the envelope is configurable, and
the 20000x64 case is ~5 MiB of f32 -- past the 4 MiB budget the dispatch
test pins).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from numpy.testing import assert_allclose

from repro.kernels import ops, ref
from repro.kernels.spmm_ell import spmm_ell_pallas
from repro.kernels.spmm_ell_hbm import spmm_ell_hbm_pallas


def _case(b, deg, n, f, dtype=jnp.float32, seed=None):
    key = jax.random.PRNGKey(seed if seed is not None else b * 31 + deg)
    k1, k2, k3 = jax.random.split(key, 3)
    idx = jax.random.randint(k1, (b, deg), 0, n).astype(jnp.int32)
    val = jax.random.normal(k2, (b, deg), jnp.float32)
    x = jax.random.normal(k3, (n, f), dtype)
    return idx, val, x


# ---------------------------------------------------------------------------
# parity: HBM variant vs oracle vs resident kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,deg,n,f", [
    (1, 1, 1, 1),            # degenerate minimum
    (8, 4, 16, 8),           # everything below one tile
    (33, 7, 50, 12),         # b a non-multiple of bb, f of 128
    (128, 32, 300, 64),      # multi-tile, wide rows
    (200, 9, 3000, 96),      # a large source
    (257, 5, 20000, 64),     # above the 4 MiB resident envelope (5 MiB f32)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_ell_hbm_sweep(b, deg, n, f, dtype):
    idx, val, x = _case(b, deg, n, f, dtype)
    got = spmm_ell_hbm_pallas(idx, val, x, interpret=True)
    want = ref.spmm_ell(idx, val, x)
    resident = spmm_ell_pallas(idx, val, x, interpret=True)
    tol = dict(rtol=2e-2, atol=1e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)
    assert_allclose(np.asarray(got), np.asarray(want), **tol)
    assert_allclose(np.asarray(got), np.asarray(resident), **tol)


@pytest.mark.parametrize("bb", [8, 16, 128, 40])
def test_spmm_ell_hbm_tile_sizes(bb):
    """Non-multiple tile sizes: b % bb != 0."""
    idx, val, x = _case(53, 6, 210, 16)
    got = spmm_ell_hbm_pallas(idx, val, x, bb=bb, interpret=True)
    want = ref.spmm_ell(idx, val, x)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_spmm_ell_hbm_padding_zero_vals():
    """Padding slots carry val == 0; their index may point anywhere valid --
    their row is copied like any other but must not contribute."""
    idx = jnp.array([[5, 0], [2, 1]], jnp.int32)
    val = jnp.array([[1.0, 0.0], [0.5, 0.0]])   # second slot is padding
    x = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    got = spmm_ell_hbm_pallas(idx, val, x, interpret=True)
    want = jnp.stack([x[5], 0.5 * x[2]])
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_spmm_ell_hbm_all_padding_rows():
    """Rows whose every slot is padding (val == 0 everywhere) come out 0."""
    idx, val, x = _case(40, 4, 100, 8)
    val = val.at[7].set(0.0).at[23].set(0.0)
    got = spmm_ell_hbm_pallas(idx, val, x, bb=16, interpret=True)
    want = ref.spmm_ell(idx, val, x)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(got)[7] == 0) and np.all(np.asarray(got)[23] == 0)


# ---------------------------------------------------------------------------
# int8 source rows consumed natively (x_scale epilogue dequant)
# ---------------------------------------------------------------------------

def _quantize_per_channel(x):
    """Per-channel symmetric int8 quantization of a [n, f] f32 matrix."""
    scale = (jnp.max(jnp.abs(x), axis=0, keepdims=True) / 127.0 + 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


@pytest.mark.parametrize("b,deg,n,f", [
    (8, 4, 16, 8),
    (33, 7, 50, 12),          # non-multiple tiles
    (128, 16, 3000, 32),      # multi-tile
])
def test_spmm_ell_hbm_int8_scale_parity(b, deg, n, f):
    """int8 rows with the epilogue scale reproduce the dequantize-up-front
    result (the scale commutes with the neighbor sum)."""
    idx, val, x = _case(b, deg, n, f)
    q, scale = _quantize_per_channel(x)
    got = spmm_ell_hbm_pallas(idx, val, q, x_scale=scale, interpret=True)
    want = ref.spmm_ell(idx, val, q.astype(jnp.float32) * scale)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_spmm_ell_hbm_int8_matches_resident_q_kernel():
    """Both variants dequantize in-kernel: HBM int8 output matches the
    resident quantized kernel's on the same operands."""
    idx, val, x = _case(60, 6, 400, 16)
    q, scale = _quantize_per_channel(x)
    hbm = spmm_ell_hbm_pallas(idx, val, q, x_scale=scale, bb=32,
                              interpret=True)
    resident = spmm_ell_pallas(idx, val, q, x_scale=scale, interpret=True)
    assert_allclose(np.asarray(hbm), np.asarray(resident),
                    rtol=1e-6, atol=1e-6)


def test_spmm_ell_hbm_int8_ragged_width():
    """A width that is not a multiple of 128 is lane-padded for the row
    DMAs and sliced back."""
    idx, val, x = _case(75, 8, 400, 40)
    q, scale = _quantize_per_channel(x)
    got = spmm_ell_hbm_pallas(idx, val, q, x_scale=scale, interpret=True)
    want = ref.spmm_ell(idx, val, q.astype(jnp.float32) * scale)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_ops_dispatch_routes_hbm_int8(monkeypatch):
    """ops.spmm_ell with an int8 x + x_scale forced onto the HBM variant:
    no up-front dequant materialization, still oracle-parity."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    monkeypatch.setenv("REPRO_SPMM_VARIANT", "hbm")
    idx, val, x = _case(60, 6, 333, 16)
    q, scale = _quantize_per_channel(x)
    got = ops.spmm_ell(idx, val, q, x_scale=scale)
    want = ref.spmm_ell(idx, val, q.astype(jnp.float32) * scale)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the row-DMA gather: one copy per slot, every slot copied
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    "repeated_ids",     # one source row feeds many slots of one tile
    "last_row",         # every slot reads the source's last row
    "pad_to_last_row",  # padding slots point at the last row, val 0
    "single_source",    # n_src == 1
    "deg_one",          # D == 1: one DMA wave per tile
    "tile_tail",        # b one past a tile: the tail tile is mostly padding
    "wide_sparse",      # wide rows, mostly padding slots
])
def test_spmm_ell_hbm_gather_cases(case):
    idx, val, x = _case(45, 6, 90, 24, seed=7)
    if case == "repeated_ids":
        idx = jnp.full_like(idx, 17)
    elif case == "last_row":
        idx = jnp.full_like(idx, 89)
    elif case == "pad_to_last_row":
        pad = jnp.arange(6)[None, :] >= 3
        idx = jnp.where(pad, 89, idx)
        val = jnp.where(pad, 0.0, val)
    elif case == "single_source":
        idx, x = jnp.zeros_like(idx), x[:1]
    elif case == "deg_one":
        idx, val = idx[:, :1], val[:, :1]
    elif case == "tile_tail":
        idx, val, x = _case(17, 5, 90, 24, seed=8)
    else:
        idx, val, x = _case(40, 12, 500, 256, seed=9)
        val = jnp.where(jnp.arange(12)[None, :] % 4 == 0, val, 0.0)
    got = spmm_ell_hbm_pallas(idx, val, x, bb=16, interpret=True)
    want = ref.spmm_ell(idx, val, x)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ops.py dispatch heuristic
# ---------------------------------------------------------------------------

def test_spmm_variant_heuristic(monkeypatch):
    monkeypatch.delenv("REPRO_SPMM_VARIANT", raising=False)
    monkeypatch.setenv("REPRO_SPMM_VMEM_BUDGET_MB", "4")
    assert ops.spmm_ell_variant(512, 64) == "resident"
    assert ops.spmm_ell_variant(20000, 64) == "hbm"       # 5 MiB > 4 MiB
    monkeypatch.setenv("REPRO_SPMM_VARIANT", "resident")
    assert ops.spmm_ell_variant(20000, 64) == "resident"
    monkeypatch.setenv("REPRO_SPMM_VARIANT", "hbm")
    assert ops.spmm_ell_variant(8, 8) == "hbm"


def test_spmm_variant_configure(monkeypatch):
    monkeypatch.delenv("REPRO_SPMM_VARIANT", raising=False)
    monkeypatch.delenv("REPRO_SPMM_VMEM_BUDGET_MB", raising=False)
    try:
        ops.configure_spmm_dispatch(variant="hbm")
        assert ops.spmm_ell_variant(8, 8) == "hbm"
        ops.configure_spmm_dispatch(variant="auto", vmem_budget_mb=0.001)
        assert ops.spmm_ell_variant(64, 64) == "hbm"
        with pytest.raises(ValueError):
            ops.configure_spmm_dispatch(variant="nope")
    finally:
        ops._dispatch_overrides.clear()


def test_spmm_variant_configure_reset(monkeypatch):
    """reset=True drops programmatic overrides instead of leaking them
    between test/benchmark cases."""
    monkeypatch.delenv("REPRO_SPMM_VARIANT", raising=False)
    monkeypatch.delenv("REPRO_SPMM_VMEM_BUDGET_MB", raising=False)
    try:
        ops.configure_spmm_dispatch(variant="hbm", vmem_budget_mb=0.001)
        assert ops.spmm_ell_variant(8, 8) == "hbm"
        ops.configure_spmm_dispatch(reset=True)
        assert not ops._dispatch_overrides
        assert ops.spmm_ell_variant(8, 8) == "resident"   # back to defaults
        # reset composes with new settings in one call
        ops.configure_spmm_dispatch(variant="hbm", reset=True)
        assert ops._dispatch_overrides == {"variant": "hbm"}
    finally:
        ops._dispatch_overrides.clear()


def test_ops_dispatch_routes_hbm(monkeypatch):
    """Forced-pallas + forced-hbm: ops.spmm_ell runs the HBM kernel and
    still matches the oracle."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    monkeypatch.setenv("REPRO_SPMM_VARIANT", "hbm")
    idx, val, x = _case(60, 6, 333, 16)
    got = ops.spmm_ell(idx, val, x)
    want = ref.spmm_ell(idx, val, x)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_full_graph_apply_through_hbm(monkeypatch):
    """GCN full-graph oracle is unchanged when routed through the HBM
    variant."""
    from repro.graph.batching import full_operands
    from repro.graph.structure import build_graph
    from repro.nn.gnn_layers import GCN

    rng = np.random.default_rng(0)
    n, m = 120, 600
    src = rng.integers(0, n, m).astype(np.int64)
    dst = rng.integers(0, n, m).astype(np.int64)
    feats = rng.normal(size=(n, 16)).astype(np.float32)
    labels = rng.integers(0, 3, n)
    tr = np.arange(n)
    g = build_graph(src, dst, n, feats, labels, (tr, tr, tr))

    p = GCN.init(jax.random.PRNGKey(0), g.features.shape[1], 8)
    x = jnp.asarray(g.features)
    y_plain = GCN.full_apply(p, x, full_operands(g), jax.nn.relu)

    # now force every spmm through the HBM Pallas kernel (interpret mode)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    monkeypatch.setenv("REPRO_SPMM_VARIANT", "hbm")
    y_hbm = GCN.full_apply(p, x, full_operands(g), jax.nn.relu)
    assert_allclose(np.asarray(y_hbm), np.asarray(y_plain),
                    rtol=1e-5, atol=1e-5)
