"""Int8 codeword/assignment operand path (DESIGN.md section 13): the
per-branch/per-channel codeword quantizer and its drift-aware rescale, the
int8-epilogue kernel variants (fused context +/- w_t, SpMM x_scale) against
the dequantized-fp32 oracle, uint8 assignment emission from the VQ-update
kernel, the ops.py dispatch consuming QTensor/uint8 operands data-driven
(no env reads inside jit), the precision-aware state constructors in
core/conv.py + models/gnn.py, and fp32-vs-int8 end-to-end agreement for
inference and a short training run.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from numpy.testing import assert_allclose

from repro.core.codebook import CodebookConfig
from repro.core.conv import (assignment_dtype, init_layer_vq_state,
                             layer_codewords, quantize_layer_state)
from repro.core.message_passing import inject_context_grad
from repro.distributed.quantization import (CODEWORD_SCALE_DRIFT, QTensor,
                                            quantize_codewords,
                                            quantize_tensor)
from repro.kernels import ops, ref
from repro.kernels.context_ell import context_ell_pallas
from repro.kernels.spmm_ell import spmm_ell_pallas
from repro.kernels.vq_update import vq_assign_update_pallas


def _case(b, deg, n, nb, k, f_blk, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    ids = jax.random.randint(k1, (b, deg), 0, n).astype(jnp.int32)
    val = jax.random.normal(k2, (b, deg), jnp.float32)
    assign = jax.random.randint(k3, (nb, n), 0, k).astype(jnp.uint8)
    cw = jax.random.normal(k4, (nb, k, f_blk), jnp.float32)
    return ids, val, assign, cw


# ---------------------------------------------------------------------------
# quantizer: shapes, round-trip error, drift-aware rescale
# ---------------------------------------------------------------------------

def test_quantize_codewords_shapes_and_roundtrip():
    cw = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 8))
    qt = quantize_codewords(cw)
    assert qt.q.shape == (4, 64, 8) and qt.q.dtype == jnp.int8
    assert qt.scale.shape == (4, 1, 8) and qt.scale.dtype == jnp.float32
    deq = qt.q.astype(jnp.float32) * qt.scale
    # symmetric int8 per (branch, channel): error bounded by half a step
    amax = jnp.max(jnp.abs(cw), axis=-2, keepdims=True)
    assert float(jnp.max(jnp.abs(deq - cw) / (amax / 127.0))) <= 0.51


def test_quantize_codewords_drift_band():
    cw = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 4))
    prev = quantize_codewords(cw)
    # within the band (amax shrank by < drift): scale is reused exactly
    kept = quantize_codewords(cw * 0.95, prev=prev)
    assert_allclose(np.asarray(kept.scale), np.asarray(prev.scale))
    # shrunk below amax/drift or grown above amax: rescaled
    for factor in (1.0 / (CODEWORD_SCALE_DRIFT * 1.2), 1.5):
        moved = quantize_codewords(cw * factor, prev=prev)
        assert not np.allclose(np.asarray(moved.scale),
                               np.asarray(prev.scale))


# ---------------------------------------------------------------------------
# kernel parity: int8 operands vs the dequantized-fp32 oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,deg,n,nb,k,f_blk", [
    (8, 4, 16, 2, 4, 8),
    (33, 7, 50, 4, 16, 8),
    (257, 5, 999, 1, 256, 8),      # k=256 at the uint8 boundary
    (40, 11, 300, 8, 200, 5),      # 8 x 5, k % 128 != 0, D % 8 != 0
])
@pytest.mark.parametrize("with_wt", [False, True])
def test_context_ell_int8_parity(b, deg, n, nb, k, f_blk, with_wt):
    ids, val, assign, cw = _case(b, deg, n, nb, k, f_blk)
    qt = quantize_codewords(cw)
    deq = qt.q.astype(jnp.float32) * qt.scale
    w_t = jax.random.normal(jax.random.PRNGKey(9),
                            (nb * f_blk, 5)) if with_wt else None
    got = context_ell_pallas(ids, val, assign, qt.q, cw_scale=qt.scale,
                             w_t=w_t, interpret=True)
    want = ref.context_ell(ids, val, assign.astype(jnp.int32), deq, w_t=w_t)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    # the CPU reference with int8 operands agrees too
    ref_q = ref.context_ell(ids, val, assign, qt.q, w_t=w_t,
                            cw_scale=qt.scale)
    assert_allclose(np.asarray(ref_q), np.asarray(want), rtol=1e-5,
                    atol=1e-5)


def test_spmm_ell_int8_parity():
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    ids = jax.random.randint(k1, (64, 8), 0, 100).astype(jnp.int32)
    val = jax.random.normal(k2, (64, 8), jnp.float32)
    x = jax.random.normal(k3, (100, 16), jnp.float32)
    qt = quantize_tensor(x)
    deq = qt.q.astype(jnp.float32) * qt.scale
    got = spmm_ell_pallas(ids, val, qt.q, x_scale=qt.scale, interpret=True)
    want = ref.spmm_ell(ids, val, deq)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    ref_q = ref.spmm_ell(ids, val, qt.q, qt.scale)
    assert_allclose(np.asarray(ref_q), np.asarray(want), rtol=1e-5,
                    atol=1e-5)


# ---------------------------------------------------------------------------
# uint8 assignment emission from the VQ-update kernel
# ---------------------------------------------------------------------------

def test_vq_update_emit_uint8_matches_int32():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (100, 8), jnp.float32)
    cw = jax.random.normal(jax.random.PRNGKey(5), (64, 8), jnp.float32)
    i32, qe32, c32, s32 = vq_assign_update_pallas(x, cw, interpret=True)
    i8, qe8, c8, s8 = vq_assign_update_pallas(x, cw, interpret=True,
                                              emit_dtype=jnp.uint8)
    assert i8.dtype == jnp.uint8
    assert np.array_equal(np.asarray(i32), np.asarray(i8).astype(np.int32))
    assert_allclose(np.asarray(qe32), np.asarray(qe8))
    assert np.array_equal(np.asarray(c32), np.asarray(c8))


def test_vq_update_emit_uint8_needs_small_k():
    x = jnp.zeros((8, 4))
    cw = jnp.zeros((300, 4))
    with pytest.raises(ValueError, match="emit_dtype"):
        vq_assign_update_pallas(x, cw, interpret=True,
                                emit_dtype=jnp.uint8)


# ---------------------------------------------------------------------------
# ops dispatch: QTensor/uint8 operands are consumed data-driven
# ---------------------------------------------------------------------------

def test_ops_context_ell_qtensor_cpu_path():
    ids, val, assign, cw = _case(16, 4, 40, 2, 16, 8)
    qt = quantize_codewords(cw)
    deq = qt.q.astype(jnp.float32) * qt.scale
    got = ops.context_ell(ids, val, assign, qt)
    want = ref.context_ell(ids, val, assign.astype(jnp.int32), deq)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_ops_spmm_ell_qtensor_cpu_path():
    key = jax.random.PRNGKey(6)
    k1, k2, k3 = jax.random.split(key, 3)
    ids = jax.random.randint(k1, (32, 4), 0, 50).astype(jnp.int32)
    val = jax.random.normal(k2, (32, 4), jnp.float32)
    x = jax.random.normal(k3, (50, 8), jnp.float32)
    qt = quantize_tensor(x)
    got = ops.spmm_ell(ids, val, qt)
    want = ref.spmm_ell(ids, val, qt.q.astype(jnp.float32) * qt.scale)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_uint8_table_shifts_dispatch_crossover():
    """The assignment table no longer enters VMEM (XLA gathers it ahead
    of the fused kernel), so its storage moves no crossover: traced
    through the dispatch, at a budget the int32 [4, 100k] table (1.6 MB)
    once exceeded, int32 and uint8 tables both take the fused kernel, and
    a codebook too large for the budget takes the loop whatever the
    table's dtype."""
    from repro.analysis.trace_count import CONTEXT_TRACE_COUNT
    sds = jax.ShapeDtypeStruct
    ops.configure_context_dispatch(reset=True, vmem_budget_mb=1.0)
    try:
        n, nb, f_blk, deg = 100_000, 4, 8, 16
        for dt in (jnp.int32, jnp.uint8):
            for k, want in ((256, "context.fused"), (8192, "context.loop")):
                before = CONTEXT_TRACE_COUNT.snapshot()
                jax.make_jaxpr(lambda *a: ops._context_ell_kernel(*a))(
                    sds((256, deg), jnp.int32), sds((256, deg), jnp.float32),
                    sds((nb, n), dt), sds((nb, k, f_blk), jnp.float32),
                    None, None)
                delta = CONTEXT_TRACE_COUNT.delta(before)
                assert {key for key, c in delta.items() if c} == {want}, \
                    (dt, k, delta)
    finally:
        ops.configure_context_dispatch(reset=True)


def test_kernel_precision_config(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_PRECISION", raising=False)
    assert ops.kernel_precision() == "fp32"
    monkeypatch.setenv("REPRO_KERNEL_PRECISION", "int8")
    assert ops.kernel_precision() == "int8"
    ops.configure_kernel_precision("fp32")      # override out-ranks env
    try:
        assert ops.kernel_precision() == "fp32"
    finally:
        ops.configure_kernel_precision(reset=True)
    assert ops.kernel_precision() == "int8"
    with pytest.raises(ValueError):
        ops.configure_kernel_precision("int4")


# ---------------------------------------------------------------------------
# state constructors: precision-aware assignment dtype + qcw snapshots
# ---------------------------------------------------------------------------

def test_init_layer_vq_state_precision(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_PRECISION", raising=False)
    cfg = CodebookConfig(k=64, f_prod=4)
    st32 = init_layer_vq_state(jax.random.PRNGKey(0), 50, 16, 16, cfg)
    assert st32.assignment.dtype == jnp.int32 and st32.qcw is None
    ops.configure_kernel_precision("int8")
    try:
        assert assignment_dtype(cfg) == jnp.uint8
        st8 = init_layer_vq_state(jax.random.PRNGKey(0), 50, 16, 16, cfg)
    finally:
        ops.configure_kernel_precision(reset=True)
    assert st8.assignment.dtype == jnp.uint8
    assert st8.qcw is not None
    fcw, gcw = layer_codewords(st8, 16, cfg)
    assert isinstance(fcw, QTensor) and isinstance(gcw, QTensor)
    # dense=True always yields dense f32 tables (GAT/transformer path)
    dfcw, _ = layer_codewords(st8, 16, cfg, dense=True)
    assert not isinstance(dfcw, QTensor) and dfcw.dtype == jnp.float32


def test_quantize_layer_state_drift_reuse():
    cfg = CodebookConfig(k=32, f_prod=4)
    st = init_layer_vq_state(jax.random.PRNGKey(1), 30, 8, 8, cfg)
    q1 = quantize_layer_state(st, 8, cfg)
    assert q1.qcw is not None
    # requantizing an unchanged codebook keeps the grid byte-identical
    q2 = quantize_layer_state(q1, 8, cfg)
    assert np.array_equal(np.asarray(q1.qcw.feat.q),
                          np.asarray(q2.qcw.feat.q))
    assert_allclose(np.asarray(q1.qcw.feat.scale),
                    np.asarray(q2.qcw.feat.scale))


# ---------------------------------------------------------------------------
# Eq. 7 backward with a QTensor gradient-codeword operand
# ---------------------------------------------------------------------------

def test_inject_context_grad_qtensor():
    b, deg, n, nb, f_blk, f_out = 8, 3, 20, 2, 4, 6
    ids, val, assign, gcw = _case(b, deg, n, nb, 16, f_blk, seed=7)
    qt = quantize_codewords(gcw)
    deq = qt.q.astype(jnp.float32) * qt.scale
    x = jax.random.normal(jax.random.PRNGKey(8), (b, f_out))
    w = jax.random.normal(jax.random.PRNGKey(9), (f_out, nb * f_blk))

    def loss(x_b, gq):
        return jnp.sum(inject_context_grad(x_b, val, ids, gq, assign, w))

    # grad only wrt x_b: the int8 snapshot is a frozen operand, but the
    # custom-VJP backward still builds its cotangent (the QTensor-safe
    # tree_map zeros in _inject_bwd) -- a non-tree-safe rule would throw
    gx_q = jax.grad(loss)(x, qt)
    gx_d = jax.grad(loss)(x, deq)
    assert_allclose(np.asarray(gx_q), np.asarray(gx_d), rtol=1e-5,
                    atol=1e-5)
    # the phantom term is real (not the identity grad of ones)
    assert not np.allclose(np.asarray(gx_q), 1.0)


# ---------------------------------------------------------------------------
# end-to-end: fp32-trained model served int8, and int8 training smoke
# ---------------------------------------------------------------------------

def test_quantized_inference_agreement(monkeypatch):
    # pin fp32 state construction so the comparison is really int8-vs-fp32
    # even when the whole sweep runs under REPRO_KERNEL_PRECISION=int8
    monkeypatch.delenv("REPRO_KERNEL_PRECISION", raising=False)
    from repro.graph.datasets import synthetic_arxiv
    from repro.models.gnn import (GNNConfig, init_gnn, init_vq_states,
                                  quantize_vq_states)
    from repro.train.gnn_trainer import vq_inference

    g = synthetic_arxiv(n=300, seed=0)
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=16,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=32, f_prod=4))
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    vq = init_vq_states(jax.random.PRNGKey(1), cfg, g.n)
    vq8 = quantize_vq_states(vq, cfg)
    for st in vq8:
        assert st.assignment.dtype == jnp.uint8 and st.qcw is not None
    y32 = vq_inference(params, vq, g, cfg, batch_size=100)
    y8 = vq_inference(params, vq8, g, cfg, batch_size=100)
    agree = float((np.argmax(np.asarray(y32), -1) ==
                   np.argmax(np.asarray(y8), -1)).mean())
    assert agree >= 0.98


def test_quantize_vq_states_needs_small_k():
    from repro.graph.datasets import synthetic_arxiv
    from repro.models.gnn import (GNNConfig, init_vq_states,
                                  quantize_vq_states)
    g = synthetic_arxiv(n=100, seed=0)
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=16,
                    n_out=g.num_classes, n_layers=1,
                    codebook=CodebookConfig(k=300, f_prod=4))
    vq = init_vq_states(jax.random.PRNGKey(1), cfg, g.n)
    with pytest.raises(ValueError, match="256"):
        quantize_vq_states(vq, cfg)


def test_int8_training_smoke():
    from repro.graph.datasets import synthetic_arxiv
    from repro.models.gnn import GNNConfig
    from repro.train.gnn_trainer import train_vq

    g = synthetic_arxiv(n=300, seed=0)
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=16,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=32, f_prod=4))
    ops.configure_kernel_precision("int8")
    try:
        r = train_vq(g, cfg, epochs=2, batch_size=100, eval_every=100)
    finally:
        ops.configure_kernel_precision(reset=True)
    for st in r["vq_states"]:
        assert st.assignment.dtype == jnp.uint8
        assert st.qcw is not None and st.qcw.feat.q.dtype == jnp.int8
    assert np.isfinite(r["final"]["val"])
