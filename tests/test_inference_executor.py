"""Inference-executor tests (DESIGN.md section 11): jitted ``lax.scan``
layer sweeps vs the eager per-batch fallback, the wrap-padded tail
regression (``g.n % batch_size != 0``), inductive feature-half refresh
inside jit, the compile-count / jaxpr contracts, the one-compile serve
step, and the accounting / metric bugfix satellites of ISSUE 5."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.configs.scenarios import MATRIX_BACKBONES
from repro.core import codebook as cbm
from repro.core.codebook import CodebookConfig
from repro.graph.batching import (build_epoch_plan, epoch_slices,
                                  full_operands, inference_slices)
from repro.graph.datasets import synthetic_arxiv
from repro.analysis.trace_count import INFER_TRACE_COUNT
from repro.models.gnn import (GNNConfig,
                              _layer_out_dims, _vq_infer_layer_body,
                              hits_at_k, init_gnn, init_vq_states,
                              vq_infer_epoch, vq_serve_batch)
from repro.train.gnn_trainer import vq_inference


@pytest.fixture(scope="module")
def g():
    return synthetic_arxiv(n=300, seed=0)


def _setup(g, backbone):
    cfg = GNNConfig(backbone=backbone, f_in=g.f, hidden=32,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=32, f_prod=4))
    ops = full_operands(g)
    return dict(cfg=cfg, ops=ops, x=jnp.asarray(g.features),
                params=init_gnn(jax.random.PRNGKey(0), cfg),
                vq=init_vq_states(jax.random.PRNGKey(1), cfg, g.n),
                plan=build_epoch_plan(g, full_ops=ops))


@pytest.fixture(scope="module", params=MATRIX_BACKBONES)
def setup(g, request):
    """Every backbone, for the tests whose subject is an inference or
    serving path."""
    return _setup(g, request.param)


@pytest.fixture(scope="module")
def gcn_setup(g):
    """GCN only, for the tests whose subject has no backbone."""
    return _setup(g, "gcn")


def _both_paths(g, setup, batch, monkeypatch, **kw):
    monkeypatch.setenv("REPRO_INFER_EXECUTOR", "0")
    eager = vq_inference(setup["params"], setup["vq"], g, setup["cfg"],
                         batch, **kw)
    monkeypatch.setenv("REPRO_INFER_EXECUTOR", "1")
    exe = vq_inference(setup["params"], setup["vq"], g, setup["cfg"],
                       batch, **kw)
    return exe, eager


# ---------------------------------------------------------------------------
# executor vs eager fallback (the ragged-tail regression, satellite 1)
# ---------------------------------------------------------------------------

def test_executor_matches_eager_nondivisible(g, setup, monkeypatch):
    """g.n % batch_size != 0: the wrap-padded executor must agree with the
    eager per-batch loop on every (real) node."""
    assert g.n % 128 != 0
    exe, eager = _both_paths(g, setup, 128, monkeypatch)
    assert exe.shape == (g.n, setup["cfg"].n_out)
    assert_allclose(exe, eager, rtol=2e-5, atol=1e-6)


def test_executor_matches_eager_divisible(g, setup, monkeypatch):
    assert g.n % 100 == 0
    exe, eager = _both_paths(g, setup, 100, monkeypatch)
    assert_allclose(exe, eager, rtol=2e-5, atol=1e-6)


def test_tail_padding_never_leaks_into_real_outputs(g, setup, monkeypatch):
    """Nodes duplicated by the wrap-padding (real slot early in the epoch,
    padded slot in the tail batch) must keep their REAL-slot output: the
    padded slot's write is diverted to the sacrificial row.  The eager
    fallback only ever writes real slots, so exact agreement on the
    duplicated nodes pins the masked-scatter contract."""
    batch = 128
    ids, smask = inference_slices(g.n, batch)
    dup = ids[-1][smask[-1] == 0]
    assert len(dup) > 0                      # the shape really has a tail
    exe, eager = _both_paths(g, setup, batch, monkeypatch)
    assert_allclose(exe[dup], eager[dup], rtol=2e-5, atol=1e-6)


def test_inference_slices_is_identity_epoch_slices():
    ids, smask = inference_slices(10, 4)
    ref_ids, ref_smask = epoch_slices(np.arange(10), 4)
    assert np.array_equal(ids, ref_ids)
    assert np.array_equal(smask, ref_smask)


# ---------------------------------------------------------------------------
# inductive feature-half refresh inside the jitted sweep
# ---------------------------------------------------------------------------

def test_inductive_refresh_inside_jit(g, setup, monkeypatch):
    exe, eager = _both_paths(g, setup, 128, monkeypatch, inductive=True)
    assert_allclose(exe, eager, rtol=2e-5, atol=1e-6)


def test_inductive_executor_states_match_host_assignment(g, setup):
    """The layer-0 state returned by the executor carries exactly the
    feature-half assignment of the input features (computed on host as the
    oracle), proving the refresh really runs inside the layer sweep."""
    s = setup
    ids, smask = inference_slices(g.n, 128)
    _, states = vq_infer_epoch(
        s["params"], s["vq"], s["plan"], jnp.asarray(ids.astype(np.int32)),
        jnp.asarray(smask), s["x"], s["ops"].degrees, s["cfg"],
        inductive=True)
    fi, _ = _layer_out_dims(s["cfg"])[0]
    want = cbm.assign_features_only(
        s["vq"][0].codebook, s["x"], fi, s["cfg"].layer_codebook_cfg())
    assert np.array_equal(np.asarray(states[0].assignment),
                          np.asarray(want))
    # and the histogram invariant of refresh_assignment holds
    assert_allclose(np.asarray(states[0].counts).sum(-1),
                    np.asarray(s["vq"][0].counts).sum(-1), rtol=1e-6)


# ---------------------------------------------------------------------------
# compile-count / jaxpr contracts
# ---------------------------------------------------------------------------

def test_compile_count_independent_of_batch_count(g):
    """One inference pass costs exactly n_layers layer traces, whatever S
    is and whether the batch size divides g.n; a repeat call re-traces
    nothing.  (Fresh cfg -> cold jit cache for this test.)"""
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=16,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=16, f_prod=4))
    params = init_gnn(jax.random.PRNGKey(2), cfg)
    vq = init_vq_states(jax.random.PRNGKey(3), cfg, g.n)

    before = INFER_TRACE_COUNT.snapshot()
    vq_inference(params, vq, g, cfg, 128)      # S = 3 (padded tail)
    assert INFER_TRACE_COUNT.delta(before)["layer"] == cfg.n_layers

    before = INFER_TRACE_COUNT.snapshot()
    vq_inference(params, vq, g, cfg, 128)      # warm: zero new traces
    assert INFER_TRACE_COUNT.delta(before)["layer"] == 0

    before = INFER_TRACE_COUNT.snapshot()
    vq_inference(params, vq, g, cfg, 97)       # S = 4, still ragged n
    assert INFER_TRACE_COUNT.delta(before)["layer"] == cfg.n_layers


def test_layer_body_jaxpr_one_scan_size_independent_of_S(g, setup):
    """The layer sweep lowers to ONE lax.scan whose jaxpr size does not
    grow with the number of batches S (the eager path grew linearly)."""
    s = setup
    body = functools.partial(_vq_infer_layer_body, cfg=s["cfg"], layer=0)

    def jaxpr_for(S, b):
        perm = jnp.zeros((S, b), jnp.int32)
        sm = jnp.ones((S, b), jnp.float32)
        return jax.make_jaxpr(body)(
            s["params"][0], s["vq"][0], s["plan"], perm, sm, s["x"],
            s["ops"].degrees)

    j2, j5 = jaxpr_for(2, 64), jaxpr_for(5, 64)
    for j in (j2, j5):
        assert sum(1 for e in j.jaxpr.eqns
                   if e.primitive.name == "scan") == 1
    assert len(j2.jaxpr.eqns) == len(j5.jaxpr.eqns)


# ---------------------------------------------------------------------------
# serving step
# ---------------------------------------------------------------------------

def test_serve_batch_matches_executor_on_identical_partition(g, setup):
    """With no padding and identical batch partitions across layers, the
    layer-locked executor and the per-request all-layer serve step are the
    same computation: layer l+1's gathered activations ARE the batch's own
    layer-l outputs."""
    s = setup
    ids, smask = inference_slices(g.n, 100)    # divisible: no padding
    assert (smask > 0).all()
    out_exec, _ = vq_infer_epoch(
        s["params"], s["vq"], s["plan"], jnp.asarray(ids.astype(np.int32)),
        jnp.asarray(smask), s["x"], s["ops"].degrees, s["cfg"])
    served = np.concatenate(
        [np.asarray(vq_serve_batch(
            s["params"], s["vq"], s["plan"],
            jnp.asarray(ids[i].astype(np.int32)), s["x"],
            s["ops"].degrees, s["cfg"])) for i in range(ids.shape[0])])
    assert_allclose(np.asarray(out_exec), served, rtol=2e-5, atol=1e-6)


def test_serve_batch_duplicate_ids_rows_agree(g, setup):
    """Request padding repeats ids: every duplicate row must compute the
    same output (the node->slot scatter keeps one authoritative slot)."""
    s = setup
    bids = np.arange(64) % 40                  # ids 0..23 appear twice
    out = np.asarray(vq_serve_batch(
        s["params"], s["vq"], s["plan"], jnp.asarray(bids.astype(np.int32)),
        s["x"], s["ops"].degrees, s["cfg"]))
    assert_allclose(out[:24], out[40:], rtol=1e-6, atol=1e-7)


def test_gnn_server_serve_and_drain(g, setup):
    from repro.launch.serve_gnn import GNNServer, drain_requests
    s = setup
    server = GNNServer(g, s["cfg"], s["params"], s["vq"], batch=64)
    server.warmup()
    req = np.arange(100) % g.n                 # spans two steps (padding)
    out = server.serve(req)
    assert out.shape == (100, s["cfg"].n_out)
    assert server.serve(np.zeros(0, np.int64)).shape == (0, s["cfg"].n_out)
    # chunking + padding must not change per-node outputs
    assert_allclose(out[:64], server.serve(req[:64]), rtol=1e-6, atol=1e-7)
    rng = np.random.default_rng(0)
    requests = [rng.integers(0, g.n, sz) for sz in (3, 64, 7, 130)]
    rep = drain_requests(server, requests)
    assert rep["nodes"] == sum(len(r) for r in requests)
    assert rep["requests"] == len(requests)
    assert rep["steps"] >= 4 and rep["nodes_per_s"] > 0
    assert rep["request_p99_ms"] >= rep["request_p50_ms"]


def test_gnn_server_rejects_indivisible_mesh(g, gcn_setup):
    from repro.launch.serve_gnn import GNNServer

    class _StubMesh:
        shape = {"data": 2}
    with pytest.raises(ValueError, match="divisible"):
        GNNServer(g, gcn_setup["cfg"], gcn_setup["params"],
                  gcn_setup["vq"], batch=33, mesh=_StubMesh())


# ---------------------------------------------------------------------------
# bugfix satellites: hits_at_k, pad bucket, memory accounting
# ---------------------------------------------------------------------------

def test_hits_at_k_empty_pos_is_zero_not_nan():
    out = hits_at_k(np.zeros(0), np.asarray([0.5, 1.5]))
    assert out == 0.0 and not np.isnan(out)


def test_hits_at_k_empty_neg_is_one():
    assert hits_at_k(np.asarray([1.0, 2.0]), np.zeros(0)) == 1.0


def test_pad_bucket_values_and_cap_boundary():
    from repro.train.gnn_trainer import PAD_BUCKET_CAP, _pad_bucket
    assert _pad_bucket(1) == 256
    assert _pad_bucket(256) == 256
    assert _pad_bucket(257) == 512
    assert _pad_bucket(4096, cap=4096) == 4096
    with pytest.raises(ValueError, match="pad-bucket cap"):
        _pad_bucket(4097, cap=4096)
    # non-power-of-two cap: the bucket clamp shrinks padding only, the
    # bucket always covers every real node
    assert _pad_bucket(4500, cap=5000) == 5000
    assert _pad_bucket(PAD_BUCKET_CAP) == PAD_BUCKET_CAP
    with pytest.raises(ValueError, match="pad-bucket cap"):
        _pad_bucket(PAD_BUCKET_CAP + 1)


@pytest.mark.parametrize("f,f_grad,f_prod", [
    (10, 10, 4),    # f not divisible by f_prod
    (16, 4, 4),     # grad-width-capped layout (1 branch, not 4)
    (12, 12, 4),    # divisible layout: old and new accounting agree
])
def test_vq_batch_bytes_codebook_term_matches_allocation(f, f_grad, f_prod):
    """The Table 3 codebook term must equal what init_codebook actually
    allocates per layer (the old `max(1, f // f_prod)` count disagrees on
    non-divisible and grad-capped layouts)."""
    from repro.train.gnn_trainer import vq_batch_bytes
    b, deg, L, k = 64, 8, 2, 32
    total = vq_batch_bytes(b, deg, f, L, k, f_prod=f_prod, f_grad=f_grad)
    other = b * deg * 4 * 6 + L * b * f * 4 + b * deg * f * 4
    cb = cbm.init_codebook(jax.random.PRNGKey(0), f, f_grad,
                           CodebookConfig(k=k, f_prod=f_prod))
    assert total - other == L * cb.codewords_w.size * 4


def test_trainer_accounting_matches_hidden_layer_allocation(g):
    """The train_vq call site must feed the BACKBONE's f_grad into the
    accounting: for GAT the gradient codewords live at f_out + heads, so
    the hidden-layer codebook term must equal what init_vq_states actually
    allocates for a hidden layer (defaulting f_grad to cfg.hidden silently
    re-created the naive count)."""
    from repro.nn.gnn_layers import BACKBONES
    cfg = GNNConfig(backbone="gat", f_in=g.f, hidden=64,
                    n_out=g.num_classes, n_layers=3, heads=4,
                    codebook=CodebookConfig(k=16, f_prod=4))
    vq = init_vq_states(jax.random.PRNGKey(0), cfg, 10)
    fi0, fo0 = _layer_out_dims(cfg)[0]
    f_grad = BACKBONES["gat"].f_grad(fi0, fo0, heads=cfg.heads)
    nb, fb, gb = cbm.branch_layout(cfg.hidden, f_grad, 4)
    mid = vq[1].codebook.codewords_w       # hidden layer: fi = fo = hidden
    assert mid.shape == (nb, cfg.codebook.k, fb + gb)
    # the naive f // f_prod count would have claimed 16 branches
    assert nb != cfg.hidden // 4


def test_vq_batch_bytes_regression_vs_naive_branch_count():
    """Pin the bug: for a grad-capped layout the naive f // f_prod count
    (4 branches) over-counted what branch_layout allocates (1 branch)."""
    nb, fb, gb = cbm.branch_layout(16, 4, 4)
    assert (nb, fb, gb) == (1, 16, 4)
    assert nb != max(1, 16 // 4)
