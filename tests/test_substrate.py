"""Substrate tests: graph structures/datasets/samplers, optimizer, bounds
(hypothesis property tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

try:  # property tests are optional: skip (not error) without hypothesis
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import bounds
from repro.graph.batching import inductive_view, make_pack
from repro.graph.datasets import DATASETS, synthetic_arxiv, synthetic_ppi
from repro.graph.sampling import (cluster_gcn_batches, graphsaint_rw_batches,
                                  ns_sage_batches, partition_graph)
from repro.train.optimizer import adam, rmsprop


# ---------------------------------------------------------------------------
# graph substrate
# ---------------------------------------------------------------------------

def test_datasets_build():
    for name, fn in DATASETS.items():
        g = fn()
        assert g.n > 0 and g.m > 0
        assert g.features.shape == (g.n, g.f)
        assert g.max_degree() <= 48
        # CSR invariants
        assert g.in_csr.indptr[-1] == g.m
        assert (g.in_csr.indices < g.n).all()


def test_pack_positions_consistent():
    g = synthetic_arxiv(n=300, seed=0)
    bidx = np.arange(64)
    pack = make_pack(g, bidx)
    nbr = np.asarray(pack.nbr_ids)
    pos = np.asarray(pack.nbr_pos)
    mask = np.asarray(pack.nbr_mask)
    # wherever pos >= 0, the neighbor id must equal batch_ids[pos]
    for r in range(64):
        for d in range(nbr.shape[1]):
            if mask[r, d] > 0 and pos[r, d] >= 0:
                assert bidx[pos[r, d]] == nbr[r, d]


def test_inductive_view_hides_test_nodes():
    g = synthetic_ppi(n=400)
    gv = inductive_view(g)
    vis = np.zeros(g.n, bool)
    vis[g.train_idx] = True
    for i in np.where(~vis)[0]:
        assert len(gv.in_csr.neighbors(i)) == 0


def test_samplers_produce_valid_subgraphs():
    g = synthetic_arxiv(n=400, seed=0)
    rng = np.random.default_rng(0)
    for src, dst, nodes, seed_pos, seed_w in ns_sage_batches(
            g, 32, [5, 5], rng, g.train_idx):
        assert (src < len(nodes)).all() and (dst < len(nodes)).all()
        assert len(seed_pos) == 32 and len(seed_w) == 32
        assert (seed_pos < len(nodes)).all()
        break
    part = partition_graph(g, 8, rng)
    assert part.min() >= 0 and part.max() < 8
    for src, dst, nodes, seed_pos, seed_w in cluster_gcn_batches(
            g, part, 2, rng):
        assert len(nodes) > 0
        break
    for src, dst, nodes, seed_pos, seed_w in graphsaint_rw_batches(
            g, 64, 3, rng, g.train_idx):
        assert len(nodes) >= 64
        break


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adam_matches_manual_step():
    opt = adam(lr=0.1, b1=0.9, b2=0.999)
    p = {"w": jnp.ones((3,))}
    st_ = opt.init(p)
    g = {"w": jnp.full((3,), 0.5)}
    p2, st2 = opt.update(g, st_, p)
    # bias-corrected first step: delta = lr * g / (|g| + eps)
    assert_allclose(np.asarray(p2["w"]), np.ones(3) - 0.1, rtol=1e-4)
    assert int(st2.step) == 1


def test_rmsprop_decreases_quadratic():
    opt = rmsprop(lr=0.05)
    p = {"w": jnp.array([3.0, -2.0])}
    st_ = opt.init(p)
    for _ in range(100):
        g = {"w": 2 * p["w"]}
        p, st_ = opt.update(g, st_, p)
    assert float(jnp.abs(p["w"]).max()) < 0.5


def test_optimizer_preserves_namedtuple_structure():
    """Regression: NamedTuple params are tuples; the update must not
    collapse them (bug found in the dry run)."""
    from repro.core.codebook import (CodebookConfig, CodebookState,
                                     init_codebook)
    p = {"vq": init_codebook(jax.random.PRNGKey(0), 8, 8,
                             CodebookConfig(k=4, f_prod=4))}
    opt = adam(1e-3)
    st_ = opt.init(p)
    g = jax.tree_util.tree_map(jnp.ones_like, p)
    p2, _ = opt.update(g, st_, p)
    assert isinstance(p2["vq"], CodebookState)


# ---------------------------------------------------------------------------
# Theorem 2 / Corollary 3 bounds (hypothesis property test)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(8, 40), f=st.sampled_from([4, 8, 16]),
           k=st.integers(2, 8), seed=st.integers(0, 1000))
    def test_theorem2_bound_holds(n, f, k, seed):
        """|| C R R' X W - C X W ||_F <= eps ||C|| ||X|| ||W||  for a fixed
        convolution (Lip(h)=0, identity activation): the Thm 2 inequality."""
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 4)
        c = jax.random.normal(ks[0], (n, n)) / np.sqrt(n)
        x = jax.random.normal(ks[1], (n, f))
        w = jax.random.normal(ks[2], (f, f)) / np.sqrt(f)
        assign = jax.random.randint(ks[3], (n,), 0, k)
        onehot = jax.nn.one_hot(assign, k)
        cw = (onehot.T @ x) / jnp.maximum(onehot.sum(0)[:, None], 1e-9)
        x_hat = cw[assign]

        eps = bounds.vq_relative_error(x, x_hat)
        lhs = bounds.fro(c @ x_hat @ w - c @ x @ w)
        rhs = bounds.feature_error_bound(
            eps, bounds.fro(c), bounds.fro(x), bounds.fro(w))
        assert float(lhs) <= float(rhs) * (1 + 1e-5)
else:
    @pytest.mark.skip(reason="hypothesis not installed (requirements-dev.txt)")
    def test_theorem2_bound_holds():
        pass
