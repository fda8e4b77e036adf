"""The benchmark's generator draws the repository's SBM edges bit for bit,
with the degree cap applied by rank instead of an edge loop."""
import numpy as np
import pytest

from bench.harness import graphgen

ARXIV = dict(classes=40, avg_deg=7.0, homophily=0.65, max_degree=32,
             train_frac=0.54, f=128, noise=0.8, structure_seed=0)


@pytest.mark.parametrize("n,seed", [(400, 0), (2500, 3), (6000, 2 ** 31 + 5)])
def test_edges_match_synthetic_arxiv(n, seed):
    from repro.graph.datasets import synthetic_arxiv
    from repro.graph.structure import csr_from_coo
    g = synthetic_arxiv(n=n, seed=seed)
    labels, src, dst = graphgen.structure(dict(ARXIV, n=n), seed)
    src, dst = graphgen.dedupe(src, dst, n)
    csr = csr_from_coo(src, dst, n)
    assert np.array_equal(csr.indptr, g.in_csr.indptr)
    assert np.array_equal(csr.indices, g.in_csr.indices)
    assert np.array_equal(labels, g.labels)


def test_every_seed_relabels_one_structure():
    cfg = dict(ARXIV, n=3000)
    a, b = graphgen.generate(cfg, 1), graphgen.generate(cfg, 2 ** 33)
    assert not np.array_equal(a.src, b.src)
    for d in (a, b):
        assert sorted(np.bincount(d.dst, minlength=3000)) == sorted(
            np.bincount(b.dst, minlength=3000))
        assert sorted(np.bincount(d.src, minlength=3000)) == sorted(
            np.bincount(b.src, minlength=3000))
        assert np.array_equal(np.bincount(d.labels), np.bincount(b.labels))
    # an edge's endpoints keep their classes under the relabelling
    la, lb = a.labels, b.labels
    assert sorted(zip(la[a.src], la[a.dst])) == sorted(zip(lb[b.src],
                                                           lb[b.dst]))


def test_sbm_edges_match_the_loop_form():
    from repro.graph.datasets import _sbm_edges
    labels = np.random.default_rng(9).integers(0, 7, 900)
    for cap in (4, 16):
        a = _sbm_edges(np.random.default_rng(1), labels, 25.0, 0.7, cap)
        b = graphgen.sbm_edges(np.random.default_rng(1), labels, 25.0, 0.7,
                               cap)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        assert np.bincount(b[1]).max() <= cap


def test_in_edge_rank():
    dst = np.array([3, 1, 3, 3, 0, 1])
    assert graphgen.in_edge_rank(dst).tolist() == [0, 0, 1, 2, 0, 1]


def test_features_follow_the_seed():
    cfg = dict(ARXIV, n=800, f=16)
    a = graphgen.generate(cfg, 11)
    b = graphgen.generate(cfg, 11)
    c = graphgen.generate(cfg, 12)
    assert a.x.shape == (800, 16)
    assert np.array_equal(np.asarray(a.x), np.asarray(b.x))
    assert not np.array_equal(np.asarray(a.x), np.asarray(c.x))
    assert np.isfinite(np.asarray(a.x)).all()


def test_feature_mixing_is_the_neighbour_mean():
    """x = 0.7 x0 + 0.3 mean of in-neighbours, with x0 recovered from a
    graph without edges drawn from the same key."""
    import jax.numpy as jnp
    n, f = 50, 8
    labels = np.arange(n) % 5
    src = np.array([1, 2, 3, 3])
    dst = np.array([0, 0, 4, 4])        # a duplicated edge counts twice
    key = graphgen.jax_key(4)
    nbr, mask = graphgen.ell_table(src, dst, n, 4)
    x = np.asarray(graphgen.features(key, labels, nbr, mask, f, 5, 0.8))
    e_nbr, e_mask = graphgen.ell_table(src[:0], dst[:0], n, 4)
    x0 = np.asarray(graphgen.features(key, labels, e_nbr, e_mask, f, 5,
                                      0.8)) / 0.7
    want0 = 0.7 * x0[0] + 0.3 * (x0[1] + x0[2]) / 2
    want4 = 0.7 * x0[4] + 0.3 * x0[3]
    np.testing.assert_allclose(x[0], want0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(x[4], want4, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(x[7], 0.7 * x0[7], rtol=1e-5, atol=1e-6)
    del jnp
