"""End-to-end behaviour tests: the paper's training regimes learn and VQ
inference agrees with exact inference."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.codebook import CodebookConfig
from repro.graph.batching import full_operands, inductive_view
from repro.graph.datasets import synthetic_arxiv, synthetic_collab, \
    synthetic_ppi
from repro.models.gnn import GNNConfig, full_predict, node_metric
from repro.train.gnn_trainer import (train_full, train_sampler, train_vq,
                                     vq_inference)


@pytest.fixture(scope="module")
def arxiv():
    return synthetic_arxiv(n=800, seed=0)


def _cfg(g, backbone="gcn", **kw):
    return GNNConfig(backbone=backbone, f_in=g.f, hidden=48,
                     n_out=g.num_classes, n_layers=2,
                     codebook=CodebookConfig(k=128, f_prod=4), **kw)


def test_vq_gnn_learns_and_tracks_full_graph(arxiv):
    g = arxiv
    cfg = _cfg(g)
    rf = train_full(g, cfg, epochs=30, eval_every=30)
    rv = train_vq(g, cfg, epochs=30, batch_size=300, eval_every=30)
    assert rf["final"]["val"] > 0.75          # the task is learnable
    assert rv["final"]["val"] > rf["final"]["val"] - 0.08


def test_sampler_baseline_trains(arxiv):
    g = arxiv
    r = train_sampler(g, _cfg(g), "graphsaint-rw", epochs=20,
                      batch_size=150, eval_every=20)
    assert r["final"]["val"] > 0.6


def test_vq_inference_agrees_with_exact(arxiv):
    g = arxiv
    cfg = _cfg(g)
    r = train_vq(g, cfg, epochs=30, batch_size=300, eval_every=30)
    exact = np.asarray(full_predict(
        r["params"], jnp.asarray(g.features), full_operands(g), cfg))
    approx = vq_inference(r["params"], r["vq_states"], g, cfg, 300)
    agree = (exact.argmax(-1) == approx.argmax(-1)).mean()
    assert agree > 0.85, agree


def test_inductive_ppi_path():
    g = synthetic_ppi(n=500)
    gv = inductive_view(g)
    cfg = GNNConfig(backbone="sage", f_in=g.f, hidden=48,
                    n_out=g.num_classes, n_layers=2, multilabel=True,
                    codebook=CodebookConfig(k=64, f_prod=4))
    r = train_vq(gv, cfg, epochs=15, batch_size=250, eval_every=15)
    # inductive inference: unseen nodes assigned by feature half
    emb = vq_inference(r["params"], r["vq_states"], g, cfg, 250,
                       inductive=True)
    f1 = float(node_metric(jnp.asarray(emb)[g.test_idx],
                           jnp.asarray(g.labels)[g.test_idx], True))
    assert f1 > 0.55, f1


def test_link_prediction_path():
    g = synthetic_collab(n=800)
    cfg = GNNConfig(backbone="sage", f_in=g.f, hidden=48, n_out=48,
                    n_layers=2, task="link",
                    codebook=CodebookConfig(k=64, f_prod=4))
    r = train_vq(g, cfg, epochs=15, batch_size=400, eval_every=15)
    assert r["final"]["val"] > 0.1    # hits@50 well above random
