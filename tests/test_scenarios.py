"""Scenario-matrix registry tests: pin the GNN backbone set and the
scale-method axis, and run every backbone through the ``train_scenario``
dispatch front."""
import pytest

from repro.configs.scenarios import (MATRIX_BACKBONES, MATRIX_TASKS,
                                     SCENARIO_KNOBS, assert_gnn_only,
                                     matrix_cells)
from repro.nn.gnn_layers import BACKBONES
from repro.train.gnn_trainer import SCALE_METHODS


def test_backbone_set_pinned():
    """The matrix enumerates exactly the paper's Table 2 convolution
    types; a new registration in nn.gnn_layers must be reviewed here
    before it widens the CI matrix."""
    assert set(MATRIX_BACKBONES) == {"gcn", "sage", "gat", "gin",
                                     "transformer"}
    assert set(MATRIX_BACKBONES) == set(BACKBONES)


def test_scale_methods_pinned():
    assert SCALE_METHODS == ("full", "vq", "ns_sage", "labor", "cluster",
                             "saint", "hybrid")
    assert MATRIX_TASKS == ("node", "link")


def test_matrix_cells_enumerate_gnn_only():
    cells = matrix_cells(tasks=("node",))
    assert len(cells) == len(MATRIX_BACKBONES) * len(SCALE_METHODS)
    backbones = {b for b, _, _ in cells}
    assert_gnn_only(backbones)            # no unknown backbone
    with pytest.raises(ValueError, match="unknown"):
        assert_gnn_only(["gcn", "mlp"])


def test_knobs_documented():
    for knob in ("REPRO_SCALE_METHOD", "REPRO_SAMPLER_FANOUT",
                 "REPRO_WALK_LENGTH", "REPRO_N_PARTS", "REPRO_HYBRID_CTX",
                 "REPRO_SAMPLER_EXECUTOR"):
        assert knob in SCENARIO_KNOBS


@pytest.mark.parametrize("backbone", MATRIX_BACKBONES)
def test_train_scenario_smoke(backbone):
    """One tiny end-to-end cell per trainer family through the dispatch
    front (full / vq / one sampler / hybrid), for every backbone."""
    from repro.graph.datasets import synthetic_arxiv
    from repro.models.gnn import GNNConfig
    from repro.core.codebook import CodebookConfig
    from repro.train.gnn_trainer import train_scenario
    g = synthetic_arxiv(n=200, seed=0)
    cfg = GNNConfig(backbone=backbone, f_in=g.f, hidden=16,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=16, f_prod=4))
    for method in ("full", "vq", "saint", "hybrid"):
        r = train_scenario(g, cfg, method, epochs=1, batch_size=64,
                           eval_every=1)
        assert "val" in r["final"], method


@pytest.mark.parametrize("n", [4000, 4003, 169343])
def test_paper_batch_size_fills_every_batch(n):
    """An epoch at the paper's ~n/4 batch is four batches, none of them a
    sliver of n % 4 real nodes (a full-size optimizer step on a few
    nodes' gradient)."""
    import numpy as np
    from types import SimpleNamespace
    from repro.configs.vq_gnn_paper import paper_batch_size
    from repro.graph.batching import epoch_slices
    b = paper_batch_size(SimpleNamespace(n=n))
    _, mask = epoch_slices(np.arange(n), b)
    assert mask.shape[0] == 4
    assert mask.sum(axis=1).min() >= b - 3
