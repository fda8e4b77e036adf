"""Scenario-matrix registry tests (ISSUE 6): pin the GNN backbone set,
pin the scale-method axis, and enforce the LM-config quarantine -- the
llama/whisper/moe seeds of ``configs.registry`` must never enumerate as
matrix cells."""
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
    assert_gnn_only(backbones)            # no LM arch ids leaked


def test_lm_archs_quarantined():
    """Every id of the generic LM/speech/vision registry must FAIL the
    GNN-only guard -- the quarantine the scenario matrix depends on."""
    from repro.configs.registry import ARCHS, LM_ARCHS
    assert ARCHS is LM_ARCHS              # back-compat alias intact
    assert len(LM_ARCHS) >= 10
    for name in LM_ARCHS:
        with pytest.raises(ValueError, match="leaked|unknown"):
            assert_gnn_only([name])
    # and none of them collides with a GNN backbone name
    assert not set(LM_ARCHS) & set(MATRIX_BACKBONES)


def test_knobs_documented():
    for knob in ("REPRO_SCALE_METHOD", "REPRO_SAMPLER_FANOUT",
                 "REPRO_WALK_LENGTH", "REPRO_N_PARTS", "REPRO_HYBRID_CTX",
                 "REPRO_SAMPLER_EXECUTOR"):
        assert knob in SCENARIO_KNOBS


def test_train_scenario_smoke():
    """One tiny end-to-end cell per trainer family through the dispatch
    front (full / vq / one sampler / hybrid)."""
    from repro.graph.datasets import synthetic_arxiv
    from repro.models.gnn import GNNConfig
    from repro.core.codebook import CodebookConfig
    from repro.train.gnn_trainer import train_scenario
    g = synthetic_arxiv(n=200, seed=0)
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=16,
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
