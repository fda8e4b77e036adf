"""The paper's own experimental configurations (App. F hyper-parameters),
as GNNConfig presets keyed by (dataset, backbone).

Paper setup: 3 layers, hidden 128, codebook 1024 (256 "should also work"),
f_prod=4 product VQ, RMSprop(alpha=0.99) lr 3e-3, batch 40K on 169K nodes
(~n/4).  The synthetic look-alikes are ~40x smaller, so the presets scale
k and batch proportionally while keeping every ratio (k/n, b/n, f_prod).
"""
from __future__ import annotations

from repro.core.codebook import CodebookConfig
from repro.graph.structure import Graph
from repro.models.gnn import GNNConfig

PAPER_HIDDEN = 128
PAPER_LAYERS = 3
PAPER_F_PROD = 4
PAPER_LR = 3e-3           # RMSprop, App. F


def paper_config(g: Graph, backbone: str = "gcn",
                 full_scale: bool = False) -> GNNConfig:
    """GNNConfig matching the paper's App. F setup, scaled to the graph."""
    if full_scale:
        k, hidden, layers = 1024, PAPER_HIDDEN, PAPER_LAYERS
    else:
        k = max(64, min(1024, g.n // 8))
        hidden, layers = 64, 2
    task = "link" if g.train_edges is not None else "node"
    return GNNConfig(
        backbone=backbone, f_in=g.f, hidden=hidden,
        n_out=(hidden if task == "link" else g.num_classes),
        n_layers=layers, task=task, multilabel=g.multilabel,
        codebook=CodebookConfig(k=k, f_prod=PAPER_F_PROD))


def paper_batch_size(g: Graph) -> int:
    """40K of 169K nodes ~ n/4 (App. F), rounded up so an epoch is four
    full batches.  ``n // 4`` would leave a fifth batch of ``n % 4`` real
    nodes, whose loss-masked gradient still takes a full-size RMSprop step
    (on ogbn-arxiv's n = 169343, a 3-node step that sends the loss from
    1.9 to 12.5)."""
    return max(64, -(-g.n // 4))
