"""Link prediction (paper Table 4, ogbl-collab setting): VQ-GNN vs
full-graph on the synthetic collab look-alike, Hits@50 metric.

    PYTHONPATH=src python examples/link_prediction.py
"""
import argparse

from repro.core.codebook import CodebookConfig
from repro.graph.datasets import synthetic_collab
from repro.models.gnn import GNNConfig
from repro import hostenv
from repro.train.gnn_trainer import train_full, train_vq


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--epochs", type=int, default=40)
    args = ap.parse_args()
    hostenv.enable_compile_cache()

    g = synthetic_collab(n=args.n)
    print(f"graph: {g.n} nodes, {g.m} message edges, "
          f"{len(g.val_edges)} val / {len(g.test_edges)} test positives")
    cfg = GNNConfig(backbone="sage", f_in=g.f, hidden=64, n_out=64,
                    n_layers=2, task="link",
                    codebook=CodebookConfig(k=256, f_prod=4))
    rf = train_full(g, cfg, epochs=args.epochs, eval_every=args.epochs)
    rv = train_vq(g, cfg, epochs=args.epochs, batch_size=500,
                  eval_every=args.epochs)
    print(f"full-graph Hits@50: val {rf['final']['val']:.4f} "
          f"test {rf['final']['test']:.4f}")
    print(f"VQ-GNN     Hits@50: val {rv['final']['val']:.4f} "
          f"test {rv['final']['test']:.4f}")


if __name__ == "__main__":
    main()
