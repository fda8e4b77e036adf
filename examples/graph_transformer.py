"""Graph-Transformer with GLOBAL attention scaled by VQ (paper App. G).

The case no sampling method can handle: every node attends to every node
(a dense learnable convolution, O(n^2) messages).  VQ-GNN reduces each
mini-batch row to b in-batch keys + k codeword keys -- this example trains
it mini-batched, which is impossible for subgraph samplers.

    PYTHONPATH=src python examples/graph_transformer.py
"""
import argparse

from repro.core.codebook import CodebookConfig
from repro.graph.datasets import synthetic_arxiv
from repro.models.gnn import GNNConfig
from repro import hostenv
from repro.train.gnn_trainer import train_full, train_vq


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1200)
    ap.add_argument("--epochs", type=int, default=30)
    args = ap.parse_args()
    hostenv.enable_compile_cache()

    g = synthetic_arxiv(n=args.n)
    cfg = GNNConfig(backbone="transformer", f_in=g.f, hidden=64,
                    n_out=g.num_classes, n_layers=2, heads=4,
                    codebook=CodebookConfig(k=128))
    print(f"global attention: {g.n}^2 = {g.n**2:,} messages per layer "
          f"full-graph; VQ mini-batch: b*(b+k) per batch")
    rf = train_full(g, cfg, epochs=args.epochs, eval_every=args.epochs)
    rv = train_vq(g, cfg, epochs=args.epochs, batch_size=300,
                  eval_every=args.epochs)
    print(f"full-graph  val acc: {rf['final']['val']:.4f}")
    print(f"VQ-GNN      val acc: {rv['final']['val']:.4f}")


if __name__ == "__main__":
    main()
