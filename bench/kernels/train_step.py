"""Model operations of one VQ-GNN training step (Alg. 1), for the mfu
metrics.  Per layer with input width fi, output width fo, batch b and
padded neighbour width d:

* dense products with W (GCN: one per layer, SAGE-Mean: two), forward and
  the gradient of W (2 x 2 b fi fo each), and the gradient of the layer
  input (2 b fi fo each) on every layer but the first, whose input needs no
  gradient;
* the exact in-batch SpMM and the codeword context term forward
  (2 b d fi each), and the SpMM's backward (2 b d fi) past the first layer;
* the Eq. 7 gradient-codeword messages (2 b d fo) and their W^T product
  (2 b fo fi) past the first layer;
* the VQ update's distances to the k codewords over the concatenated
  feature and gradient widths (2 b k (fi + fo)).

Recomputation and the one-hot products that some kernels use to gather
rows do not count."""


def count(backbone: str, dims: list, b: int, d: int, k: int) -> float:
    ws = 1 if backbone == "gcn" else 2
    ops = 0.0
    for l, (fi, fo) in enumerate(dims):
        later = l > 0
        ops += ws * (2 + later) * 2.0 * b * fi * fo
        ops += (2 + later) * 2.0 * b * d * fi
        if later:
            ops += 2.0 * b * d * fo + 2.0 * b * fo * fi
        ops += 2.0 * b * k * (fi + fo)
    return ops
