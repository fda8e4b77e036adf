"""Seeded graph generator for the benchmark cells.

The edge algorithm is the degree-capped stochastic block model of the
repository's synthetic datasets (same random draws, in the same order), with
the degree cap applied by each edge's rank among the in-edges of its
destination instead of a Python loop over edges.

The configuration fixes the structure's own seed, and ``--seed`` relabels
its nodes by a random permutation: every seed gets the same degree sequence
(so the same padded neighbour width and the same work per epoch) in another
order, with its own split, features, weights and batches.  Node features are
class-conditioned Gaussians with sub-cluster structure plus one hop of
neighbour averaging, drawn on the device from the seed (a segment sum over
the padded in-neighbour table, one neighbour slot at a time), so a wide
feature table never passes through the host.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class GraphData(NamedTuple):
    n: int
    src: np.ndarray          # [m] int64 kept edges (deduplicated)
    dst: np.ndarray          # [m] int64
    labels: np.ndarray       # [n] int64
    train_idx: np.ndarray
    val_idx: np.ndarray
    test_idx: np.ndarray
    x: object                # [n, f] float32 device array


def jax_key(seed: int):
    """A jax PRNG key from any non-negative seed, including those above
    32 bits."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def in_edge_rank(dst: np.ndarray) -> np.ndarray:
    """Rank of each edge among the earlier edges (in list order) that share
    its destination: 0 for the first in-edge of a node, 1 for the second..."""
    order = np.argsort(dst, kind="stable")
    sd = dst[order]
    first = np.searchsorted(sd, sd, side="left")
    rank = np.empty(len(dst), np.int64)
    rank[order] = np.arange(len(dst), dtype=np.int64) - first
    return rank


def sbm_edges(rng: np.random.Generator, labels: np.ndarray, avg_deg: float,
              homophily: float, max_degree: int
              ) -> tuple[np.ndarray, np.ndarray]:
    """Degree-capped SBM edges, symmetrized, self loops dropped; the first
    ``max_degree`` in-edges of each node in a random edge order are kept."""
    n = len(labels)
    n_classes = int(labels.max()) + 1
    by_class = [np.where(labels == c)[0] for c in range(n_classes)]
    half = max(1, int(avg_deg) // 2)
    degs = np.clip(rng.poisson(half, n), 1, max_degree // 2)
    total = int(degs.sum())
    srcs = np.repeat(np.arange(n), degs)
    same = rng.random(total) < homophily
    dst = rng.integers(0, n, total)
    src_class = labels[srcs]
    for c in range(n_classes):
        sel = same & (src_class == c)
        if sel.any():
            dst[sel] = rng.choice(by_class[c], size=int(sel.sum()))
    keep = srcs != dst
    s, d = srcs[keep], dst[keep]
    src_all = np.concatenate([s, d])
    dst_all = np.concatenate([d, s])
    order = rng.permutation(len(src_all))
    src_all, dst_all = src_all[order], dst_all[order]
    keep = in_edge_rank(dst_all) < max_degree
    return src_all[keep], dst_all[keep]


def dedupe(src: np.ndarray, dst: np.ndarray, n: int
           ) -> tuple[np.ndarray, np.ndarray]:
    eid = src.astype(np.int64) * n + dst.astype(np.int64)
    keep = np.unique(eid, return_index=True)[1]
    return src[keep], dst[keep]


def splits(rng: np.random.Generator, n: int, train_frac: float
           ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    perm = rng.permutation(n)
    n_tr = int(train_frac * n)
    n_val = int(0.15 * n)
    return perm[:n_tr], perm[n_tr:n_tr + n_val], perm[n_tr + n_val:]


def ell_table(src: np.ndarray, dst: np.ndarray, n: int, width: int
              ) -> tuple[np.ndarray, np.ndarray]:
    """[n, width] in-neighbour ids and 0/1 mask, neighbours in edge-list
    order; ``width`` must cover the largest in-degree."""
    rank = in_edge_rank(dst)
    if len(rank) and rank.max() >= width:
        raise ValueError(f"in-degree {rank.max() + 1} exceeds the table "
                         f"width {width}")
    nbr = np.zeros((n, width), np.int32)
    mask = np.zeros((n, width), np.float32)
    nbr[dst, rank] = src
    mask[dst, rank] = 1.0
    return nbr, mask


def features(key, labels, nbr, mask, f: int, n_classes: int, noise: float,
             mix: float = 0.3, sub_clusters: int = 6):
    """Device feature table [n, f]: each class owns ``sub_clusters``
    sub-centres; a node is its sub-centre plus noise, then mixed with the
    mean of its in-neighbours (duplicated edges count twice, as in the
    repository's datasets)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, labels, nbr, mask):
        n = labels.shape[0]
        kc, ks, ko, kn = jax.random.split(key, 4)
        centers = jax.random.normal(kc, (n_classes, f), jnp.float32)
        subs = centers[:, None, :] + 0.6 * jax.random.normal(
            ks, (n_classes, sub_clusters, f), jnp.float32)
        sub_of = jax.random.randint(ko, (n,), 0, sub_clusters)
        x = subs[labels, sub_of] + (0.35 * noise) * jax.random.normal(
            kn, (n, f), jnp.float32)

        def add(acc, col):
            ids, m = col
            return acc + m[:, None] * x[ids], None

        agg, _ = jax.lax.scan(add, jnp.zeros_like(x), (nbr.T, mask.T))
        agg = agg / jnp.maximum(jnp.sum(mask, axis=1), 1.0)[:, None]
        return (1.0 - mix) * x + mix * agg

    return make(key, jnp.asarray(labels, jnp.int32), jnp.asarray(nbr),
                jnp.asarray(mask))


def structure(graph_cfg: dict, seed: int
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(labels, src, dst) of the SBM drawn from ``seed``, as the
    repository's ``_node_classification`` draws them."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, int(graph_cfg["classes"]), int(graph_cfg["n"]))
    src, dst = sbm_edges(rng, labels, graph_cfg["avg_deg"],
                         graph_cfg["homophily"], graph_cfg["max_degree"])
    return labels, src, dst


def generate(graph_cfg: dict, seed: int) -> GraphData:
    """The cell's graph for ``--seed``: the configuration's structure with
    its nodes relabelled, a split, and features drawn on the device."""
    n = int(graph_cfg["n"])
    n_classes = int(graph_cfg["classes"])
    labels0, src0, dst0 = structure(graph_cfg,
                                    int(graph_cfg["structure_seed"]))
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    labels = np.empty_like(labels0)
    labels[perm] = labels0
    src, dst = perm[src0], perm[dst0]
    tr, va, te = splits(rng, n, graph_cfg["train_frac"])
    nbr, mask = ell_table(src, dst, n, int(graph_cfg["max_degree"]))
    x = features(jax_key(seed), labels, nbr, mask, int(graph_cfg["f"]),
                 n_classes, float(graph_cfg["noise"]))
    s, d = dedupe(src, dst, n)
    return GraphData(n, s, d, labels.astype(np.int64), tr, va, te, x)
