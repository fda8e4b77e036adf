"""Pieces every cell runner shares: the cell's graph and model, the program's
containers for them, host spans, and the comparison helpers."""
from __future__ import annotations

import numpy as np

from bench.harness import graphgen


def model_dict(config: dict) -> dict:
    """The reference's view of the configuration: model hyper-parameters
    plus the graph widths they depend on."""
    m = dict(config["model"])
    m["f_in"] = int(config["graph"]["f"])
    m["classes"] = int(config["graph"]["classes"])
    return m


def program_config(config: dict):
    from repro.core.codebook import CodebookConfig
    from repro.models.gnn import GNNConfig
    m = model_dict(config)
    cb = m["codebook"]
    return GNNConfig(
        backbone=m["backbone"], f_in=m["f_in"], hidden=m["hidden"],
        n_out=m["classes"], n_layers=m["layers"],
        codebook=CodebookConfig(k=m["k"], f_prod=m["f_prod"],
                                gamma=cb["gamma"], beta=cb["beta"],
                                revive_threshold=cb["revive_threshold"]))


def program_graph(gd: graphgen.GraphData):
    """The program's Graph over the generated edges; features stay on the
    device."""
    from repro.graph.structure import build_graph
    return build_graph(gd.src, gd.dst, gd.n, gd.x, gd.labels,
                       (gd.train_idx, gd.val_idx, gd.test_idx))


def train_mask(gd: graphgen.GraphData) -> np.ndarray:
    tm = np.zeros(gd.n, np.float32)
    tm[gd.train_idx] = 1.0
    return tm


_REFERENCES: dict = {}


def reference(cell):
    """The configuration's plain reference module: its ``reference`` key,
    a path from the root, loaded once per process so its jitted functions
    compile once."""
    import os
    from bench.harness import cell as cellmod
    path = os.path.join(cell.root, cell.config["reference"])
    if path not in _REFERENCES:
        _REFERENCES[path] = cellmod.load_module(
            path, f"bench_reference_{len(_REFERENCES)}")
    return _REFERENCES[path]


def weight_key(seed: int):
    import jax
    return jax.random.fold_in(graphgen.jax_key(seed), 1)


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def epoch_slices(perm: np.ndarray, b: int) -> tuple[np.ndarray, np.ndarray]:
    """[S, b] batches of a permutation; the tail is filled from its start,
    and those slots carry 0 in the slot mask."""
    n = len(perm)
    s = -(-n // b)
    pad = s * b - n
    ids = np.concatenate([perm, perm[:pad]]) if pad else perm
    sm = np.ones(s * b, np.float32)
    sm[n:] = 0.0
    return ids.reshape(s, b), sm.reshape(s, b)


def leaf_norms(tree) -> np.ndarray:
    import jax
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)))
                     for a in jax.tree_util.tree_leaves(tree)])


def norm_gap(prog: np.ndarray, ref: np.ndarray,
             counted: np.ndarray | None = None) -> float:
    """Worst leaf of |prog norm - ref norm| / max(ref norm of the leaf,
    ref norm of the median leaf)."""
    if counted is None:
        counted = np.ones(len(ref), bool)
    floor = float(np.median(ref[counted]))
    den = np.maximum(ref, floor)
    gaps = np.abs(prog - ref) / np.maximum(den, 1e-30)
    return float(np.max(gaps[counted]))


def row_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: largest |got - want| over the largest |want| of all rows
    compared."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return np.max(np.abs(got - want), axis=1) / scale
