"""Scenario-matrix registry: the GNN axes of the paper's comparison.

The matrix ``benchmarks/bench_ablation.py`` sweeps -- and the CI
``scenario-matrix`` job gates -- is backbone x scale method x task:

  backbones      the paper's Table 2 convolution types (``nn.gnn_layers``)
  scale methods  how training fits in device memory: the full-graph oracle,
                 VQ-GNN (Alg. 1), the four sampling baselines on the
                 sampler epoch executor, and the VQ/sampling hybrid
                 (``train.gnn_trainer.train_scenario`` dispatch)
  tasks          node classification / link prediction

``tests/test_scenarios.py`` pins each axis.
"""
from repro.train.gnn_trainer import SCALE_METHODS

# pinned tuple (not BACKBONES.keys()) so an accidental registration in
# nn.gnn_layers widens the CI matrix only after an explicit review here;
# the consistency test asserts the two stay equal.
MATRIX_BACKBONES = ("gcn", "sage", "gat", "gin", "transformer")

MATRIX_TASKS = ("node", "link")

# env knobs honored by train_scenario / the benchmark driver
SCENARIO_KNOBS = {
    "REPRO_SCALE_METHOD": "scale method when not passed explicitly "
                          f"(one of {SCALE_METHODS}; default 'vq')",
    "REPRO_SAMPLER_FANOUT": "per-layer fanout for ns_sage/labor/hybrid "
                            "(default 5)",
    "REPRO_WALK_LENGTH": "GraphSAINT random-walk length (default 3)",
    "REPRO_N_PARTS": "Cluster-GCN partition count (default 32)",
    "REPRO_HYBRID_CTX": "hybrid context-slot budget per batch "
                        "(default batch_size)",
    "REPRO_SAMPLER_EXECUTOR": "0 -> per-batch host loop instead of the "
                              "sampler epoch executor (default on)",
}


def matrix_cells(tasks=("node",)):
    """Enumerate (backbone, scale_method, task) cells of the matrix."""
    return [(b, m, t) for t in tasks for b in MATRIX_BACKBONES
            for m in SCALE_METHODS]


def assert_gnn_only(names) -> None:
    """Guard used by the matrix path: raise if a name that is not one of
    ``MATRIX_BACKBONES`` shows up where a GNN backbone is expected."""
    unknown = sorted(set(names) - set(MATRIX_BACKBONES))
    if unknown:
        raise ValueError(
            f"unknown backbones {unknown}; expected a subset of "
            f"{MATRIX_BACKBONES}")
