"""Inference traffic: whole transductive sweeps of the inference executor
(``models.gnn.vq_infer_epoch``: one ``vq_infer_layer`` scan per layer over
every node, in the configuration's batch size), back to back.

The comparison reads the last sweep of the window, every row, against the
reference's sweep over the same batches with the seed's weights and codeword
assignments.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench.harness import common, graphgen, weights


class Run:
    def __init__(self, cell, seed: int, seconds: float):
        self.cell, self.seed = cell, seed
        self.config = cell.config
        self.model = common.model_dict(cell.config)
        self.b = int(cell.config["batch"])

    def setup(self) -> None:
        import jax.numpy as jnp
        from repro.graph.batching import build_epoch_plan, full_operands

        self.gd = gd = graphgen.generate(self.config["graph"], self.seed)
        g = common.program_graph(gd)
        self.n = gd.n
        ops = full_operands(g)
        self.plan = build_epoch_plan(g, full_ops=ops)
        self.width = int(self.plan.nbr_ids.shape[1])
        self.deg = ops.degrees
        self.cfg = common.program_config(self.config)
        params, states = weights.make(self.model, gd.n,
                                      common.weight_key(self.seed))
        self.params, self.vq = params, weights.to_program(states)
        ids, sm = common.epoch_slices(np.arange(gd.n), self.b)
        self.ids, self.sm = ids, sm
        self.ids_d = jnp.asarray(ids.astype(np.int32))
        self.sm_d = jnp.asarray(sm)
        self.out = self._sweep()          # compiles the layer programs

    def _sweep(self):
        from repro.models.gnn import vq_infer_epoch
        with common.span("program.vq_infer_epoch"):
            acts, _ = vq_infer_epoch(self.params, self.vq, self.plan,
                                     self.ids_d, self.sm_d, self.gd.x,
                                     self.deg, self.cfg)
        with common.span("host.wait"):
            acts.block_until_ready()
        return acts

    def window(self, seconds: float) -> dict:
        sweeps, ends = 0, []
        t0 = time.perf_counter()
        while True:
            self.out = self._sweep()
            sweeps += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds:
                break
        per = np.diff([0.0] + ends)
        print(f"infer: {sweeps} sweeps, seconds each min {per.min():.4f} "
              f"median {np.median(per):.4f} max {per.max():.4f}",
              file=sys.stderr)
        self.sweeps, self.elapsed = sweeps, elapsed
        self.out = np.asarray(self.out)
        return {"infer_nodes_per_s": sweeps * self.n / elapsed}

    def dims(self) -> list:
        return weights.layer_dims(self.model)

    def counters(self) -> dict:
        return {"sweeps": self.sweeps, "window_s": self.elapsed,
                "rows": self.sweeps * self.n}

    def attempted(self) -> tuple[int, int]:
        return self.sweeps, 0

    def release(self) -> None:
        self.plan = self.params = self.vq = None

    def reference(self, precision: str) -> np.ndarray:
        import jax.numpy as jnp
        vqgnn = common.reference(self.cell)
        params, states = weights.make(self.model, self.n,
                                      common.weight_key(self.seed))
        t = vqgnn.tables(self.gd.src, self.gd.dst, self.n)
        out, _ = vqgnn.infer_sweep(
            params, states, jnp.asarray(self.ids.astype(np.int32)),
            jnp.asarray(self.sm), self.gd.x, t, self.model, precision)
        return np.asarray(out)

    @staticmethod
    def compare(got: np.ndarray, ref: np.ndarray) -> dict:
        gaps = common.row_gaps(got, ref)
        return {"out_gap": float(np.max(gaps)),
                "out_gap_p50": float(np.median(gaps))}

    def readings(self, precision: str) -> dict:
        return self.compare(self.out, self.reference(precision))
