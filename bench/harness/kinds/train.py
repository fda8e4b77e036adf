"""Training traffic: back-to-back epochs of the device-resident epoch
executor (``models.gnn.vq_train_epoch``), each with the host work of the
trainer's epoch loop: a fresh permutation, its batches, the device put and
the losses fetched.  No evaluation runs.

Set-up makes the graph and the weights from the seed, then runs the first
epoch, which compiles the program; the window continues the same state.
The reference follows that first epoch from the same weights and batches,
and the comparison reads each step's loss, the optimizer's accumulated
squared gradients and the change of the parameters and codebooks.
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

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.graph.batching import build_epoch_plan, full_operands
        from repro.train.optimizer import rmsprop

        self.gd = gd = graphgen.generate(self.config["graph"], self.seed)
        g = common.program_graph(gd)
        self.n = gd.n
        ops = full_operands(g)
        self.plan = build_epoch_plan(g, full_ops=ops)
        self.width = int(self.plan.nbr_ids.shape[1])
        self.deg = ops.degrees
        self.labels = jnp.asarray(gd.labels.astype(np.int32))
        self.tm = jnp.asarray(common.train_mask(gd))
        self.cfg = common.program_config(self.config)
        self.opt = rmsprop(self.model["lr"], alpha=self.model["rms_alpha"],
                           eps=self.model["rms_eps"])
        params, states = weights.make(self.model, gd.n,
                                      common.weight_key(self.seed))
        vq = weights.to_program(states)
        ost = self.opt.init(params)
        self.rng = np.random.default_rng([self.seed, 1])
        # the first epoch compiles the program; the reference follows it
        self.first_perm = self.rng.permutation(gd.n)
        params, vq, ost, losses = self._epoch(params, vq, ost,
                                              self.first_perm)
        self.first = {
            "losses": np.asarray(losses),
            "params": jax.tree_util.tree_map(np.asarray, params),
            "nu": jax.tree_util.tree_map(np.asarray, ost.nu),
            "codewords": [np.asarray(s.codebook.codewords_w) for s in vq]}
        self.state = (params, vq, ost)

    def _epoch(self, params, vq, ost, perm):
        import jax.numpy as jnp
        from repro.graph.batching import epoch_slices
        from repro.models.gnn import vq_train_epoch
        with common.span("host.batches"):
            ids, sm = epoch_slices(perm, self.b)
            ids_d = jnp.asarray(ids.astype(np.int32))
            sm_d = jnp.asarray(sm)
        with common.span("program.vq_train_epoch"):
            params, vq, ost, losses, _ = vq_train_epoch(
                params, vq, ost, self.plan, ids_d, sm_d, self.gd.x,
                self.labels, self.tm, self.deg, self.cfg, self.opt)
        with common.span("host.fetch_losses"):
            losses = np.asarray(losses)
        return params, vq, ost, losses

    # -- window -------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        params, vq, ost = self.state
        epochs, ends = 0, []
        t0 = time.perf_counter()
        while True:
            with common.span("host.permutation"):
                perm = self.rng.permutation(self.n)
            params, vq, ost, losses = self._epoch(params, vq, ost, perm)
            epochs += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds:
                break
        per = np.diff([0.0] + ends)
        print(f"train: {epochs} epochs, seconds each min {per.min():.4f} "
              f"median {np.median(per):.4f} max {per.max():.4f}",
              file=sys.stderr)
        self.state = (params, vq, ost)
        self.epochs, self.elapsed = epochs, elapsed
        return {"train_nodes_per_s": epochs * self.n / elapsed}

    def dims(self) -> list:
        return weights.layer_dims(self.model)

    def counters(self) -> dict:
        return {"epochs": self.epochs, "window_s": self.elapsed,
                "rows": self.epochs * self.n,
                "steps": self.epochs * -(-self.n // self.b)}

    def attempted(self) -> tuple[int, int]:
        return self.epochs, 0

    # -- correctness --------------------------------------------------------
    def release(self) -> None:
        self.state = None
        self.plan = None

    def reference(self, precision: str) -> dict:
        """The reference's first epoch, from the seed's weights."""
        import jax
        import jax.numpy as jnp
        vqgnn = common.reference(self.cell)
        params, states = weights.make(self.model, self.n,
                                      common.weight_key(self.seed))
        cw0 = [np.asarray(s["codewords_w"]) for s in states]
        p0 = jax.tree_util.tree_map(np.asarray, params)
        t = vqgnn.tables(self.gd.src, self.gd.dst, self.n)
        ids, sm = common.epoch_slices(self.first_perm, self.b)
        p1, st1, v1, losses = vqgnn.train_epoch(
            params, states, jnp.asarray(ids.astype(np.int32)),
            jnp.asarray(sm), self.gd.x, self.labels, self.tm, t,
            self.model, precision)
        return {"losses": np.asarray(losses), "p0": p0, "cw0": cw0,
                "params": jax.tree_util.tree_map(np.asarray, p1),
                "nu": jax.tree_util.tree_map(np.asarray, v1),
                "codewords": [np.asarray(s["codewords_w"]) for s in st1]}

    @staticmethod
    def compare(got: dict, ref: dict) -> dict:
        """The numbers compared: worst step's relative loss gap, and the
        worst leaf's norm gap of the accumulated squared gradients, of the
        parameters' change and of each layer's codeword change."""
        import jax
        loss_gap = float(np.max(np.abs(got["losses"] - ref["losses"])
                                / np.abs(ref["losses"])))
        g_ref = common.leaf_norms(jax.tree_util.tree_map(np.sqrt, ref["nu"]))
        g_got = common.leaf_norms(jax.tree_util.tree_map(np.sqrt, got["nu"]))
        # leaves whose gradient is nought to rounding move by round-off
        # alone under RMSprop: a rule on the reference's gradient
        counted = g_ref >= 1e-3 * np.median(g_ref)

        def change(p1, p0):
            return jax.tree_util.tree_map(
                lambda a, b: np.asarray(a, np.float64) - b, p1, p0)
        d_ref = common.leaf_norms(change(ref["params"], ref["p0"]))
        d_got = common.leaf_norms(change(got["params"], ref["p0"]))
        c_ref = np.array([np.linalg.norm(np.asarray(a, np.float64) - b)
                          for a, b in zip(ref["codewords"], ref["cw0"])])
        c_got = np.array([np.linalg.norm(np.asarray(a, np.float64) - b)
                          for a, b in zip(got["codewords"], ref["cw0"])])
        return {"loss_gap": loss_gap,
                "grad_gap": common.norm_gap(g_got, g_ref, counted),
                "update_gap": common.norm_gap(d_got, d_ref, counted),
                "codebook_gap": common.norm_gap(c_got, c_ref)}

    def readings(self, precision: str) -> dict:
        return self.compare(self.first, self.reference(precision))

    def extra(self) -> dict:
        return {"first_epoch_losses": self.first["losses"].tolist()}
