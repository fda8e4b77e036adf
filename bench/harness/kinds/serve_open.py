"""Open-loop serving traffic through ``launch.serve_gnn.GNNServer``.

Set-up builds the server from the seed's weights, refreshes every node's
codeword assignment (``GNNServer.refresh``), compiles the serve step and
draws the window's requests (``harness/traffic.py``).  The window is the
server's micro-batching loop: whenever requests are queued, the next step
takes up to ``slots`` of their node ids in arrival order (a large request
spans steps, small ones share a step, the rest of a step is padding) and
runs at once; when none is queued, the loop sleeps until the next arrival.
A request is done when the step holding its last id returns, and its
latency runs from the moment it was due.  Requests due in the window are
all served, past its close if need be.

The comparison takes the answers of a seeded sample of the requests, with
the longest, and the id vectors of the steps that served them, and lets the
reference refresh the assignments and recompute those steps.  It reads the
median row gap, which moves when every row is a little off, and the share
of rows off by more than ``ROW_OFF``, which moves when some rows are wrong.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench.harness import common, graphgen, traffic, weights

DRAIN_S = 60.0        # how long past the window's close answers are awaited
# a row gap above this is no rounding: float32 rows differ by under 2e-7
ROW_OFF = 1e-6


class Run:
    def __init__(self, cell, seed: int, seconds: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.config, self.mix = cell.config, cell.mix
        self.model = common.model_dict(cell.config)
        self.slots = int(self.mix["slots"])

    def setup(self) -> None:
        from repro.launch.serve_gnn import GNNServer
        self.gd = gd = graphgen.generate(self.config["graph"], self.seed)
        g = common.program_graph(gd)
        self.n = gd.n
        params, states = weights.make(self.model, gd.n,
                                      common.weight_key(self.seed))
        self.server = GNNServer(g, common.program_config(self.config),
                                params, weights.to_program(states),
                                self.slots)
        self.width = int(self.server.plan.nbr_ids.shape[1])
        self.server.refresh()
        self.server.warmup()
        self.req = traffic.open_loop(self.mix, gd.n, self.seconds, self.seed)
        self.picked = traffic.sample(self.req, int(self.mix["checked"]),
                                     self.seed)

    def window(self, seconds: float) -> dict:
        req, b = self.req, self.slots
        due, ends, ids = req.due, req.ends, req.ids
        starts = ends - req.sizes
        # slot positions of the checked requests, ascending
        chk = np.concatenate([np.arange(starts[i], ends[i])
                              for i in self.picked]) if len(self.picked) \
            else np.zeros(0, np.int64)
        got = np.full((len(chk), self.server.f_out), np.nan, np.float32)
        chk_step = np.full(len(chk), -1, np.int64)
        chk_row = np.zeros(len(chk), np.int64)
        step_ids: dict[int, np.ndarray] = {}
        total = int(ends[-1]) if len(ends) else 0
        done = np.full(len(due), np.nan)
        step_end, step_real, step_dur, late = [], [], [], []
        pos, steps = 0, 0
        t0 = time.perf_counter()
        while pos < total:
            now = time.perf_counter() - t0
            if now > seconds + DRAIN_S:
                break
            k = int(np.searchsorted(due, now, side="right"))
            avail = int(ends[k - 1]) if k else 0
            if avail <= pos:
                with common.span("host.idle"):
                    nxt = due[k]
                    while True:
                        wait = nxt - (time.perf_counter() - t0)
                        if wait <= 0:
                            break
                        time.sleep(wait if wait > 2e-3 else 0)
                late.append(time.perf_counter() - t0 - nxt)
                continue
            end = min(pos + b, avail)
            with common.span("host.batch"):
                step = np.zeros(b, np.int32)
                step[:end - pos] = ids[pos:end]
            ts = time.perf_counter()
            with common.span("program.serve_step"):
                out = self.server.step(step)
            t = time.perf_counter() - t0
            step_dur.append(t0 + t - ts)
            with common.span("host.complete"):
                lo = int(np.searchsorted(ends, pos, side="right"))
                hi = int(np.searchsorted(ends, end, side="right"))
                done[lo:hi] = t
                c0 = int(np.searchsorted(chk, pos))
                c1 = int(np.searchsorted(chk, end))
                if c1 > c0:
                    got[c0:c1] = out[chk[c0:c1] - pos]
                    chk_step[c0:c1] = steps
                    chk_row[c0:c1] = chk[c0:c1] - pos
                    step_ids[steps] = step
            step_end.append(t)
            step_real.append(end - pos)
            pos = end
            steps += 1
        self.elapsed = time.perf_counter() - t0
        step_end, step_real = np.array(step_end), np.array(step_real)
        lat = (done - due) * 1e3
        self.failed = int(np.sum(np.isnan(done)))
        lat = np.where(np.isnan(lat), np.inf, lat)
        in_window = step_end <= seconds
        self.steps, self.real = steps, int(step_real.sum())
        self.late = np.array(late) if late else np.zeros(1)
        self.got, self.chk_step, self.chk_row = got, chk_step, chk_row
        self.step_ids = step_ids
        self.latency_ms = lat
        self.step_dur = sd = np.array(step_dur) * 1e3
        med = float(np.median(sd)) if len(sd) else 0.0
        print(f"serve: {len(due)} requests, {steps} steps, fill "
              f"{self.real / max(1, steps * b):.4f}, p50 "
              f"{np.percentile(lat, 50):.3f} ms, p99 "
              f"{np.percentile(lat, 99):.3f} ms, step p50 {med:.3f} ms max "
              f"{sd.max() if len(sd) else 0.0:.3f} ms, "
              f"{int(np.sum(sd > 2 * med))} steps over twice the p50, "
              f"generator late p99 "
              f"{np.percentile(self.late, 99) * 1e3:.3f} ms max "
              f"{self.late.max() * 1e3:.3f} ms, drained "
              f"{self.elapsed - seconds:.3f} s past the close",
              file=sys.stderr, flush=True)
        return {"serve_p95_ms": float(np.percentile(lat, 95)),
                "serve_nodes_per_s": float(step_real[in_window].sum())
                / seconds}

    def dims(self) -> list:
        return weights.layer_dims(self.model)

    def counters(self) -> dict:
        return {"steps": self.steps, "real_slots": self.real,
                "slots": self.steps * self.slots, "window_s": self.elapsed,
                "requests": len(self.req.due)}

    def attempted(self) -> tuple[int, int]:
        return len(self.req.due), self.failed

    def release(self) -> None:
        self.server = None

    def reference(self, precision: str) -> np.ndarray:
        """The reference's answers for the checked slots."""
        import jax.numpy as jnp
        vqgnn = common.reference(self.cell)
        params, states = weights.make(self.model, self.n,
                                      common.weight_key(self.seed))
        t = vqgnn.tables(self.gd.src, self.gd.dst, self.n)
        ids, sm = common.epoch_slices(np.arange(self.n), self.slots)
        _, used = vqgnn.infer_sweep(
            params, states, jnp.asarray(ids.astype(np.int32)),
            jnp.asarray(sm), self.gd.x, t, self.model, precision,
            refresh=True)
        key = vqgnn._freeze(self.model)
        want = np.full(self.got.shape, np.nan, np.float32)
        for s, step in self.step_ids.items():
            rows = np.asarray(vqgnn.serve_step(
                params, used, jnp.asarray(step), self.gd.x, t, key,
                precision))
            sel = np.where(self.chk_step == s)[0]
            want[sel] = rows[self.chk_row[sel]]
        return want

    @staticmethod
    def compare(got: np.ndarray, want: np.ndarray) -> dict:
        if not len(got) or np.isnan(got).any():
            return {"row_gap_p50": float("nan"),
                    "row_off_share": float("nan")}
        gaps = common.row_gaps(got, want)
        return {"row_gap_p50": float(np.median(gaps)),
                "row_off_share": float(np.mean(gaps > ROW_OFF)),
                "row_gap_max": float(np.max(gaps))}

    def readings(self, precision: str) -> dict:
        return self.compare(self.got, self.reference(precision))
