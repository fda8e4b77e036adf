"""Profiler trace of the window, reduced to device busy time, per-kernel
device time and the breakdown of the result line.

The trace is JAX's ``.xplane.pb``.  Device planes are ``/device:TPU:<i>``;
their ``XLA Ops`` line holds one event per operation that ran, named by its
HLO instruction's text (``%fusion.12 = f32[8,128]{...} fusion(...)``; a
Pallas kernel's custom call takes the name of its jitted wrapper,
``%spmm_ell_hbm_pallas.3`` or, under differentiation,
``%jvp_jit_spmm_ell_hbm_pallas__.42``).  The reduction keeps the
instruction's name and result type; an operand's name in the text is not
a match.  A loop is one
event that contains the events of its body.  The window is the host span
``bench.window``; host spans of the harness (``host.*``, ``program.*``)
say what the host was doing while the device sat idle.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from typing import NamedTuple

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("host.", "program.")


class Op(NamedTuple):
    name: str
    start: int      # ns
    dur: int        # ns


def union_ns(intervals: list[tuple[int, int]]) -> int:
    """Total length of the union of [start, end) intervals."""
    if not intervals:
        return 0
    iv = sorted(intervals)
    total, cur_s, cur_e = 0, iv[0][0], iv[0][1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + cur_e - cur_s


def gaps_ns(intervals: list[tuple[int, int]], lo: int, hi: int
            ) -> list[tuple[int, int]]:
    """The stretches of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def hlo_name(text: str) -> tuple[str, str, str]:
    """(name, result type, opcode) of an HLO instruction's text:
    ``%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(...)`` gives
    ``("fusion.3", "f32[8,128]", "fusion")``."""
    lhs, _, rhs = text.partition(" = ")
    m = _OPCODE.search(" " + rhs)
    return (lhs.strip().lstrip("%"), rhs.split("{")[0].split(" ")[0],
            m.group(1) if m else "")


CONTROL = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


class Summary:
    """What a traced window did on the device and on the host."""

    def __init__(self, ops: list[list[Op]], spans: list[Op],
                 window: tuple[int, int], results: dict | None = None,
                 control: set | None = None):
        """``results`` maps an operation's name to its result type;
        ``control`` names the loops and calls, whose events contain the
        events of their bodies."""
        self.lo, self.hi = window
        self.results = results or {}
        control = control or set()
        self.window_s = (self.hi - self.lo) / 1e9
        self.ops = [[o for o in dev if o.start < self.hi
                     and o.start + o.dur > self.lo] for dev in ops]
        self.leaves = [[o for o in dev if o.name not in control]
                       for dev in self.ops]
        self.spans = [s for s in spans if s.start < self.hi
                      and s.start + s.dur > self.lo]
        busy = [union_ns([(max(o.start, self.lo),
                           min(o.start + o.dur, self.hi)) for o in dev])
                for dev in self.ops]
        self.busy_s = float(np.mean(busy)) / 1e9 if busy else 0.0

    def kernel(self, match) -> tuple[float, int]:
        """(summed device seconds, launches) of the innermost operations
        whose name ``match`` accepts, over the chips traced."""
        t, n = 0, 0
        for dev in self.leaves:
            for o in dev:
                if match(o.name):
                    t += o.dur
                    n += 1
        return t / 1e9, n

    def device_ops(self, top: int = 10) -> list:
        """The innermost operations that took most device time, each HLO
        instruction with its result type, averaged over the chips
        traced."""
        agg: dict[str, int] = {}
        for dev in self.leaves:
            for o in dev:
                k = o.name
                if self.results.get(o.name):
                    k += " " + self.results[o.name]
                agg[k] = agg.get(k, 0) + o.dur
        n = max(1, len(self.ops))
        best = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n / 1e9] for k, v in best]

    def idle_gaps(self, top: int = 10) -> list:
        """Idle device time, by the harness span the host was in at the
        middle of each gap (the latest-started span that covers it)."""
        sp = sorted(self.spans, key=lambda o: o.start) or \
            [Op("host.other", 0, 0)]
        starts = np.array([o.start for o in sp], np.int64)
        ends = np.array([o.start + o.dur for o in sp], np.int64)
        names = [o.name for o in sp] + ["host.other"]
        agg: dict[str, int] = {}
        for dev in self.ops:
            gaps = gaps_ns([(o.start, o.start + o.dur) for o in dev],
                           self.lo, self.hi)
            if not gaps:
                continue
            g = np.array(gaps, np.int64)
            mid = (g[:, 0] + g[:, 1]) // 2
            who = np.searchsorted(starts, mid, side="right") - 1
            inside = (who >= 0) & (ends[np.maximum(who, 0)] > mid)
            who = np.where(inside, who, len(sp))
            for w, d in zip(who, g[:, 1] - g[:, 0]):
                agg[names[w]] = agg.get(names[w], 0) + int(d)
        n = max(1, len(self.ops))
        best = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n / 1e9] for k, v in best]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(),
                "idle_gaps": self.idle_gaps()}


def read(path: str, n_devices: int) -> Summary:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: list[list[Op]] = []
    spans: list[Op] = []
    results: dict[str, str] = {}
    control: set[str] = set()
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            if int(plane.name[len("/device:TPU:"):]) >= n_devices:
                continue
            dev = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, result, opcode = hlo_name(e.name)
                    results[name] = result
                    if opcode in CONTROL:
                        control.add(name)
                    dev.append(Op(name, int(e.start_ns),
                                  int(e.duration_ns)))
            ops.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    elif e.name.startswith(HOST_PREFIXES):
                        spans.append(Op(e.name, int(e.start_ns),
                                        int(e.duration_ns)))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    return Summary(ops, spans, window, results, control)


class Tracer:
    """Runs the profiler around the window into a temporary directory
    (under TMPDIR), reduces the trace and removes it."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self, devices) -> Summary:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if not files:
                raise FileNotFoundError(f"no trace under {self.dir}")
            return read(files[0], len(devices))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
