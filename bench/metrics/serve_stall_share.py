"""Share of the traced window lost to stalled serve steps.  The program's
spans tile each ``GNNServer.step`` on the host thread that runs it: the id
put (``program.serve.put``), the enqueue of the jitted step
(``program.serve.dispatch``), and the wait for and copy of its rows
(``program.serve.fetch``).  A step runs from the start of its put to the
end of its fetch; every step longer than twice the median step adds its
excess over the median.  Only the host's clock is read.  A program without
the spans, or fewer than 100 steps, reads nothing: the median would not be
a steady step."""

import numpy as np

PUT = "program.serve.put"
DISPATCH = "program.serve.dispatch"
FETCH = "program.serve.fetch"
MIN_STEPS = 100


def steps(t) -> list[tuple[int, int]]:
    """(start, end) of each step wholly inside the window: from the start
    of a put span to the end of the fetch span after its dispatch."""
    ev = sorted((s.start, s.start + s.dur, s.name) for s in t.spans
                if s.name in (PUT, DISPATCH, FETCH))
    return [(a[0], c[1]) for a, b, c in zip(ev, ev[1:], ev[2:])
            if (a[2], b[2], c[2]) == (PUT, DISPATCH, FETCH)
            and a[0] >= t.lo and c[1] <= t.hi]


def read(ctx):
    t = ctx["trace"]
    if t is None or t.window_s <= 0:
        return None
    st = steps(t)
    if len(st) < MIN_STEPS:
        return None
    d = np.array([e - s for s, e in st], np.int64)
    med = float(np.median(d))
    excess = float(np.sum(d[d > 2 * med] - med))
    return 100.0 * excess / 1e9 / t.window_s
