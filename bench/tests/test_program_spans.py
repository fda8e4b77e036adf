"""The reader of the program's spans inside ``GNNServer.step``: the steps
they tile and the stall share, and how the harness's idle breakdown names
them."""
import os

import pytest

from bench.harness import cell as cellmod, tracing
from bench.harness.tracing import Op
from bench.tests import tiny

STALL = "serve_stall_share"


def reader():
    return cellmod.metric_reader(tiny.ROOT, STALL)


def read(summary):
    return reader().read({"trace": summary})


def one_step(t0, dur=100, gap=10):
    """Spans of one step from ``t0``: put, dispatch, then fetch, which
    lasts ``dur``; ``gap`` of the harness's own work follows."""
    r = reader()
    return [Op("program.serve_step", t0, 30 + dur),
            Op(r.PUT, t0, 10), Op(r.DISPATCH, t0 + 10, 20),
            Op(r.FETCH, t0 + 30, dur),
            Op("host.complete", t0 + 30 + dur, gap)]


def steady(n, durs=()):
    """``n`` steps of 130 (put 10, dispatch 20, fetch 100) and then one per
    entry of ``durs``, each followed by 10 of the harness's work."""
    spans, t = [], 0
    for d in [100] * n + list(durs):
        spans += one_step(t, d)
        t += 30 + d + 10
    return tracing.Summary([], spans, (0, t)), t


def test_no_span_or_no_trace_reads_nothing():
    harness_only = tracing.Summary([[Op("fusion.1", 0, 100)]],
                                   [Op("program.serve_step", 0, 500)],
                                   (0, 1000))
    assert read(harness_only) is None
    assert read(None) is None
    assert read(tracing.Summary([], one_step(0), (0, 0))) is None


def test_steps_are_put_dispatch_fetch_inside_the_window():
    r = reader()
    spans = one_step(-5) + one_step(200) + [Op(r.PUT, 400, 10)]
    s = tracing.Summary([], spans, (0, 1000))
    # the first step starts before the window, the last has no fetch
    assert r.steps(s) == [(200, 330)]


def test_steps_skip_a_broken_sequence():
    r = reader()
    # a put with no dispatch after it, then a whole step; a dispatch and
    # fetch with no put before them
    spans = [Op(r.PUT, 0, 10), Op(r.PUT, 20, 10), Op(r.DISPATCH, 30, 10),
             Op(r.FETCH, 40, 50), Op(r.DISPATCH, 100, 10),
             Op(r.FETCH, 110, 50)]
    s = tracing.Summary([], spans, (0, 1000))
    assert r.steps(s) == [(20, 90)]


def test_stall_share_sums_the_excess_of_long_steps():
    s, t = steady(110, [1000, 250, 190])           # 2 steps over 2 x 130
    # the median step is 130 (30 + fetch 100); excess (1030 - 130) and
    # (280 - 130); the 220 step is under twice the median
    assert read(s) == pytest.approx(100.0 * (900 + 150) / t)
    assert read(steady(100)[0]) == 0.0


def test_a_step_of_twice_the_median_is_no_stall():
    s, t = steady(110, [230, 231])                 # 260 and 261 against 130
    assert read(s) == pytest.approx(100.0 * (261 - 130) / t)


def test_stall_share_needs_100_steps():
    s, _ = steady(98, [5000])
    assert len(reader().steps(s)) == 99
    assert read(s) is None


def test_idle_breakdown_names_the_program_spans():
    r = reader()
    # the device works [0, 100) and [300, 1000); its gap [100, 300) has its
    # midpoint inside put, so the breakdown gives put the whole gap
    spans = [Op(r.FETCH, 50, 100), Op("host.complete", 150, 20),
             Op(r.PUT, 170, 60), Op(r.DISPATCH, 230, 120)]
    ops = [Op("fusion.1", 0, 100), Op("fusion.2", 300, 700)]
    s = tracing.Summary([ops], spans, (0, 1000))
    assert dict(s.idle_gaps()) == {r.PUT: pytest.approx(200e-9)}


def test_recorded_chip_trace_has_no_program_spans():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb")
    s = tracing.read(path, 1)
    assert s.ops and any(s.ops)
    assert reader().steps(s) == []
    assert read(s) is None
