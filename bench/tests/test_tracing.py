"""The reduction from a profiler trace to busy time, kernel time and the
breakdown."""
import pytest

from bench.harness import tracing
from bench.harness.tracing import Op


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (39, 41), (50, 55)]
    assert tracing.union_ns(iv) == 20 + 11 + 5
    assert tracing.gaps_ns(iv, 0, 60) == [(20, 30), (41, 50), (55, 60)]
    assert tracing.gaps_ns(iv, 25, 45) == [(25, 30), (41, 45)]
    assert tracing.union_ns([]) == 0


def test_summary_busy_kernels_and_idle_attribution():
    ops = [Op("fusion.1", 100, 100), Op("while.4", 290, 220),
           Op("spmm_ell_hbm_pallas.7", 300, 200),
           Op("fusion.2", 900, 50), Op("copy.3", 2000, 10)]
    spans = [Op("host.batches", 200, 100), Op("program.step", 500, 500)]
    s = tracing.Summary([ops], spans, (0, 1000), control={"while.4"})
    assert s.window_s == pytest.approx(1e-6)
    # the loop around the kernel is busy; copy.3 is past the window
    assert s.busy_s == pytest.approx(370e-9)
    assert s.kernel(lambda x: "spmm_ell_hbm_pallas" in x) == (
        pytest.approx(2e-7), 1)
    assert s.kernel(lambda x: "while" in x) == (0.0, 0)
    gaps = dict(s.idle_gaps())
    # idle: [0,100) host.other, [200,290) host.batches, [510,900) and
    # [950,1000) program.step
    assert gaps == {"program.step": pytest.approx(440e-9),
                    "host.other": pytest.approx(100e-9),
                    "host.batches": pytest.approx(90e-9)}
    top = s.device_ops()
    assert top[0] == ["spmm_ell_hbm_pallas.7", pytest.approx(2e-7)]
    assert {k for k, _ in top} == {"spmm_ell_hbm_pallas.7", "fusion.1",
                                   "fusion.2"}


def test_loops_are_not_kernels():
    ops = [Op("while.1", 0, 100), Op("a", 0, 40), Op("b", 39, 30),
           Op("c", 70, 30), Op("d", 200, 5)]
    s = tracing.Summary([ops], [], (0, 300), control={"while.1"})
    assert [o.name for o in s.leaves[0]] == ["a", "b", "c", "d"]
    assert s.busy_s == pytest.approx(105e-9)
    assert dict(s.idle_gaps()) == {"host.other": pytest.approx(195e-9)}


def test_hlo_name_and_result_type():
    assert tracing.hlo_name(
        "%fusion.437 = f32[32768]{0:T(1024)S(1)} fusion(s32[2709504]{0} "
        "%jvp_jit_spmm_ell_hbm_pallas__.42)") == ("fusion.437", "f32[32768]",
                                                  "fusion")
    assert tracing.hlo_name(
        "%while.31 = (s32[]{:T(128)}, f32[128]{0:T(128)}) while((s32[], "
        "f32[128]) %tuple.9), condition=%cond")[2] == "while"
    assert tracing.hlo_name("1") == ("1", "", "")


def test_recorded_chip_trace():
    """A trace recorded on a v5e (72 KB): three windowed rounds of a 2 ms
    host span, then the resident SpMM kernel and a small matmul."""
    import os
    from jax.profiler import ProfileData
    path = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb")
    s = tracing.read(path, 1)
    assert s.kernel(lambda x: "spmm_ell_pallas" in x)[1] == 3
    # the same busy time, summed naively from the device plane
    pd = ProfileData.from_file(path)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    evs = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
           for line in plane.lines if line.name == "XLA Ops"
           for e in line.events]
    lo, hi = s.lo, s.hi
    covered = set()
    for a, b in evs:
        covered.update(range(max(a, lo), min(b, hi)))
    assert s.busy_s == pytest.approx(len(covered) / 1e9)
    assert 0 < s.busy_s < s.window_s
    gaps = dict(s.idle_gaps())
    assert gaps["host.batches"] >= 3 * 2e-3
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    top = dict(s.device_ops())
    assert "spmm_ell_pallas.1 f32[128,256]" in top
