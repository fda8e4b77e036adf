"""Kernel and step operation counts against hand-worked values, and the
roofline readers on a made-up trace."""
import os

import pytest

from bench.harness import cell as cellmod, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = cellmod.peaks(ROOT, "TPU v5 lite")


def kernel(name):
    return cellmod.kernel_count(ROOT, name)


def test_spmm_hbm_count():
    # 2 rows x 3 slots x 4 columns; ids+values 2*3*8, source 5*4*4, out 2*4*4
    assert kernel("spmm_hbm").count(2, 3, 4, 5) == (48.0, 160.0)


def test_context_count():
    # 2 branches of 4 columns: ops 2*2*3*8; bytes: slots 48, assignment
    # 2*10*4, codewords 2*6*4*4, output 2*8*4
    assert kernel("context").count(2, 3, 2, 4, 10, 6) == (96.0, 384.0)


def test_train_step_count():
    dims = [(4, 4), (4, 2)]
    # layer 0: W 2*2*10*4*4, SpMM+context 2*2*10*3*4, VQ 2*10*5*8
    # layer 1: W 3*2*10*4*2, SpMM+context 3*2*10*3*4, Eq. 7 2*10*3*2 +
    # 2*10*2*4, VQ 2*10*5*6
    assert kernel("train_step").count("gcn", dims, 10, 3, 5) == 4000.0
    assert kernel("train_step").count("sage", dims, 10, 3, 5) == 5120.0


class Drv:
    model = {"backbone": "gcn", "k": 1024, "f_prod": 4}
    b, width, n = 42336, 21, 169343

    def dims(self):
        return [(128, 128), (128, 128), (128, 40)]


def summary(ops):
    return tracing.Summary([ops], [], (0, 10 ** 10))


def least(count, *shape):
    ops, nbytes = count(*shape)
    return max(ops / PEAKS["flops_bf16"], nbytes / PEAKS["hbm_bytes_per_s"])


def ctx(trace, steps):
    return {"trace": trace, "run": Drv(), "counters": {"steps": steps},
            "peaks": PEAKS, "kernel": kernel}


def test_spmm_roofline_is_100_at_the_least_time():
    d = Drv()
    t = [least(kernel("spmm_hbm").count, d.b, d.width, fi, d.b)
         for fi, _ in d.dims()]
    ops = [tracing.Op(f"jvp_jit_spmm_ell_hbm_pallas__.{i}", i * 10 ** 8,
                      int(round(x * 1e9))) for i, x in enumerate(t * 2)]
    reader = cellmod.metric_reader(ROOT, "roofline.spmm_hbm.train")
    assert reader.read(ctx(summary(ops), 2)) == pytest.approx(100, rel=1e-3)
    assert reader.read(ctx(summary(ops[:-1]), 2)) is None


def test_context_roofline_over_loop_and_fused_launches():
    d = Drv()
    reader = cellmod.metric_reader(ROOT, "roofline.context.train")
    # GCN at arxiv widths: branches 32, 32, 8; forward 3 terms, Eq. 7 2
    # at arxiv widths: layers 0 and 1 past the fused kernel's budget run
    # per branch (32 launches a term), the 8-branch head fused
    mixed = [tracing.Op(f"spmm_ell_pallas.{i}", i * 10 ** 6, 1000)
             for i in range(32 * 3)] + \
        [tracing.Op(f"context_ell_pallas.{i}", 10 ** 9 + i * 10 ** 6, 1000)
         for i in range(2)]
    fused = [tracing.Op(f"context_ell_pallas.{i}", i * 10 ** 6, 1000)
             for i in range(5)]
    hbm = [tracing.Op("spmm_ell_hbm_pallas.1", 2 * 10 ** 9, 10 ** 6)]
    a = reader.read(ctx(summary(mixed + hbm), 1))
    b = reader.read(ctx(summary(fused), 1))
    assert a is not None and b is not None
    assert a == pytest.approx(b * 5 / len(mixed))
    assert reader.read(ctx(summary(fused[:-1]), 1)) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        cellmod.peaks(ROOT, "TPU v99")
