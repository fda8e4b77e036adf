"""The control: the reference computed one precision step below the
configuration's ("high", three bfloat16 passes, for float32 at "highest"),
put in the program's place, fails the comparison; the program passes it.
At the cells' own widths on a graph of 8000 nodes, which a CPU test run
holds; on the chip the same comparison runs at the cells' full size
(``bench/calibrate.py readings --control high``)."""
import pytest

from bench import run
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")), n=8000,
                          width=128, k=1024)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_control_fails_and_program_passes(root, cell):
    ok = run.run_cell(cell, 5, 0.5, False, root=root, require_tpu=False)
    assert ok["correct"], ok["checks"]
    ctl = run.run_cell(cell, 5, 0.5, False, root=root, require_tpu=False,
                       precision="high")
    assert not ctl["correct"], ctl["checks"]
