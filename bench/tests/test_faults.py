"""A whole run on the CPU, past the harness's look for a chip, at a small
size: sound runs come out correct, and a run whose timed path is broken
underneath comes out not correct, once for each fault the cell can have."""
import numpy as np
import pytest

from bench import run
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")))


def go(root, cell, fault=None, seed=7):
    return run.run_cell(cell, seed, 1.0, False, root=root,
                        require_tpu=False, fault=fault)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_sound_run_is_correct(root, cell):
    r = go(root, cell)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


def _patch_epoch(monkeypatch, change):
    from repro.models import gnn
    real = gnn.vq_train_epoch

    def broken(params, vq, ost, plan, perm, smask, *rest):
        return change(real, params, vq, ost, plan, perm, smask, *rest)
    monkeypatch.setattr(gnn, "vq_train_epoch", broken)


def state_unchanged(real, params, vq, ost, plan, perm, smask, *rest):
    import jax
    import jax.numpy as jnp
    keep = jax.tree_util.tree_map(jnp.copy, (params, vq, ost))
    out = real(params, vq, ost, plan, perm, smask, *rest)
    return keep + tuple(out[3:])


def half_batch(real, params, vq, ost, plan, perm, smask, *rest):
    import jax.numpy as jnp
    half = smask.shape[1] // 2
    return real(params, vq, ost, plan, perm,
                smask.at[:, half:].set(0.0) if hasattr(smask, "at")
                else jnp.asarray(smask).at[:, half:].set(0.0), *rest)


@pytest.mark.parametrize("cell", ["gcn-arxiv.train", "sage-arxiv.train"])
@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_training_faults_are_caught(root, cell, fault, monkeypatch):
    _patch_epoch(monkeypatch, fault)
    r = go(root, cell)
    assert not r["correct"], r["checks"]


def test_altered_inference_answer_is_caught(root, monkeypatch):
    from repro.models import gnn
    real = gnn.vq_infer_epoch

    def broken(*a, **kw):
        acts, st = real(*a, **kw)
        return acts.at[0].add(1.0), st
    monkeypatch.setattr(gnn, "vq_infer_epoch", broken)
    assert not go(root, "gcn-arxiv.infer")["correct"]


def _every_row(rows):
    return np.roll(rows, 1, axis=0)


def _upper_half(rows):
    """Rows wrong in the upper half of the step's slots only: the median
    row stays right, so the share of rows off has to catch it."""
    out = np.array(rows)
    half = out.shape[0] // 2
    out[half:] = np.roll(out[half:], 1, axis=0)
    return out


@pytest.mark.parametrize("alter", [_every_row, _upper_half])
def test_altered_served_answer_is_caught(root, alter):
    def fault(drv):
        real = drv.server.step
        drv.server.step = lambda ids: alter(real(ids))
    assert not go(root, "gcn-arxiv.serve", fault)["correct"]
