"""Kernel-path custom VJPs and the scalar-indexed kernel forms vs the oracles.

A ``pallas_call`` has no transpose rule, so ``ops.spmm_ell`` and
``ops.context_ell`` carry custom VJPs on the kernel path whose backward is
the oracle's own VJP.  The cotangents here must equal ``jax.vjp`` of
``ref.spmm_ell`` / ``ref.context_ell`` for every differentiable operand.

The forward sweeps hit what the scalar-indexed kernel forms add: padding
slots skipped by a scalar branch, rows and sources that tile neither the
row tile nor the one-hot source block, widths that are not a multiple of
128 (the HBM row DMAs pad them), codeword widths below the 8-row sublane
tile (``f_blk`` 3 and 5) or past one lane row (130), and argmin ties.  Kernels run in
interpret mode on the CPU.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.context_ell import context_ell_pallas
from repro.kernels.spmm_ell import spmm_ell_pallas
from repro.kernels.spmm_ell_hbm import spmm_ell_hbm_pallas
from repro.kernels.vq_assign import vq_assign_pallas
from repro.kernels.vq_update import vq_assign_update_pallas


@pytest.fixture
def kernels_on(monkeypatch):
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    yield
    ops.configure_spmm_dispatch(reset=True)
    ops.configure_context_dispatch(reset=True)


def _ell(key, b, deg, n, live=0.75):
    """ELL operands with about ``1 - live`` of the slots padding (val 0)."""
    k1, k2, k3 = jax.random.split(key, 3)
    ids = jax.random.randint(k1, (b, deg), 0, n).astype(jnp.int32)
    val = jax.random.normal(k2, (b, deg), jnp.float32)
    keep = jax.random.uniform(k3, (b, deg)) < live
    return ids, jnp.where(keep, val, 0.0)


def _close(got, want, tol=1e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("variant", ["resident", "hbm"])
@pytest.mark.parametrize("b,deg,n,f", [(1, 1, 1, 1), (45, 7, 29, 3),
                                       (130, 16, 700, 128)])
def test_spmm_custom_vjp_matches_oracle(kernels_on, variant, b, deg, n, f):
    ops.configure_spmm_dispatch(variant=variant)
    ids, val = _ell(jax.random.PRNGKey(b + deg), b, deg, n)
    x = jax.random.normal(jax.random.PRNGKey(f), (n, f), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(7), (b, f), jnp.float32)

    out, vjp = jax.vjp(lambda v, s: ops.spmm_ell(ids, v, s), val, x)
    want, vjp_ref = jax.vjp(lambda v, s: ref.spmm_ell(ids, v, s), val, x)
    _close(out, want)
    for got, exp in zip(vjp(ct), vjp_ref(ct)):
        _close(got, exp)


def test_spmm_custom_vjp_under_jit_grad(kernels_on):
    """The training-step form: jit(grad) through the kernel path."""
    ids, val = _ell(jax.random.PRNGKey(3), 37, 5, 60)
    x = jax.random.normal(jax.random.PRNGKey(4), (60, 8), jnp.float32)

    def loss(spmm, v, s):
        return jnp.sum(jnp.tanh(spmm(ids, v, s)) ** 2)

    got = jax.jit(jax.grad(lambda v, s: loss(ops.spmm_ell, v, s),
                           argnums=(0, 1)))(val, x)
    want = jax.grad(lambda v, s: loss(ref.spmm_ell, v, s),
                    argnums=(0, 1))(val, x)
    for g, w in zip(got, want):
        _close(g, w)


@pytest.mark.parametrize("variant", ["fused", "loop"])
@pytest.mark.parametrize("with_w", [False, True])
def test_context_custom_vjp_matches_oracle(kernels_on, variant, with_w):
    """Cotangents for the edge values (GAT's attention weights carry one),
    the codewords and the fused W^T."""
    ops.configure_context_dispatch(variant=variant)
    b, deg, n, nb, k, f_blk, f_out = 21, 6, 40, 3, 7, 5, 9
    ids, val = _ell(jax.random.PRNGKey(11), b, deg, n)
    assign = jax.random.randint(jax.random.PRNGKey(12), (nb, n), 0, k)
    cw = jax.random.normal(jax.random.PRNGKey(13), (nb, k, f_blk))
    w_t = jax.random.normal(jax.random.PRNGKey(14), (nb * f_blk, f_out))
    ct = jax.random.normal(jax.random.PRNGKey(15),
                           (b, f_out if with_w else nb * f_blk))

    def run(fn, v, c, w):
        return fn(ids, v, assign.astype(jnp.int32), c, w if with_w else None)

    out, vjp = jax.vjp(lambda v, c, w: run(ops.context_ell, v, c, w),
                       val, cw, w_t)
    want, vjp_ref = jax.vjp(lambda v, c, w: run(ref.context_ell, v, c, w),
                            val, cw, w_t)
    _close(out, want, 1e-4)
    for got, exp in zip(vjp(ct), vjp_ref(ct)):
        _close(got, exp, 1e-4)


@pytest.mark.parametrize("b,deg,n,f,bb", [(45, 7, 29, 3, 8),
                                          (200, 12, 333, 130, 64)])
def test_spmm_resident_ragged(b, deg, n, f, bb):
    ids, val = _ell(jax.random.PRNGKey(b), b, deg, n, live=0.5)
    x = jax.random.normal(jax.random.PRNGKey(n), (n, f), jnp.float32)
    _close(spmm_ell_pallas(ids, val, x, bb=bb, interpret=True),
           ref.spmm_ell(ids, val, x))


@pytest.mark.parametrize("b,deg,n,f,bb", [
    (45, 7, 29, 3, 16),           # a narrow, lane-padded row
    (70, 9, 1000, 128, 32),       # many row tiles
])
def test_spmm_hbm_ragged(b, deg, n, f, bb):
    ids, val = _ell(jax.random.PRNGKey(b), b, deg, n, live=0.5)
    x = jax.random.normal(jax.random.PRNGKey(n), (n, f), jnp.float32)
    _close(spmm_ell_hbm_pallas(ids, val, x, bb=bb, interpret=True),
           ref.spmm_ell(ids, val, x))


@pytest.mark.parametrize("nb,k,f_blk", [
    (3, 7, 3),       # f_blk below the 8-row sublane tile
    (5, 9, 5),       # five branches of padded 5-row codewords
    (2, 4, 130),     # wider than one lane row
])
@pytest.mark.parametrize("with_w", [False, True])
def test_context_ell_ragged(nb, k, f_blk, with_w):
    b, deg, n = 37, 6, 50
    ids, val = _ell(jax.random.PRNGKey(nb * k), b, deg, n, live=0.6)
    assign = jax.random.randint(jax.random.PRNGKey(1), (nb, n), 0, k)
    cw = jax.random.normal(jax.random.PRNGKey(2), (nb, k, f_blk))
    w_t = jax.random.normal(jax.random.PRNGKey(3), (nb * f_blk, 6)) \
        if with_w else None
    _close(context_ell_pallas(ids, val, assign, cw, w_t=w_t, bb=16,
                              interpret=True),
           ref.context_ell(ids, val, assign, cw, w_t), 1e-4)


def test_vq_argmin_ties_pick_the_first_codeword():
    """Duplicated codewords tie exactly; the kernels' masked-iota argmin
    keeps jnp.argmin's rule (smallest id), across k-tiles too."""
    base = jax.random.normal(jax.random.PRNGKey(0), (6, 8))
    cw = jnp.concatenate([base, base, base], axis=0)       # ids i, i+6, i+12
    x = base[jnp.array([0, 3, 5, 1, 2, 4, 0, 5, 3])] + 1e-3
    want = ref.vq_assign(x, cw)
    np.testing.assert_array_equal(np.asarray(want) < 6, True)
    got = vq_assign_pallas(x, cw, bb=8, kb=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    idx, _, counts, _ = vq_assign_update_pallas(x, cw, bb=8, kb=8,
                                                interpret=True)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    _close(counts, ref.vq_assign_update(x, cw)[2])
