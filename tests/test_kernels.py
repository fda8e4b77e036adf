"""Per-kernel validation: shape/dtype sweeps vs the ref.py jnp oracles,
executed in interpret mode (the sanctioned CPU path for Pallas TPU kernels).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

try:  # property tests are optional: skip (not error) without hypothesis
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ref
from repro.kernels.spmm_ell import spmm_ell_pallas
from repro.kernels.vq_assign import vq_assign_pallas


# ---------------------------------------------------------------------------
# vq_assign
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,f", [(1, 1, 1), (7, 3, 5), (64, 16, 4),
                                   (130, 33, 12), (256, 512, 128),
                                   (100, 1024, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vq_assign_sweep(b, k, f, dtype):
    kx, kc = jax.random.split(jax.random.PRNGKey(b * 131 + k))
    x = jax.random.normal(kx, (b, f), dtype)
    c = jax.random.normal(kc, (k, f), dtype)
    got = vq_assign_pallas(x, c, interpret=True)
    want = ref.vq_assign(x, c)
    # ties can legitimately differ: accept either when distances are equal
    x32, c32 = x.astype(jnp.float32), c.astype(jnp.float32)
    d = ((x32[:, None] - c32[None]) ** 2).sum(-1)
    d_got = jnp.take_along_axis(d, got[:, None].astype(jnp.int32), 1)[:, 0]
    d_want = jnp.take_along_axis(d, want[:, None].astype(jnp.int32), 1)[:, 0]
    assert_allclose(np.asarray(d_got), np.asarray(d_want), rtol=1e-5,
                    atol=1e-5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(b=st.integers(1, 40), k=st.integers(1, 40), f=st.integers(1, 24))
    def test_vq_assign_hypothesis(b, k, f):
        kx, kc = jax.random.split(jax.random.PRNGKey(b * 7919 + k * 31 + f))
        x = jax.random.normal(kx, (b, f))
        c = jax.random.normal(kc, (k, f))
        got = vq_assign_pallas(x, c, interpret=True)
        assert got.shape == (b,)
        assert int(got.min()) >= 0 and int(got.max()) < k
else:
    @pytest.mark.skip(reason="hypothesis not installed (requirements-dev.txt)")
    def test_vq_assign_hypothesis():
        pass


# ---------------------------------------------------------------------------
# spmm_ell
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,deg,n,f", [(1, 1, 1, 1), (8, 4, 16, 8),
                                       (33, 7, 50, 12), (128, 32, 300, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_ell_sweep(b, deg, n, f, dtype):
    key = jax.random.PRNGKey(b + deg * 100)
    k1, k2, k3 = jax.random.split(key, 3)
    idx = jax.random.randint(k1, (b, deg), 0, n)
    val = jax.random.normal(k2, (b, deg), jnp.float32)
    x = jax.random.normal(k3, (n, f), dtype)
    got = spmm_ell_pallas(idx, val, x, interpret=True)
    want = ref.spmm_ell(idx, val, x)
    assert_allclose(np.asarray(got), np.asarray(want),
                    rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                    atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_spmm_ell_padding_zero_vals():
    idx = jnp.array([[5, 0], [2, 1]], jnp.int32)
    val = jnp.array([[1.0, 0.0], [0.5, 0.0]])   # second slot is padding
    x = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    got = spmm_ell_pallas(idx, val, x, interpret=True)
    want = jnp.stack([x[5], 0.5 * x[2]])
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
