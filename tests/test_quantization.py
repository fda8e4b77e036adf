"""int8 per-channel tensor quantization (``quantize_tensor``): accuracy
contracts."""
import jax
import jax.numpy as jnp

from repro.distributed.quantization import quantize_tensor


def test_tensor_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    t = quantize_tensor(w)
    err = jnp.abs(t.q.astype(jnp.float32) * t.scale - w)
    assert float(err.max()) <= float(t.scale.max()) * 0.51


def test_matmul_relative_error_small():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (16, 128))
    w = jax.random.normal(k2, (128, 64))
    t = quantize_tensor(w)
    y = x @ w
    yq = x @ (t.q.astype(jnp.float32) * t.scale)
    rel = float(jnp.abs(y - yq).mean() / jnp.abs(y).mean())
    assert rel < 0.01, rel
