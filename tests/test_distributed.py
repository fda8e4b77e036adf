"""Distribution-layer tests: int8 compression and the error-feedback
gradient all-reduce (``distributed/collectives.py``)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.collectives import (compressed_grad_allreduce,
                                           dequantize_int8, quantize_int8)


def test_quantize_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).normal(0, 3, (64, 32)),
                    jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x).max()
    assert float(err) <= float(s) * 1.01     # within one quantization step


def test_compressed_allreduce_error_feedback():
    """Error feedback: the residual carries exactly what quantization lost,
    so the two-step sum converges to the true sum."""
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (128,)),
                    jnp.float32)

    def one_dev(xx, res):
        # psum over a single-device axis == identity; tests the plumbing
        return compressed_grad_allreduce({"g": xx}, "i", res)

    out, res = jax.vmap(lambda xx: one_dev(xx, None), axis_name="i")(
        x[None])
    recon1 = out["g"][0]
    # second step with the residual: cumulative sum error shrinks
    out2, _ = jax.vmap(lambda xx, rr: compressed_grad_allreduce(
        {"g": xx}, "i", {"g": rr}), axis_name="i")(x[None], res["g"][None])
    total_err = jnp.abs((recon1 + out2["g"][0]) - 2 * x).max()
    naive_err = 2 * jnp.abs(recon1 - x).max()
    assert float(total_err) <= float(naive_err) + 1e-6
