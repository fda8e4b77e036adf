"""Distribution-layer tests: sharding rules, compressed collectives, and a
small-mesh dry-run executed in a subprocess (8 virtual devices -- the same
code path as the 512-device production dry-run)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, get_smoke
from repro.distributed.collectives import (compressed_grad_allreduce,
                                           dequantize_int8, quantize_int8)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


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


@pytest.mark.parametrize("arch", ["granite-3-8b", "qwen3-moe-30b-a3b",
                                  "llama3.2-3b", "whisper-tiny"])
def test_strategy_selection(arch):
    from repro.distributed.sharding import strategy_for
    # strategy choice is a pure function of the full config + mesh shape;
    # evaluate against a mock 16-way-model mesh via the production rules
    import repro.distributed.sharding as shd
    cfg = ARCHS[arch]

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    s = strategy_for(cfg, FakeMesh())
    expected = {"granite-3-8b": "tp_fsdp",
                "qwen3-moe-30b-a3b": "moe_ep_dp",
                "llama3.2-3b": "fsdp",
                "whisper-tiny": "replicate"}[arch]
    assert s == expected


def test_param_shardings_never_invalid():
    """Every leaf's spec must divide its dims on the production mesh --
    checked for all 10 archs without any device allocation."""
    import repro.distributed.sharding as shd
    from repro.models import lm

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    sizes = {"pod": 2, "data": 16, "model": 16}
    for arch, cfg in ARCHS.items():
        params = jax.eval_shape(
            lambda c=cfg: lm.init_lm(jax.random.PRNGKey(0), c))
        strategy = shd.strategy_for(cfg, FakeMesh())
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        for path, leaf in flat:
            pathstr = "".join(str(p) for p in path)
            spec = shd._spec_for_leaf(pathstr, tuple(leaf.shape), strategy,
                                      FakeMesh(), cfg)
            for dim, entry in zip(leaf.shape, tuple(spec)):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                n = int(np.prod([sizes[a] for a in axes]))
                assert dim % n == 0, (arch, pathstr, leaf.shape, spec)


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess():
    """End-to-end mini dry-run on 16 virtual devices (mesh 4x4) -- the same
    lower+compile path as the 512-chip run, in a fresh process so the
    XLA_FLAGS device-count override is safe."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp
from repro.configs.registry import get_smoke
from repro.distributed import sharding as shd
from repro.models import lm
from repro.train.loop import TrainState, make_train_step
from repro.train.optimizer import adam

mesh = jax.make_mesh((4, 4), ("data", "model"))
cfg = get_smoke("granite-3-8b")
opt = adam(1e-3)
params = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
opt_state = jax.eval_shape(opt.init, params)
state = TrainState(params, opt_state, jax.ShapeDtypeStruct((), jnp.int32))
strategy = "tp_fsdp"
state_sh = TrainState(
    params=shd.param_shardings(params, cfg, mesh, strategy),
    opt=type(opt_state)(step=shd.replicated(mesh),
                        mu=shd.param_shardings(opt_state.mu, cfg, mesh,
                                               strategy),
                        nu=shd.param_shardings(opt_state.nu, cfg, mesh,
                                               strategy)),
    step=shd.replicated(mesh))
step = make_train_step(cfg, opt, accum=2)
tok = jax.ShapeDtypeStruct((8, 33), jnp.int32)
with mesh:
    fn = jax.jit(step, in_shardings=(state_sh, None),
                 out_shardings=(state_sh, shd.replicated(mesh)))
    compiled = fn.lower(state, tok).compile()
    ca = compiled.cost_analysis()
    print("COMPILED_OK", ca["flops"] > 0)
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert "COMPILED_OK True" in out.stdout, out.stderr[-2000:]
