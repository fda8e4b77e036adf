"""Compile the main-path Pallas kernels and a training step for a TPU v5e.

Nothing here runs: each test lowers and compiles for a *described*
``v5e:2x2`` chip (``jax.experimental.topologies``), which raises what the
chip's compiler would raise -- a gather Mosaic cannot lower, a block past
the VMEM or SMEM budget -- at no chip time.  Shapes are the paper's widths
(``configs/vq_gnn_paper.paper_config(full_scale=True)``: hidden 128,
k = 1024, f_prod = 4, so 32 product-VQ branches of 4 feature dims; ELL
width D = 32) at the ogbn-arxiv node count and a ~n/4 batch.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.  Code that asks ``jax.default_backend()`` still sees the CPU here,
so the tests steer the dispatch themselves (``ops.interpret_mode`` and
``ops._use_pallas``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

N = 169343          # ogbn-arxiv nodes
B = 42336           # paper_batch_size: ceil(n / 4)
D = 32              # ELL width (synthetic_arxiv's degree cap)
K = 1024
NB, F_BLK = 32, 4   # hidden 128 / f_prod 4


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def sds(one_chip):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture
def kernels_on(monkeypatch):
    """Dispatch as on a TPU: compiled kernels, never the oracles."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)


def compile_text(fn, *args) -> str:
    """Compile ``fn`` for the described chip; the optimized HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def assert_kernels(text: str, n: int = 1) -> None:
    assert text.count("tpu_custom_call") >= n, "no Pallas kernel compiled"


@pytest.mark.parametrize("b,n_src", [(4096, 4096),   # serve micro-batch
                                     (B, K)])        # a codeword table
def test_spmm_ell_resident_compiles(sds, b, n_src):
    from repro.kernels.spmm_ell import spmm_ell_pallas
    f = 128 if n_src != K else F_BLK
    assert_kernels(compile_text(
        lambda i, v, x: spmm_ell_pallas(i, v, x), sds((b, D), jnp.int32),
        sds((b, D)), sds((n_src, f))))


@pytest.mark.parametrize("b,n_src", [(B, B),          # intra-batch source
                                     (N, N)])         # full-graph eval
def test_spmm_ell_hbm_compiles(sds, b, n_src):
    from repro.kernels.spmm_ell_hbm import spmm_ell_hbm_pallas
    assert_kernels(compile_text(
        lambda i, v, x: spmm_ell_hbm_pallas(i, v, x),
        sds((b, D), jnp.int32), sds((b, D)), sds((n_src, 128))))


def test_spmm_ell_quantized_sources_compile(sds):
    """int8 rows: kept in storage dtype in VMEM and widened per one-hot
    block (resident), or widened ahead of the row DMAs (HBM)."""
    from repro.kernels.spmm_ell import spmm_ell_pallas
    from repro.kernels.spmm_ell_hbm import spmm_ell_hbm_pallas
    assert_kernels(compile_text(
        lambda i, v, x, s: spmm_ell_pallas(i, v, x, x_scale=s),
        sds((B, D), jnp.int32), sds((B, D)), sds((K, F_BLK), jnp.int8),
        sds((1, F_BLK))))
    assert_kernels(compile_text(
        lambda i, v, x, s: spmm_ell_hbm_pallas(i, v, x, x_scale=s),
        sds((B, D), jnp.int32), sds((B, D)), sds((B, 128), jnp.int8),
        sds((1, 128))))


@pytest.mark.parametrize("b", [B, 4096])     # training/inference, serving
@pytest.mark.parametrize("nb,f_blk,f_out", [
    (NB, F_BLK, None),        # Eq. 6 forward, layers 0-1
    (NB, F_BLK, 128),         # Eq. 7 backward with the fused W^T epilogue
    (8, 16, None),            # layer 2's feature codewords (f_in 128)
    (8, 5, 128),              # layer 2's gradient codewords (f_grad 40)
    (1, 128, None),           # full-width codebook (transformer): one-hot
    (2, 301, None),           # Reddit's layer 0 (f 602): one-hot
])
def test_context_ell_compiles(sds, nb, f_blk, f_out, b):
    """The codeword lookup (lane gathers of 128-codeword table rows) at
    the paper's k, D and batch sizes, and the one-hot the kernel keeps for
    wide branches."""
    from repro.kernels.context_ell import context_ell_pallas
    args = [sds((b, D), jnp.int32), sds((b, D)), sds((nb, N), jnp.int32),
            sds((nb, K, f_blk))]
    if f_out is None:
        fn = lambda i, v, a, c: context_ell_pallas(i, v, a, c)  # noqa: E731
    else:
        args.append(sds((nb * f_blk, f_out)))
        fn = lambda i, v, a, c, w: context_ell_pallas(  # noqa: E731
            i, v, a, c, w_t=w)
    assert_kernels(compile_text(fn, *args))


def test_context_ell_quantized_compiles(sds):
    """The int8 tier's operands: int8 codewords with their scales and a
    uint8 assignment table (k = 256), with the fused W^T epilogue."""
    from repro.kernels.context_ell import context_ell_pallas
    assert_kernels(compile_text(
        lambda i, v, a, c, s, w: context_ell_pallas(i, v, a, c, cw_scale=s,
                                                    w_t=w),
        sds((B, D), jnp.int32), sds((B, D)), sds((NB, N), jnp.uint8),
        sds((NB, 256, F_BLK), jnp.int8), sds((NB, 1, F_BLK)),
        sds((NB * F_BLK, 128))))


def test_vq_assign_compiles(sds):
    from repro.kernels.vq_assign import vq_assign_pallas
    assert_kernels(compile_text(
        lambda x, c: vq_assign_pallas(x, c, want_min=True),
        sds((B, 2 * F_BLK)), sds((K, 2 * F_BLK))))


@pytest.mark.parametrize("k,emit", [(K, jnp.int32), (256, jnp.uint8)])
def test_vq_assign_update_compiles(sds, k, emit):
    """The codebook update's call shape: vmapped over the 32 branches."""
    from repro.kernels.vq_update import vq_assign_update_pallas
    assert_kernels(compile_text(
        jax.vmap(lambda x, c: vq_assign_update_pallas(x, c,
                                                      emit_dtype=emit)),
        sds((NB, B, 2 * F_BLK)), sds((NB, k, 2 * F_BLK))))


def test_training_step_grad_compiles(sds, kernels_on):
    """jax.grad of one Alg. 1 step at the paper's widths, kernels on: the
    SpMM and context custom VJPs stand in for the transpose rule a
    pallas_call lacks (a smaller graph keeps the compile short)."""
    from repro.core.codebook import CodebookConfig
    from repro.core.conv import MinibatchPack
    from repro.models.gnn import (GNNConfig, _vq_step_body, init_gnn,
                                  init_vq_states)
    from repro.train.optimizer import rmsprop

    n, b = 8192, 2048
    cfg = GNNConfig(backbone="gcn", f_in=128, hidden=128, n_out=40,
                    n_layers=3, codebook=CodebookConfig(k=K, f_prod=4))
    opt = rmsprop(3e-3)
    key = jax.random.PRNGKey(0)

    def place(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    params = place(jax.eval_shape(lambda k: init_gnn(k, cfg), key))
    vq = place(jax.eval_shape(lambda k: init_vq_states(k, cfg, n), key))
    ost = place(jax.eval_shape(opt.init, params))
    pack = MinibatchPack(
        batch_ids=sds((b,), jnp.int32), nbr_ids=sds((b, D), jnp.int32),
        nbr_mask=sds((b, D)), nbr_pos=sds((b, D), jnp.int32),
        rev_ids=sds((b, D), jnp.int32), rev_mask=sds((b, D)),
        rev_pos=sds((b, D), jnp.int32))

    def step(params, vq, ost, pack, x_b, labels_b, degrees, mask):
        return _vq_step_body(params, vq, ost, pack, x_b, labels_b, degrees,
                             cfg, opt, loss_mask=mask)

    text = compile_text(step, params, vq, ost, pack, sds((b, 128)),
                        sds((b,), jnp.int32), sds((n,)), sds((b,)))
    # per layer: intra SpMM + context forward (+ Eq. 7 backward), and the
    # fused assign+stats update
    assert_kernels(text, 3 * 3)
    # five context terms, each one fused kernel: three forward, and the
    # Eq. 7 backward of the two layers past the first
    assert _context_kernels(text) == 5


def _context_kernels(text: str) -> int:
    return sum("tpu_custom_call" in line and "context_ell_pallas" in line
               for line in text.splitlines())


def _paper_model(sds, n: int):
    """The paper's GCN (hidden 128, k 1024, f_prod 4, 40 classes) as
    shapes: config, params, VQ states and an epoch plan over ``n`` nodes."""
    from repro.core.codebook import CodebookConfig
    from repro.graph.batching import EpochPlan
    from repro.models.gnn import GNNConfig, init_gnn, init_vq_states

    cfg = GNNConfig(backbone="gcn", f_in=128, hidden=128, n_out=40,
                    n_layers=3, codebook=CodebookConfig(k=K, f_prod=4))
    key = jax.random.PRNGKey(0)

    def place(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    params = place(jax.eval_shape(lambda k: init_gnn(k, cfg), key))
    vq = place(jax.eval_shape(lambda k: init_vq_states(k, cfg, n), key))
    plan = EpochPlan(nbr_ids=sds((n, D), jnp.int32), nbr_mask=sds((n, D)),
                     rev_ids=sds((n, D), jnp.int32), rev_mask=sds((n, D)))
    return cfg, params, vq, plan


def test_context_terms_take_the_fused_kernel(sds, kernels_on):
    """At the paper's n, widths and batches the dispatch sends every
    context term to the fused kernel and none to the per-branch loop: the
    ``[32, n]`` assignment table (21.7 MB) is gathered in XLA and is not
    charged against the VMEM budget.  A training step traces three forward
    terms and three Eq. 7 terms (layer 0's is dead: its cotangent is the
    input features', and the compiled step drops it); an inference sweep
    and a serve step trace one term per layer."""
    from repro.analysis.trace_count import CONTEXT_TRACE_COUNT
    from repro.core.conv import MinibatchPack
    from repro.models.gnn import (_vq_infer_layer_body, _vq_step_body,
                                  vq_serve_batch)
    from repro.train.optimizer import rmsprop

    cfg, params, vq, plan = _paper_model(sds, N)
    opt = rmsprop(3e-3)
    ost = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(opt.init, params))
    i32 = jnp.int32
    pack = MinibatchPack(
        batch_ids=sds((B,), i32), nbr_ids=sds((B, D), i32),
        nbr_mask=sds((B, D)), nbr_pos=sds((B, D), i32),
        rev_ids=sds((B, D), i32), rev_mask=sds((B, D)),
        rev_pos=sds((B, D), i32))

    def traced(fn, *args) -> dict:
        before = CONTEXT_TRACE_COUNT.snapshot()
        jax.jit(fn).trace(*args)
        return CONTEXT_TRACE_COUNT.delta(before)

    step = traced(
        lambda p, v, o, pk, x, y, deg, m: _vq_step_body(
            p, v, o, pk, x, y, deg, cfg, opt, loss_mask=m),
        params, vq, ost, pack, sds((B, 128)), sds((B,), i32), sds((N,)),
        sds((B,)))
    assert step == {"context.fused": 6, "context.loop": 0}

    sweeps = {"context.fused": 0, "context.loop": 0}
    for layer, (fi, _) in enumerate(cfg.layer_dims()):
        got = traced(
            lambda p, v, pl_, perm, sm, acts, deg, _l=layer:
            _vq_infer_layer_body(p, v, pl_, perm, sm, acts, deg, cfg=cfg,
                                 layer=_l),
            params[layer], vq[layer], plan, sds((4, B), i32),
            sds((4, B)), sds((N, fi)), sds((N,)))
        for key in sweeps:
            sweeps[key] += got[key]
    assert sweeps == {"context.fused": 3, "context.loop": 0}

    serve = traced(
        lambda p, v, pl_, ids, x, deg: vq_serve_batch.__wrapped__(
            p, v, pl_, ids, x, deg, cfg),
        params, vq, plan, sds((4096,), i32), sds((N, 128)), sds((N,)))
    assert serve == {"context.fused": 3, "context.loop": 0}
