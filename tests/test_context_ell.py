"""Fused VQ-context kernel family (kernels/context_ell.py) + the lazy
Eq. 7 backward (core/message_passing.py): kernel-vs-oracle parity over the
edge shapes, the ops.py fused/loop dispatch heuristic + configure/reset
hooks, the one-kernel-dispatch contract of context_messages_reconstruct,
the lazy-residual contract of inject_context_grad, and gradient parity of
approx_message_passing's cotangent against dense autodiff through the full
convolution matrix on a tiny graph.

Gradient tests also run under REPRO_FORCE_PALLAS=1: the kernel-path SpMM
and context dispatches carry custom VJPs whose backward is the oracle's
XLA-compiled VJP (kernels/ops.py), and the streaming Eq. 7 backward
*invokes* the context kernel forward.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from numpy.testing import assert_allclose

from repro.core.message_passing import (ConvOperands, approx_message_passing,
                                        context_messages_reconstruct,
                                        inject_context_grad_materialized,
                                        intra_messages, reconstruct)
from repro.kernels import ops, ref
from repro.kernels import context_ell as context_ell_mod
from repro.kernels.context_ell import context_ell_pallas

def _case(b, deg, n, nb, k, f_blk, seed=None, cw_dtype=jnp.float32):
    key = jax.random.PRNGKey(seed if seed is not None
                             else b * 131 + deg * 7 + nb)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    ids = jax.random.randint(k1, (b, deg), 0, n).astype(jnp.int32)
    val = jax.random.normal(k2, (b, deg), jnp.float32)
    assign = jax.random.randint(k3, (nb, n), 0, k).astype(jnp.int32)
    cw = jax.random.normal(k4, (nb, k, f_blk), cw_dtype)
    return ids, val, assign, cw


def _legacy_loop(out_ids, out_vals, assignment, codewords):
    """The pre-fusion context path: per-branch gather + SpMM + concat."""
    branch_ids = assignment[:, out_ids]                    # [nb, b, D]
    per_branch = [ref.spmm_ell(branch_ids[i], out_vals, codewords[i])
                  for i in range(codewords.shape[0])]
    return jnp.concatenate(per_branch, axis=-1)


# ---------------------------------------------------------------------------
# kernel parity: fused kernel vs oracle vs legacy per-branch loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,deg,n,nb,k,f_blk", [
    (1, 1, 1, 1, 1, 1),        # degenerate minimum
    (8, 4, 16, 2, 4, 8),       # everything below one tile
    (33, 7, 50, 4, 16, 8),     # b a non-multiple of bb, nb=4
    (128, 32, 300, 2, 64, 16), # multi-tile
    (5, 0, 10, 4, 8, 8),       # D=0 column padding (no out-of-batch slots)
    (257, 5, 999, 1, 256, 8),  # single branch, 2 codeword groups of 128
    (130, 32, 500, 32, 1024, 4),   # the paper's 32 x 4 branches, k, D
    (64, 9, 150, 8, 1024, 16),     # the head's 8 x 16, D % 8 != 0
    (40, 11, 300, 8, 300, 5),      # 8 x 5 gradient codewords, k % 128 != 0
    (40, 9, 100, 1, 1024, 128),    # one full-width branch: the one-hot
    (24, 5, 60, 2, 40, 301),       # two 301-wide branches: the one-hot
])
@pytest.mark.parametrize("cw_dtype", [jnp.float32, jnp.bfloat16])
def test_context_ell_sweep(b, deg, n, nb, k, f_blk, cw_dtype):
    ids, val, assign, cw = _case(b, deg, n, nb, k, f_blk, cw_dtype=cw_dtype)
    got = context_ell_pallas(ids, val, assign, cw, interpret=True)
    want = ref.context_ell(ids, val, assign, cw)
    tol = dict(rtol=2e-2, atol=1e-2) if cw_dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)
    assert got.shape == (b, nb * f_blk)
    assert_allclose(np.asarray(got), np.asarray(want), **tol)
    if deg > 0 and cw_dtype == jnp.float32:
        legacy = _legacy_loop(ids, val, assign, cw)
        assert_allclose(np.asarray(want), np.asarray(legacy),
                        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,deg,n,nb,k,f_blk,f_out", [
    (33, 7, 50, 4, 16, 8, 12),
    (64, 5, 200, 2, 32, 8, 8),
    (6, 0, 10, 2, 8, 4, 5),    # D=0 with epilogue
    (130, 32, 500, 32, 1024, 4, 128),  # the paper's Eq. 7 term, layers 1-2
    (40, 11, 300, 8, 300, 5, 128),     # the head's gradient codewords
])
def test_context_ell_wt_epilogue(b, deg, n, nb, k, f_blk, f_out):
    """The fused ``@ W^T`` epilogue (the streaming Eq. 7 backward form)."""
    ids, val, assign, cw = _case(b, deg, n, nb, k, f_blk)
    w_t = jax.random.normal(jax.random.PRNGKey(f_out), (nb * f_blk, f_out))
    got = context_ell_pallas(ids, val, assign, cw, w_t=w_t, interpret=True)
    want = ref.context_ell(ids, val, assign, cw, w_t)
    assert got.shape == (b, f_out)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("slots", ["distinct", "same_node",
                                   "same_codeword"])
def test_context_ell_all_out_of_batch_rows(slots):
    """Rows whose every slot is a real out-of-batch edge (no zero padding);
    several slots of a row may read one codeword (one node repeated, or
    every node on the last codeword of a 200-word book)."""
    ids, val, assign, cw = _case(40, 6, 100, 4, 200, 8)
    val = jnp.abs(val) + 0.5                     # all slots carry real edges
    if slots == "same_node":
        ids = jnp.broadcast_to(ids[:, :1], ids.shape)
    elif slots == "same_codeword":
        assign = jnp.full_like(assign, 199)
    got = context_ell_pallas(ids, val, assign, cw, interpret=True)
    want = ref.context_ell(ids, val, assign, cw)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_context_ell_padding_zero_vals():
    """Padding slots carry val == 0; their ids may point anywhere valid."""
    ids, val, assign, cw = _case(24, 5, 60, 2, 8, 8)
    val = val.at[3].set(0.0).at[17].set(0.0)
    got = context_ell_pallas(ids, val, assign, cw, interpret=True)
    want = ref.context_ell(ids, val, assign, cw)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(got)[3] == 0) and np.all(np.asarray(got)[17] == 0)


@pytest.mark.parametrize("bb", [8, 32, 100])   # incl. non-pow2, b % bb != 0
def test_context_ell_tile_sizes(bb):
    ids, val, assign, cw = _case(53, 6, 210, 4, 16, 8)
    got = context_ell_pallas(ids, val, assign, cw, bb=bb, interpret=True)
    want = ref.context_ell(ids, val, assign, cw)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lookup", [True, False])
@pytest.mark.parametrize("b,deg,n,nb,k,f_blk", [
    (33, 7, 50, 4, 16, 8),
    (40, 11, 300, 8, 300, 5),
    (24, 5, 60, 2, 140, 48),
])
@pytest.mark.parametrize("quantized", [False, True])
def test_context_ell_inner_forms(b, deg, n, nb, k, f_blk, lookup, quantized):
    """Both inner forms of the kernel, lane lookup and one-hot, whichever
    the shape would choose: f32 codewords, and int8 codewords with their
    scales and the ``w_t`` epilogue."""
    from repro.distributed.quantization import quantize_codewords
    ids, val, assign, cw = _case(b, deg, n, nb, k, f_blk)
    scale = w_t = None
    if quantized:
        qt = quantize_codewords(cw)
        cw, scale = qt.q, qt.scale
        w_t = jax.random.normal(jax.random.PRNGKey(3), (nb * f_blk, 24))
    got = context_ell_mod._context_ell(ids, val, assign, cw, cw_scale=scale,
                                       w_t=w_t, bb=128, interpret=True,
                                       lookup=lookup)
    deq = cw if scale is None else cw.astype(jnp.float32) * scale
    want = ref.context_ell(ids, val, assign, deq, w_t)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_context_inner_form_follows_width():
    """The lookup's cost grows with the branch's columns, the one-hot's
    does not: the paper's 4-, 5- and 16-wide branches take the lookup,
    full-width (128) and Reddit's 301-wide layer-0 branches the one-hot."""
    uses = context_ell_mod.uses_lookup
    assert uses(1024, 4) and uses(1024, 5) and uses(1024, 16)
    assert uses(16, 8) and uses(1, 1)
    assert not uses(1024, 128) and not uses(1024, 301)
    assert not uses(41, 602)


# ---------------------------------------------------------------------------
# ops.py dispatch: heuristic, env/configure overrides, reset
# ---------------------------------------------------------------------------

def test_context_variant_heuristic(monkeypatch):
    """The rule charges what the fused kernel holds in VMEM -- the
    codebook's tables and the double-buffered id, value and output blocks
    -- and not the [nb, n] assignment table, which XLA gathers ahead of
    the kernel (the rule takes no node count)."""
    monkeypatch.delenv("REPRO_CONTEXT_VARIANT", raising=False)
    monkeypatch.setenv("REPRO_CONTEXT_VMEM_BUDGET_MB", "4")
    # the paper's widths: 2.4 MiB held by the 32 x 4 terms, less by the
    # head's 8 x 16
    assert ops.context_ell_variant(32, 1024, 4, 32) == "fused"
    assert ops.context_ell_variant(8, 1024, 16, 32) == "fused"
    # 16k codewords per branch: 16 MiB of double-buffered lookup tables
    assert ops.context_ell_variant(32, 16384, 4, 32) == "loop"
    # 1024-row tiles: 8 MiB of double-buffered id blocks
    assert ops.context_ell_variant(32, 1024, 4, 32, bl=1024) == "loop"
    # two 301-wide branches: 5.7 MiB, mostly double-buffered one-hot tables
    assert ops.context_ell_variant(2, 1024, 301, 32) == "loop"
    monkeypatch.setenv("REPRO_CONTEXT_VARIANT", "loop")
    assert ops.context_ell_variant(1, 8, 4, 4) == "loop"
    monkeypatch.setenv("REPRO_CONTEXT_VARIANT", "fused")
    assert ops.context_ell_variant(32, 16384, 4, 32) == "fused"
    monkeypatch.setenv("REPRO_CONTEXT_VARIANT", "nope")
    with pytest.raises(ValueError):
        ops.context_ell_variant(1, 8, 4, 4)


def test_context_configure_and_reset(monkeypatch):
    monkeypatch.delenv("REPRO_CONTEXT_VARIANT", raising=False)
    monkeypatch.delenv("REPRO_CONTEXT_VMEM_BUDGET_MB", raising=False)
    shape = (4, 16, 8, 8)             # nb, k, f_blk, D: 0.12 MiB held
    try:
        ops.configure_context_dispatch(variant="loop")
        assert ops.context_ell_variant(1, 8, 4, 4) == "loop"
        ops.configure_context_dispatch(variant="auto", vmem_budget_mb=0.1)
        assert ops.context_ell_variant(*shape) == "loop"
        with pytest.raises(ValueError):
            ops.configure_context_dispatch(variant="nope")
        # reset clears every programmatic override -> back to defaults
        ops.configure_context_dispatch(reset=True)
        assert not ops._context_overrides
        assert ops.context_ell_variant(*shape) == "fused"
        # reset composes with setting new values in the same call
        ops.configure_context_dispatch(variant="loop", reset=True)
        assert ops._context_overrides == {"variant": "loop"}
    finally:
        ops._context_overrides.clear()


def test_ops_dispatch_fused_and_loop(monkeypatch):
    """Forced-pallas: both dispatch variants match the oracle."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    ids, val, assign, cw = _case(30, 6, 80, 4, 16, 8)
    w_t = jax.random.normal(jax.random.PRNGKey(5), (4 * 8, 10))
    want = ref.context_ell(ids, val, assign, cw)
    want_w = ref.context_ell(ids, val, assign, cw, w_t)
    try:
        for variant in ("fused", "loop"):
            ops.configure_context_dispatch(variant=variant, reset=True)
            got = ops.context_ell(ids, val, assign, cw)
            got_w = ops.context_ell(ids, val, assign, cw, w_t)
            assert_allclose(np.asarray(got), np.asarray(want),
                            rtol=1e-5, atol=1e-5)
            assert_allclose(np.asarray(got_w), np.asarray(want_w),
                            rtol=1e-4, atol=1e-4)
    finally:
        ops._context_overrides.clear()


# ---------------------------------------------------------------------------
# the tentpole contracts: one kernel dispatch; lazy Eq. 7 residuals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nb", [1, 2, 4])
def test_context_messages_single_dispatch(monkeypatch, nb):
    """context_messages_reconstruct issues exactly ONE kernel dispatch
    regardless of n_branches (the pre-fusion path issued nb of them)."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    monkeypatch.delenv("REPRO_CONTEXT_VARIANT", raising=False)
    ids, val, assign, cw = _case(16, 5, 40, nb, 8, 8)
    jaxpr = jax.make_jaxpr(
        lambda v, i, c, a: context_messages_reconstruct(v, i, c, a))(
            val, ids, cw, assign)
    assert str(jaxpr).count("pallas_call") == 1


def _tiny_operands(seed=0, b=6, deg=4, dr=3, n=15, nb=2, k=8,
                   f_in=8, f_grad=6):
    """Random tiny-graph ConvOperands + VQ state (dr != deg on purpose so
    residual-shape assertions cannot alias the intra-term gather)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    in_pos = jax.random.randint(ks[0], (b, deg), -1, b).astype(jnp.int32)
    in_vals = jnp.where(in_pos >= 0, jax.random.normal(ks[1], (b, deg)), 0.0)
    out_ids = jax.random.randint(ks[2], (b, deg), 0, n).astype(jnp.int32)
    out_vals = jnp.where(in_pos < 0, jax.random.normal(ks[3], (b, deg)), 0.0)
    rev_ids = jax.random.randint(ks[4], (b, dr), 0, n).astype(jnp.int32)
    rev_vals = jax.random.normal(ks[5], (b, dr))
    fcw = jax.random.normal(ks[6], (nb, k, f_in // nb))
    gcw = jax.random.normal(ks[7], (nb, k, f_grad // nb))
    assign = jax.random.randint(ks[8], (nb, n), 0, k).astype(jnp.int32)
    x_b = jax.random.normal(ks[9], (b, f_in))
    w = jax.random.normal(ks[10], (f_in, f_grad))
    cot = jax.random.normal(ks[11], (b, f_in))
    ops_ = ConvOperands(in_pos, in_vals, out_ids, out_vals,
                        rev_ids, rev_vals)
    return ops_, x_b, fcw, gcw, assign, w, cot


def test_inject_residuals_lazy():
    """inject_context_grad stores NO [b, Dr, f_grad] reconstruction: the
    vjp residuals are the O(b*Dr) edge operands + the O(k*f) codebook."""
    b, dr, f_grad = 6, 3, 6
    ops_, x_b, fcw, gcw, assign, w, _ = _tiny_operands(
        b=b, dr=dr, f_grad=f_grad)
    _, vjp_fn = jax.vjp(
        lambda x: approx_message_passing(ops_, x, fcw, gcw, assign, w), x_b)
    leaves = jax.tree_util.tree_leaves(vjp_fn)
    shapes = [tuple(l.shape) for l in leaves]
    assert (b, dr, f_grad) not in shapes          # the materialized tensor
    assert not any(l.ndim == 3 and l.shape[:2] == (b, dr) for l in leaves)
    # positive check: the codebook table IS the residual
    assert gcw.shape in shapes


@pytest.mark.parametrize("with_w", [False, True])
def test_eq7_gradient_parity_dense(with_w):
    """approx_message_passing's cotangent (streaming fused backward) ==
    dense autodiff through the full convolution matrix + the dense Eq. 7
    phantom term, on a tiny graph."""
    b, deg, dr, n, nb, k, f_in = 6, 4, 3, 15, 2, 8, 8
    f_grad = f_in if not with_w else 6
    ops_, x_b, fcw, gcw, assign, w, cot = _tiny_operands(
        b=b, deg=deg, dr=dr, n=n, nb=nb, k=k, f_in=f_in, f_grad=f_grad)
    w = w if with_w else None

    got = jax.grad(lambda x: jnp.sum(
        approx_message_passing(ops_, x, fcw, gcw, assign, w) * cot))(x_b)

    # dense C_in [b, b] and its exact autodiff cotangent C_in^T cot
    c_in = np.zeros((b, b), np.float32)
    in_pos, in_vals = np.asarray(ops_.in_pos), np.asarray(ops_.in_vals)
    for i in range(b):
        for d in range(deg):
            if in_pos[i, d] >= 0:
                c_in[i, in_pos[i, d]] += in_vals[i, d]
    dense_intra = jax.grad(lambda x: jnp.sum(
        (jnp.asarray(c_in) @ x) * cot))(x_b)

    # dense Eq. 7 phantom:  Crev @ Ghat_full (@ W^T), Ghat_full = R G~
    ghat_full = np.asarray(reconstruct(gcw, assign, jnp.arange(n)))  # [n, fg]
    c_rev = np.zeros((b, n), np.float32)
    rev_ids, rev_vals = np.asarray(ops_.rev_ids), np.asarray(ops_.rev_vals)
    for i in range(b):
        for d in range(dr):
            c_rev[i, rev_ids[i, d]] += rev_vals[i, d]
    phantom = c_rev @ ghat_full
    if w is not None:
        phantom = phantom @ np.asarray(w).T

    assert_allclose(np.asarray(got), np.asarray(dense_intra) + phantom,
                    rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("with_w", [False, True])
def test_eq7_streaming_matches_materialized(with_w):
    """The lazy streaming backward == the pre-PR materialized injection."""
    f_grad = 8 if not with_w else 6
    ops_, x_b, fcw, gcw, assign, w, cot = _tiny_operands(f_grad=f_grad)
    w = w if with_w else None

    def legacy(x):
        grad_hat = jax.lax.stop_gradient(
            reconstruct(gcw, assign, ops_.rev_ids))
        xi = inject_context_grad_materialized(x, ops_.rev_vals, grad_hat, w)
        m = intra_messages(ops_.in_pos, ops_.in_vals, xi)
        return m + context_messages_reconstruct(
            ops_.out_vals, ops_.out_ids, fcw, assign)

    g_new = jax.grad(lambda x: jnp.sum(
        approx_message_passing(ops_, x, fcw, gcw, assign, w) * cot))(x_b)
    g_old = jax.grad(lambda x: jnp.sum(legacy(x) * cot))(x_b)
    assert_allclose(np.asarray(g_new), np.asarray(g_old),
                    rtol=1e-5, atol=1e-5)


def test_eq7_inject_off_is_plain_autodiff():
    """inject=False: the cotangent is exactly the dense C_in^T term."""
    ops_, x_b, fcw, gcw, assign, w, cot = _tiny_operands()
    got = jax.grad(lambda x: jnp.sum(approx_message_passing(
        ops_, x, fcw, gcw, assign, None, inject=False) * cot))(x_b)
    want = jax.grad(lambda x: jnp.sum(
        intra_messages(ops_.in_pos, ops_.in_vals, x) * cot))(x_b)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
