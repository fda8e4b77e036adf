"""Fused VQ assign+stats kernel (kernels/vq_update.py) validation.

Parity of (assignment, counts, sums, qerr) against the jnp oracle over b/k/f
edge shapes, the optional min-distance output of vq_assign, and the
codebook.update equivalence old-path (one-hot einsum) vs fused-path --
including the dead-codeword revival branch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core import codebook as cbm
from repro.core.codebook import CodebookConfig, CodebookState
from repro.kernels import ref
from repro.kernels.vq_assign import vq_assign_pallas
from repro.kernels.vq_update import vq_assign_update_pallas


# ---------------------------------------------------------------------------
# kernel vs oracle parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,f", [
    (1, 1, 1),            # degenerate minimum
    (7, 3, 5),            # everything tiny and non-multiple
    (130, 33, 12),        # non-multiples of bb/kb/lane width
    (64, 16, 4),          # paper-ish f_blk
    (100, 1024, 8),       # b < bb, k spanning two k-tiles
    (256, 300, 128),      # k < kb after clamping, full lane width
    (520, 256, 8),        # b spanning three b-tiles, paper-scale k
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vq_update_parity_sweep(b, k, f, dtype):
    kx, kc = jax.random.split(jax.random.PRNGKey(b * 131 + k))
    x = jax.random.normal(kx, (b, f), dtype)
    c = jax.random.normal(kc, (k, f), dtype)
    gi, gq, gc, gs = vq_assign_update_pallas(x, c, interpret=True)
    wi, wq, wc, ws = ref.vq_assign_update(x, c)

    assert gi.shape == (b,) and gq.shape == (b,)
    assert gc.shape == (k,) and gs.shape == (k, f)

    # ties can legitimately differ: accept either argmin when distances tie
    x32, c32 = x.astype(jnp.float32), c.astype(jnp.float32)
    d = ((x32[:, None] - c32[None]) ** 2).sum(-1)
    d_got = jnp.take_along_axis(d, gi[:, None].astype(jnp.int32), 1)[:, 0]
    d_want = jnp.take_along_axis(d, wi[:, None].astype(jnp.int32), 1)[:, 0]
    assert_allclose(np.asarray(d_got), np.asarray(d_want), rtol=1e-5,
                    atol=1e-5)
    assert_allclose(np.asarray(gq), np.asarray(wq), rtol=1e-4, atol=1e-4)
    # stats compare exactly when assignments agree (random normals: no ties)
    if (np.asarray(gi) == np.asarray(wi)).all():
        assert_allclose(np.asarray(gc), np.asarray(wc), rtol=0, atol=0)
        assert_allclose(np.asarray(gs), np.asarray(ws), rtol=1e-5, atol=1e-5)
    assert float(gc.sum()) == b   # every (unpadded) row counted exactly once


def test_vq_update_qerr_is_true_distance():
    """qerr must equal the squared distance to the assigned codeword."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (97, 24))
    c = jax.random.normal(jax.random.PRNGKey(1), (40, 24))
    gi, gq, _, _ = vq_assign_update_pallas(x, c, interpret=True)
    want = ((np.asarray(x) - np.asarray(c)[np.asarray(gi)]) ** 2).sum(-1)
    assert_allclose(np.asarray(gq), want, rtol=1e-4, atol=1e-4)


def test_vq_update_padded_rows_excluded_from_stats():
    """b far from a bb multiple: padded rows must not leak into counts."""
    b, k, f = 9, 5, 3
    x = jax.random.normal(jax.random.PRNGKey(2), (b, f))
    c = jax.random.normal(jax.random.PRNGKey(3), (k, f))
    _, _, counts, sums = vq_assign_update_pallas(x, c, interpret=True)
    assert float(counts.sum()) == b
    assert_allclose(np.asarray(sums.sum(0)), np.asarray(x.sum(0)),
                    rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# vq_assign optional min-distance output (the former `del val` dead output)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,k,f", [(7, 3, 5), (130, 33, 12), (100, 300, 8)])
def test_vq_assign_want_min(b, k, f):
    kx, kc = jax.random.split(jax.random.PRNGKey(b + k))
    x = jax.random.normal(kx, (b, f))
    c = jax.random.normal(kc, (k, f))
    idx, mind = vq_assign_pallas(x, c, interpret=True, want_min=True)
    idx_only = vq_assign_pallas(x, c, interpret=True)
    assert (np.asarray(idx) == np.asarray(idx_only)).all()
    want = ((np.asarray(x) - np.asarray(c)[np.asarray(idx)]) ** 2).sum(-1)
    assert_allclose(np.asarray(mind), want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# codebook.update equivalence: old one-hot path vs fused path
# ---------------------------------------------------------------------------

def _reference_update(state, feats, grads, cfg):
    """The pre-fusion update math: separate assign, one-hot einsum stats,
    recomputed revival distances.  Kept here as the equivalence oracle."""
    n = state.n_branches
    v = jnp.concatenate(
        [cbm._split_branches(feats.astype(jnp.float32), n),
         cbm._split_branches(grads.astype(jnp.float32), n)], axis=-1)
    b = v.shape[1]
    batch_mean = jnp.mean(v, axis=1)
    batch_var = jnp.var(v, axis=1)
    if cfg.whiten:
        new_mean = state.mean * cfg.beta + batch_mean * (1.0 - cfg.beta)
        new_var = state.var * cfg.beta + batch_var * (1.0 - cfg.beta)
        vw = jax.vmap(lambda x, m, s: cbm._whiten(x, m, s, cfg.eps))(
            v, new_mean, new_var)
    else:
        new_mean, new_var = state.mean, state.var
        vw = v
    assignment = jax.vmap(ref.vq_assign)(vw, state.codewords_w)
    onehot = jax.nn.one_hot(assignment, cfg.k, dtype=vw.dtype)
    counts = jnp.sum(onehot, axis=1)
    sums = jnp.einsum('nbk,nbf->nkf', onehot, vw)
    new_size = state.cluster_size * cfg.gamma + counts * (1.0 - cfg.gamma)
    new_sum = state.cluster_sum * cfg.gamma + sums * (1.0 - cfg.gamma)
    new_cw = new_sum / jnp.maximum(new_size, cfg.eps)[..., None]
    alive = (new_size > 1e-3)[..., None]
    new_cw = jnp.where(alive, new_cw, state.codewords_w)
    if cfg.revive_threshold > 0:
        # true per-row quantization error ||vw_i - c_{a_i}||^2.  (The
        # pre-fusion code gathered vv[aa] -- batch rows indexed by CODEWORD
        # id -- which ranked the wrong rows for revival; the fused kernel's
        # emitted qerr is the correct per-row quantity, so the reference
        # uses the corrected formula here.)
        sel = jax.vmap(lambda vv, cc, aa: vv - cc[aa])(
            vw, state.codewords_w, assignment)
        qerr = jnp.sum(sel * sel, axis=-1)
        n_rev = min(cfg.k, b)
        _, worst = jax.lax.top_k(qerr, n_rev)
        worst_rows = jax.vmap(lambda vv, ww: vv[ww])(vw, worst)
        dead = new_size < cfg.revive_threshold
        rank = jnp.cumsum(dead.astype(jnp.int32), axis=1) - 1
        rank = jnp.clip(rank, 0, n_rev - 1)
        repl = jax.vmap(lambda wr, rk: wr[rk])(worst_rows, rank)
        new_cw = jnp.where(dead[..., None], repl, new_cw)
        new_size = jnp.where(dead, 1.0, new_size)
        new_sum = jnp.where(dead[..., None], repl, new_sum)
    return CodebookState(new_cw, new_size, new_sum, new_mean, new_var,
                         state.step + 1), assignment


def _states_allclose(got: CodebookState, want: CodebookState,
                     tol: float = 1e-4):
    for name, a, b in [("codewords_w", got.codewords_w, want.codewords_w),
                       ("cluster_size", got.cluster_size, want.cluster_size),
                       ("cluster_sum", got.cluster_sum, want.cluster_sum),
                       ("mean", got.mean, want.mean),
                       ("var", got.var, want.var)]:
        assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol,
                        err_msg=name)


@pytest.mark.parametrize("revive", [0.0, 0.05])
def test_update_equivalence_old_vs_fused(revive):
    """cbm.update (fused stats) == the unfused one-hot reference,
    including the revival branch.  For revive > 0 the codebook starts far
    away AND with near-zero EMA sizes so codewords genuinely die and the
    revival branch actually executes (asserted below, not assumed)."""
    cfg = CodebookConfig(k=16, f_prod=4, revive_threshold=revive)
    key = jax.random.PRNGKey(0)
    state = cbm.init_codebook(key, 8, 8, cfg)
    if revive > 0:   # far-away codewords + starved EMA sizes -> real deaths
        state = state._replace(
            codewords_w=state.codewords_w + 100.0,
            cluster_size=jnp.full_like(state.cluster_size, 1e-4))
    feats = jax.random.normal(key, (64, 8))
    grads = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
    revived_any = False
    for _ in range(3):
        got_state, got_stats = cbm.update(state, feats, grads, cfg)
        want_state, want_assign = _reference_update(state, feats, grads, cfg)
        assert (np.asarray(got_stats.assignment)
                == np.asarray(want_assign)).all()
        _states_allclose(got_state, want_state)
        new_size = state.cluster_size * cfg.gamma \
            + jax.vmap(lambda a: jnp.zeros((cfg.k,)).at[a].add(1.0))(
                got_stats.assignment) * (1.0 - cfg.gamma)
        revived_any |= bool((np.asarray(new_size) < revive).any())
        state = got_state
    if revive > 0:
        assert revived_any   # the branch under test actually fired


def test_update_fused_pallas_path_matches_cpu_path(monkeypatch):
    """REPRO_FORCE_PALLAS=1 routes the update through the interpret-mode
    fused kernel; the resulting state must match the CPU (oracle) path."""
    cfg = CodebookConfig(k=16, f_prod=4)
    key = jax.random.PRNGKey(0)
    state = cbm.init_codebook(key, 8, 8, cfg)
    feats = jax.random.normal(key, (48, 8))
    grads = jax.random.normal(jax.random.PRNGKey(1), (48, 8))

    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    cpu_state, cpu_stats = cbm.update(state, feats, grads, cfg)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    pls_state, pls_stats = cbm.update(state, feats, grads, cfg)

    assert (np.asarray(cpu_stats.assignment)
            == np.asarray(pls_stats.assignment)).all()
    assert_allclose(np.asarray(cpu_stats.qerr), np.asarray(pls_stats.qerr),
                    rtol=1e-4, atol=1e-4)
    _states_allclose(pls_state, cpu_state)


def test_train_vq_small_graph_pads_single_batch(monkeypatch):
    """batch_size > n used to yield NO mini-batch (the tail-drop bug, and a
    jnp.mean(None) crash risk in the vq_err monitor).  epoch_slices now
    clamps to one full-pool batch, so the epoch trains and the monitor is
    present -- on both executor paths."""
    from repro.graph.datasets import synthetic_arxiv
    from repro.models.gnn import GNNConfig
    from repro.train.gnn_trainer import train_vq
    g = synthetic_arxiv(n=60, seed=0)
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=8, n_out=g.num_classes,
                    n_layers=1, codebook=CodebookConfig(k=8, f_prod=4))
    r = train_vq(g, cfg, epochs=1, batch_size=g.n + 40, eval_every=1)
    assert "val" in r["final"] and "vq_err" in r["final"]
    monkeypatch.setenv("REPRO_EPOCH_EXECUTOR", "0")
    r = train_vq(g, cfg, epochs=1, batch_size=g.n + 40, eval_every=1)
    assert "val" in r["final"] and "vq_err" in r["final"]


def test_update_stats_relative_error_matches_manual():
    cfg = CodebookConfig(k=8, f_prod=4, whiten=False, beta=0.0)
    key = jax.random.PRNGKey(0)
    state = cbm.init_codebook(key, 8, 8, cfg)
    feats = jax.random.normal(key, (32, 8))
    grads = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    _, stats = cbm.update(state, feats, grads, cfg)
    n = state.n_branches
    v = jnp.concatenate(
        [cbm._split_branches(feats, n), cbm._split_branches(grads, n)], -1)
    recon = jax.vmap(lambda c, a: c[a])(state.codewords_w, stats.assignment)
    want = jnp.sqrt(((v - recon) ** 2).sum() / (v ** 2).sum())
    assert_allclose(float(stats.relative_error()), float(want), rtol=1e-4)
