"""VQ codebook state and streaming (EMA / online k-means) updates.

Implements Algorithm 2 of the paper (VQ-Update): exponential-moving-average
codeword estimation with implicit whitening, plus the product-VQ split
(Appendix E).  A codebook quantizes the *concatenation* of a node's layer-l
input features and its layer-l pre-activation gradients,

    V = X^(l) || G^(l+1)   (paper Sec. 4: "each pair of codewords are
                            concatenated together during VQ updates")

so one assignment matrix R serves both the forward sketch (feature codewords)
and the backward sketch (gradient codewords).

Everything here is a pure function on pytrees -> jit/pjit friendly.  At pod
scale the codebook is replicated and the (counts, sums) statistics of the EMA
update are all-reduced over the data axis -- identical math to the
single-device online k-means (see DESIGN.md section 3).

One-pass-per-branch invariant: :func:`update` performs exactly ONE distance
computation per product-VQ branch per step.  The fused assign+stats kernel
(``kernels/vq_update.py``, dispatched via ``kops.vq_assign_update``) returns
the assignment together with the per-codeword (counts, sums) and the per-row
quantization error, so neither the EMA step, nor dead-codeword revival, nor
the relative-error monitor recomputes distances or materializes a
``[n_branches, b, k]`` one-hot.  Anything added to the update path must
consume these fused outputs rather than re-deriving them.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed import quantization
from repro.kernels import ops as kops


class CodebookState(NamedTuple):
    """State of one layer's product-VQ codebooks.

    All leading axes: ``n_branches`` product-VQ branches, each quantizing
    ``f_feat_blk`` feature dims concatenated with ``f_grad_blk`` gradient dims.

    Codewords are stored in *whitened* space (``codewords_w``); reads go
    through :func:`feature_codewords` / :func:`gradient_codewords` which
    un-whiten with the smoothed mean/var (Alg. 2 line 9).
    """

    codewords_w: jax.Array      # [n_branches, k, f_blk]   whitened codewords
    cluster_size: jax.Array     # [n_branches, k]          EMA cluster sizes (eta)
    cluster_sum: jax.Array      # [n_branches, k, f_blk]   EMA cluster sums (Sigma)
    mean: jax.Array             # [n_branches, f_blk]      smoothed E[V]
    var: jax.Array              # [n_branches, f_blk]      smoothed Var[V]
    step: jax.Array             # []                       update counter

    @property
    def n_branches(self) -> int:
        return self.codewords_w.shape[0]

    @property
    def k(self) -> int:
        return self.codewords_w.shape[1]

    @property
    def f_blk(self) -> int:
        return self.codewords_w.shape[2]


class UpdateStats(NamedTuple):
    """Per-batch byproducts of :func:`update`, emitted by the fused kernel.

    All in *whitened* concat space (the space assignments are made in), so
    they come for free from the single distance pass -- consumers must not
    recompute them.
    """

    assignment: jax.Array   # [n_branches, b] int32  nearest codeword per row
    qerr: jax.Array         # [n_branches, b]        ||v_w - c_assign||^2
    vnorm2: jax.Array       # [n_branches, b]        ||v_w||^2

    def relative_error(self) -> jax.Array:
        """Whitened-space VQ relative error ||V - R V~|| / ||V|| of this
        batch -- the free training-loop convergence monitor (the Theorem-2
        feature-half epsilon is :func:`relative_error` below)."""
        return jnp.sqrt(jnp.sum(self.qerr) /
                        (jnp.sum(self.vnorm2) + 1e-12))


class CodebookConfig(NamedTuple):
    k: int = 256                 # number of codewords per branch
    f_prod: int = 4              # feature dims per product-VQ branch
    gamma: float = 0.99          # EMA decay for codeword stats (Alg. 2)
    beta: float = 0.999          # EMA decay for whitening stats (Alg. 2)
    eps: float = 1e-5
    whiten: bool = True          # implicit whitening (App. E)
    revive_threshold: float = 0.05   # EMA size under which a codeword is
    # considered dead and re-seeded on the worst-quantized batch rows


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def branch_layout(f_feat: int, f_grad: int, f_prod: int) -> tuple[int, int, int]:
    """Return (n_branches, f_feat_blk, f_grad_blk).

    The paper pairs feature block i with gradient block i under a single
    assignment matrix ("paired" mode); this requires the same number of
    blocks on each side, which we arrange by scaling the per-branch block
    width on the larger side.
    """
    import math
    cap = min(max(1, f_feat // f_prod), max(1, f_grad // f_prod))
    g = math.gcd(f_feat, f_grad)
    n_branches = 1
    for d in range(1, g + 1):
        if g % d == 0 and d <= cap:
            n_branches = d
    return n_branches, f_feat // n_branches, f_grad // n_branches


def init_codebook(key: jax.Array, f_feat: int, f_grad: int,
                  cfg: CodebookConfig) -> CodebookState:
    n_branches, fb, gb = branch_layout(f_feat, f_grad, cfg.f_prod)
    f_blk = fb + gb
    # gradient halves start at zero: before any gradient is observed the
    # Eq. 7 injection must add nothing, and per-node loss gradients (~1/b)
    # sit orders of magnitude below a random init's scale
    cw = 0.02 * jax.random.normal(key, (n_branches, cfg.k, f_blk), jnp.float32)
    cw = cw.at[:, :, fb:].set(0.0)
    return CodebookState(
        codewords_w=cw,
        cluster_size=jnp.ones((n_branches, cfg.k), jnp.float32),
        cluster_sum=cw.copy(),
        mean=jnp.zeros((n_branches, f_blk), jnp.float32),
        var=jnp.ones((n_branches, f_blk), jnp.float32),
        step=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# whitening helpers (Alg. 2 lines 2-4, 9)
# ---------------------------------------------------------------------------

def _whiten(v: jax.Array, mean: jax.Array, var: jax.Array, eps: float) -> jax.Array:
    return (v - mean[None, :]) * jax.lax.rsqrt(var[None, :] + eps)


def _unwhiten(v: jax.Array, mean: jax.Array, var: jax.Array, eps: float) -> jax.Array:
    return v * jnp.sqrt(var[None, :] + eps) + mean[None, :]


def _split_branches(x: jax.Array, n_branches: int) -> jax.Array:
    """[b, f] -> [n_branches, b, f // n_branches]."""
    b, f = x.shape
    return x.reshape(b, n_branches, f // n_branches).transpose(1, 0, 2)


def _merge_branches(x: jax.Array) -> jax.Array:
    """[n_branches, m, f_blk] -> [m, n_branches * f_blk]."""
    n, m, fb = x.shape
    return x.transpose(1, 0, 2).reshape(m, n * fb)


# ---------------------------------------------------------------------------
# codeword reads
# ---------------------------------------------------------------------------

def _unwhitened_codewords(state: CodebookState, eps: float) -> jax.Array:
    """[n_branches, k, f_blk] in original (un-whitened) space."""
    return jax.vmap(lambda c, m, v: _unwhiten(c, m, v, eps))(
        state.codewords_w, state.mean, state.var)


def feature_codewords(state: CodebookState, f_feat: int,
                      cfg: CodebookConfig) -> jax.Array:
    """Per-branch feature codewords X~: [n_branches, k, f_feat_blk]."""
    n = state.n_branches
    fb = f_feat // n
    return _unwhitened_codewords(state, cfg.eps)[:, :, :fb]


def gradient_codewords(state: CodebookState, f_feat: int,
                       cfg: CodebookConfig) -> jax.Array:
    """Per-branch gradient codewords G~: [n_branches, k, f_grad_blk]."""
    n = state.n_branches
    fb = f_feat // n
    return _unwhitened_codewords(state, cfg.eps)[:, :, fb:]


def quantized_codewords(state: CodebookState, f_feat: int,
                        cfg: CodebookConfig, *,
                        prev_feat: Optional[quantization.QTensor] = None,
                        prev_grad: Optional[quantization.QTensor] = None,
                        dtype=jnp.int8
                        ) -> tuple[quantization.QTensor, quantization.QTensor]:
    """Quantized kernel operands of the (feature, gradient) codeword tables.

    The quantize-on-update hook of the quantized tiers (DESIGN.md sections
    13/15): each table becomes a QTensor with per-branch/per-channel scales
    ([nb, 1, f_blk], amax over the k codewords only) -- the exact layout
    ``kops.context_ell`` dequantizes in one epilogue row.  ``dtype`` picks
    int8 or float8_e4m3fn storage for a fresh snapshot; passing the
    previous step's QTensors pins the dtype to theirs and enables the
    drift-aware rescale: the quantization grid is reused while the EMA
    step barely moves the table, keeping serving-side quantized bytes
    stable across refreshes.
    """
    fcw = feature_codewords(state, f_feat, cfg)
    gcw = gradient_codewords(state, f_feat, cfg)
    return (quantization.quantize_codewords(fcw, prev=prev_feat, dtype=dtype),
            quantization.quantize_codewords(gcw, prev=prev_grad, dtype=dtype))


# ---------------------------------------------------------------------------
# assignment
# ---------------------------------------------------------------------------

def assign(state: CodebookState, feats: jax.Array, grads: jax.Array,
           cfg: CodebookConfig) -> jax.Array:
    """Nearest-codeword assignment in whitened concat space.

    feats: [b, f_feat], grads: [b, f_grad]  ->  [n_branches, b] int32.
    """
    n = state.n_branches
    v = jnp.concatenate(
        [_split_branches(feats.astype(jnp.float32), n),
         _split_branches(grads.astype(jnp.float32), n)], axis=-1)
    if cfg.whiten:
        v = jax.vmap(lambda x, m, s: _whiten(x, m, s, cfg.eps))(
            v, state.mean, state.var)
    return jax.vmap(kops.vq_assign)(v, state.codewords_w)


@jax.named_scope("vq_update")
def assign_features_only(state: CodebookState, feats: jax.Array, f_feat: int,
                         cfg: CodebookConfig) -> jax.Array:
    """Assignment using only the feature half (inference / inductive setting).

    The paper (Sec. 6, PPI inductive): "during the inference stage, we find
    the codeword assignments (i.e. the nearest codeword) of the test nodes".
    At inference no gradients exist, so distance is measured on feature dims.
    """
    n = state.n_branches
    fb = f_feat // n
    v = _split_branches(feats.astype(jnp.float32), n)
    if cfg.whiten:
        v = jax.vmap(lambda x, m, s: _whiten(x, m, s, cfg.eps))(
            v, state.mean[:, :fb], state.var[:, :fb])
    return jax.vmap(kops.vq_assign)(v, state.codewords_w[:, :, :fb])


# ---------------------------------------------------------------------------
# VQ-Update (Algorithm 2)
# ---------------------------------------------------------------------------

@jax.named_scope("vq_update")
def update(state: CodebookState, feats: jax.Array, grads: jax.Array,
           cfg: CodebookConfig, *,
           axis_name: Optional[str] = None
           ) -> tuple[CodebookState, UpdateStats]:
    """One streaming VQ update with a mini-batch of (features || gradients).

    Returns (new_state, :class:`UpdateStats`) -- the stats carry the
    assignment [n_branches, b] plus the per-row quantization error the
    single fused distance pass emits (module docstring: one-pass-per-branch
    invariant).  Cluster statistics come fused from the kernel; there is no
    one-hot / ``[n, b, k]`` einsum on any path.

    If ``axis_name`` is given the (counts, sums, batch moments) are psum-ed
    over that mesh axis so that data-parallel replicas learn one codebook.
    """
    n = state.n_branches
    v = jnp.concatenate(
        [_split_branches(feats.astype(jnp.float32), n),
         _split_branches(grads.astype(jnp.float32), n)], axis=-1)
    b = v.shape[1]

    # --- batch moments (possibly cross-replica) ---
    if axis_name is None:
        batch_mean = jnp.mean(v, axis=1)                     # [n, f_blk]
        batch_var = jnp.var(v, axis=1)
    else:
        s1 = jax.lax.psum(jnp.sum(v, axis=1), axis_name)
        s2 = jax.lax.psum(jnp.sum(v * v, axis=1), axis_name)
        cnt = jax.lax.psum(jnp.asarray(b, jnp.float32), axis_name)
        batch_mean = s1 / cnt
        batch_var = jnp.maximum(s2 / cnt - batch_mean ** 2, 0.0)

    if cfg.whiten:
        new_mean = state.mean * cfg.beta + batch_mean * (1.0 - cfg.beta)
        new_var = state.var * cfg.beta + batch_var * (1.0 - cfg.beta)
        vw = jax.vmap(lambda x, m, s: _whiten(x, m, s, cfg.eps))(
            v, new_mean, new_var)
    else:
        new_mean, new_var = state.mean, state.var
        vw = v

    # --- fused: nearest codeword + cluster stats + per-row qerr, one
    # distance pass per branch (kernels/vq_update.py / the scatter oracle) ---
    assignment, qerr, counts, sums = jax.vmap(kops.vq_assign_update)(
        vw, state.codewords_w)        # [n, b], [n, b], [n, k], [n, k, f_blk]
    if axis_name is not None:
        counts = jax.lax.psum(counts, axis_name)
        sums = jax.lax.psum(sums, axis_name)

    new_size = state.cluster_size * cfg.gamma + counts * (1.0 - cfg.gamma)
    new_sum = state.cluster_sum * cfg.gamma + sums * (1.0 - cfg.gamma)
    new_cw = new_sum / jnp.maximum(new_size, cfg.eps)[..., None]

    # dead codewords keep their previous position
    alive = (new_size > 1e-3)[..., None]
    new_cw = jnp.where(alive, new_cw, state.codewords_w)

    # --- dead-codeword revival: park starved codewords on the batch rows
    # with the largest quantization error (keeps the codebook fully used;
    # standard online-k-means practice, deterministic and jit-friendly).
    # The ranking consumes the kernel-emitted qerr -- cheap [k]/[b]-shaped
    # post-processing, no recomputed reconstruction distances.  Under data
    # parallelism the candidate rows are all-gathered first: the dead mask
    # is replica-identical (psum'd sizes), so picking from replica-LOCAL
    # rows would silently write different replacement codewords on every
    # device and diverge the "replicated" codebooks ---
    if cfg.revive_threshold > 0:
        vw_rev, qerr_rev = vw, qerr
        if axis_name is not None:
            vw_rev = jax.lax.all_gather(vw, axis_name, axis=1, tiled=True)
            qerr_rev = jax.lax.all_gather(qerr, axis_name, axis=1,
                                          tiled=True)
        n_rev = min(cfg.k, qerr_rev.shape[-1])
        _, worst = jax.lax.top_k(qerr_rev, n_rev)             # [n, n_rev]
        worst_rows = jax.vmap(lambda vv, ww: vv[ww])(vw_rev, worst)
        dead = new_size < cfg.revive_threshold                # [n, k]
        # rank dead codewords so each picks a distinct worst row
        rank = jnp.cumsum(dead.astype(jnp.int32), axis=1) - 1
        rank = jnp.clip(rank, 0, n_rev - 1)
        repl = jax.vmap(lambda wr, rk: wr[rk])(worst_rows, rank)
        new_cw = jnp.where(dead[..., None], repl, new_cw)
        new_size = jnp.where(dead, 1.0, new_size)
        new_sum = jnp.where(dead[..., None], repl, new_sum)

    stats = UpdateStats(assignment=assignment, qerr=qerr,
                        vnorm2=jnp.sum(vw * vw, axis=-1))
    return CodebookState(new_cw, new_size, new_sum, new_mean, new_var,
                         state.step + 1), stats


def kmeanspp_init(key: jax.Array, state: CodebookState, feats: jax.Array,
                  grads: jax.Array, cfg: CodebookConfig) -> CodebookState:
    """Seed codewords from a batch (random rows + jitter), jit-compatible.

    A light-weight stand-in for k-means++ seeding: the streaming EMA updates
    converge from here (paper App. F uses random init as well).
    """
    n = state.n_branches
    v = jnp.concatenate(
        [_split_branches(feats.astype(jnp.float32), n),
         _split_branches(grads.astype(jnp.float32), n)], axis=-1)
    b = v.shape[1]
    mean = jnp.mean(v, axis=1)
    var = jnp.maximum(jnp.var(v, axis=1), 0.0)
    if cfg.whiten:
        vw = jax.vmap(lambda x, m, s: _whiten(x, m, s, cfg.eps))(v, mean, var)
    else:
        vw = v
    kidx, knoise = jax.random.split(key)
    rows = jax.random.randint(kidx, (n, cfg.k), 0, b)
    seeds = jax.vmap(lambda vv, rr: vv[rr])(vw, rows)          # [n, k, f_blk]
    seeds = seeds + 0.01 * jax.random.normal(knoise, seeds.shape, seeds.dtype)
    return CodebookState(
        codewords_w=seeds,
        cluster_size=jnp.ones_like(state.cluster_size),
        cluster_sum=seeds.copy(),
        mean=mean if cfg.whiten else state.mean,
        var=var if cfg.whiten else state.var,
        step=state.step,
    )


def relative_error(state: CodebookState, feats: jax.Array, grads: jax.Array,
                   assignment: jax.Array, f_feat: int,
                   cfg: CodebookConfig) -> jax.Array:
    """VQ relative error  eps = ||X - R X~||_F / ||X||_F  on the feature half.

    This is the epsilon appearing in Theorem 2 / Corollary 3 -- an offline
    oracle (tests, benchmarks): it reconstructs in un-whitened feature space,
    which costs a gather the training loop never pays.  In-training
    monitoring uses :meth:`UpdateStats.relative_error`, the whitened-space
    epsilon the fused update kernel emits for free.
    """
    n = state.n_branches
    xcw = feature_codewords(state, f_feat, cfg)               # [n, k, fb]
    xb = _split_branches(feats.astype(jnp.float32), n)        # [n, b, fb]
    recon = jax.vmap(lambda c, a: c[a])(xcw, assignment)      # [n, b, fb]
    num = jnp.sqrt(jnp.sum((xb - recon) ** 2))
    den = jnp.sqrt(jnp.sum(xb ** 2)) + 1e-12
    return num / den
