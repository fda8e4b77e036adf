"""GNN backbones as generalized graph convolutions (paper Tables 1 & 5).

Every backbone implements two execution modes sharing one parameter set:

  * ``full_apply``  -- exact message passing on an explicit (sub)graph
    (the "full-graph" oracle, the sampling baselines' subgraphs, inference);
  * ``vq_apply``    -- the paper's approximated message passing on a
    mini-batch (Eq. 6 forward, Eq. 7 backward via the custom-VJP injection,
    probe-trick gradient taps for the codebook update).  ``probe=None``
    skips the tap (the probe only matters under ``jax.grad``): the
    gradient-free consumers -- inference executor, serving step, eval --
    pass None instead of shipping per-layer zero tensors through the graph.

Backbones: GCN, SAGE-Mean, GAT (learnable row-normalized convolution,
Lipschitz-clipped scores per App. E), GIN, and a global-attention
GraphTransformer (dense learnable convolution -- the case sampling methods
cannot handle at all, paper Sec. 1/3).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.codebook import CodebookConfig
from repro.core.conv import (LayerVQState, MinibatchPack, fixed_conv_operands,
                             layer_codewords, out_of_batch_cluster_mass)
from repro.core.message_passing import (approx_message_passing,
                                        inject_context_grad_materialized,
                                        inject_context_grad_table,
                                        reconstruct)
from repro.graph.batching import FullGraphOperands
from repro.kernels import ops as kops

Params = dict[str, Any]
SCORE_CLIP = 5.0   # App. E Lipschitz regularization of attention scores


def _dense(key, f_in, f_out, scale=None):
    scale = scale or (1.0 / jnp.sqrt(f_in))
    return scale * jax.random.normal(key, (f_in, f_out), jnp.float32)


def _gcn_edge_vals(ops_: FullGraphOperands) -> tuple[jax.Array, jax.Array]:
    dt = ops_.degrees + 1.0
    vals = ops_.nbr_mask / jnp.sqrt(dt[:, None] * (dt[ops_.nbr_ids]))
    return vals, 1.0 / dt


# ===========================================================================
# GCN  (fixed convolution  C = D~^-1/2 A~ D~^-1/2)
# ===========================================================================

class GCN:
    name = "gcn"

    @staticmethod
    def init(key, f_in: int, f_out: int, **_) -> Params:
        kw, = jax.random.split(key, 1)
        return {"w": _dense(kw, f_in, f_out), "b": jnp.zeros((f_out,))}

    @staticmethod
    def f_grad(f_in: int, f_out: int, **_) -> int:
        return f_out          # gradient codewords live at the Z level

    @staticmethod
    def probe_shape(b: int, f_in: int, f_out: int, **_) -> tuple[int, ...]:
        return (b, f_out)

    @staticmethod
    def full_apply(p: Params, x, ops_: FullGraphOperands, act) -> jax.Array:
        vals, self_vals = _gcn_edge_vals(ops_)
        m = kops.spmm_ell(ops_.nbr_ids, vals, x) \
            + self_vals[:, None] * x
        return act(m @ p["w"] + p["b"])

    @staticmethod
    def vq_apply(p: Params, x_b, probe, pack: MinibatchPack,
                 vq: LayerVQState, degrees, cfg: CodebookConfig, act,
                 f_in: int, f_out: int, inject: bool = True) -> jax.Array:
        ops_, self_vals = fixed_conv_operands('gcn', pack, degrees)
        # int8 QTensor operands when the layer state carries a snapshot
        fcw, gcw = layer_codewords(vq, f_in, cfg)
        m = approx_message_passing(ops_, x_b, fcw, gcw, vq.assignment,
                                   p["w"], inject)
        m = m + self_vals[:, None] * x_b
        z = m @ p["w"] + p["b"]
        return act(z if probe is None else z + probe)


# ===========================================================================
# SAGE-Mean  (two fixed convolutions:  C1 = I,  C2 = D^-1 A)
# ===========================================================================

class SAGE:
    name = "sage"

    @staticmethod
    def init(key, f_in: int, f_out: int, **_) -> Params:
        k1, k2 = jax.random.split(key)
        return {"w1": _dense(k1, f_in, f_out), "w2": _dense(k2, f_in, f_out),
                "b": jnp.zeros((f_out,))}

    @staticmethod
    def f_grad(f_in: int, f_out: int, **_) -> int:
        return f_out

    @staticmethod
    def probe_shape(b, f_in, f_out, **_):
        return (b, f_out)

    @staticmethod
    def full_apply(p: Params, x, ops_: FullGraphOperands, act) -> jax.Array:
        vals = ops_.nbr_mask / jnp.maximum(ops_.degrees, 1.0)[:, None]
        mean_nbr = kops.spmm_ell(ops_.nbr_ids, vals, x)
        return act(x @ p["w1"] + mean_nbr @ p["w2"] + p["b"])

    @staticmethod
    def vq_apply(p: Params, x_b, probe, pack, vq, degrees, cfg, act,
                 f_in: int, f_out: int, inject: bool = True) -> jax.Array:
        ops_, _ = fixed_conv_operands('mean', pack, degrees)
        fcw, gcw = layer_codewords(vq, f_in, cfg)
        m2 = approx_message_passing(ops_, x_b, fcw, gcw, vq.assignment,
                                    p["w2"], inject)
        # identity convolution is always intra-batch -> exact autodiff
        z = x_b @ p["w1"] + m2 @ p["w2"] + p["b"]
        return act(z if probe is None else z + probe)


# ===========================================================================
# GIN  (C1 = A fixed;  C2 = (1+eps) I learnable-diagonal;  MLP head)
# ===========================================================================

class GIN:
    name = "gin"

    @staticmethod
    def init(key, f_in: int, f_out: int, **_) -> Params:
        k1, k2 = jax.random.split(key)
        return {"w1": _dense(k1, f_in, f_out), "b1": jnp.zeros((f_out,)),
                "w2": _dense(k2, f_out, f_out), "b2": jnp.zeros((f_out,)),
                "eps": jnp.zeros(())}

    @staticmethod
    def f_grad(f_in: int, f_out: int, **_) -> int:
        return f_out

    @staticmethod
    def probe_shape(b, f_in, f_out, **_):
        return (b, f_out)

    @staticmethod
    def full_apply(p: Params, x, ops_: FullGraphOperands, act) -> jax.Array:
        s = kops.spmm_ell(ops_.nbr_ids, ops_.nbr_mask, x)
        m = (1.0 + p["eps"]) * x + s
        h = jax.nn.relu(m @ p["w1"] + p["b1"])
        return act(h @ p["w2"] + p["b2"])

    @staticmethod
    def vq_apply(p: Params, x_b, probe, pack, vq, degrees, cfg, act,
                 f_in: int, f_out: int, inject: bool = True) -> jax.Array:
        ops_, _ = fixed_conv_operands('adj', pack, degrees)
        fcw, gcw = layer_codewords(vq, f_in, cfg)
        s = approx_message_passing(ops_, x_b, fcw, gcw, vq.assignment,
                                   p["w1"], inject)
        m = (1.0 + p["eps"]) * x_b + s
        z = m @ p["w1"] + p["b1"]
        h = jax.nn.relu(z if probe is None else z + probe)
        return act(h @ p["w2"] + p["b2"])


# ===========================================================================
# GAT  (learnable row-normalized convolution, paper Table 1 + App. E tricks)
# ===========================================================================

def _gat_scores(xw: jax.Array, a_dst: jax.Array, a_src: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """xw: [..., H, fh] -> per-head destination/source score halves."""
    return jnp.einsum('...hf,hf->...h', xw, a_dst), \
        jnp.einsum('...hf,hf->...h', xw, a_src)


def _gat_edge_weight(s_dst, s_src):
    """exp(clip(LeakyReLU(s_dst + s_src))) -- Lipschitz-clipped (App. E)."""
    e = jax.nn.leaky_relu(s_dst + s_src, 0.2)
    return jnp.exp(jnp.clip(e, -SCORE_CLIP, SCORE_CLIP))


class GAT:
    name = "gat"
    heads = 4

    @staticmethod
    def init(key, f_in: int, f_out: int, heads: int = 4, **_) -> Params:
        assert f_out % heads == 0
        fh = f_out // heads
        kw, ka, kb = jax.random.split(key, 3)
        return {"w": _dense(kw, f_in, f_out).reshape(f_in, heads, fh),
                "a_dst": 0.1 * jax.random.normal(ka, (heads, fh)),
                "a_src": 0.1 * jax.random.normal(kb, (heads, fh)),
                "b": jnp.zeros((f_out,))}

    @staticmethod
    def f_grad(f_in: int, f_out: int, heads: int = 4, **_) -> int:
        # probe sits at the per-head augmented message level: H * (fh + 1)
        return f_out + heads

    @staticmethod
    def probe_shape(b, f_in, f_out, heads: int = 4, **_):
        return (b, f_out + heads)

    @staticmethod
    def full_apply(p: Params, x, ops_: FullGraphOperands, act) -> jax.Array:
        n, dcap = ops_.nbr_ids.shape
        heads, fh = p["a_dst"].shape
        xw = jnp.einsum('nf,fhe->nhe', x, p["w"])            # [n, H, fh]
        s_dst, s_src = _gat_scores(xw, p["a_dst"], p["a_src"])
        w_edge = _gat_edge_weight(s_dst[:, None, :], s_src[ops_.nbr_ids]
                                  ) * ops_.nbr_mask[..., None]  # [n, D, H]
        w_self = _gat_edge_weight(s_dst, s_src)              # [n, H]
        num = jnp.einsum('ndh,ndhe->nhe', w_edge, xw[ops_.nbr_ids]) \
            + w_self[..., None] * xw
        den = w_edge.sum(axis=1) + w_self                    # [n, H]
        y = num / jnp.maximum(den, 1e-9)[..., None]
        return act(y.reshape(n, heads * fh) + p["b"])

    @staticmethod
    def vq_apply(p: Params, x_b, probe, pack: MinibatchPack,
                 vq: LayerVQState, degrees, cfg: CodebookConfig, act,
                 f_in: int, f_out: int, inject: bool = True) -> jax.Array:
        b = x_b.shape[0]
        heads, fh = p["a_dst"].shape
        # dense f32 reads: GAT mixes branches through the per-head value
        # map, so kernel-side dequant epilogues cannot express its math
        fcw, gcw = layer_codewords(vq, f_in, cfg, dense=True)

        # ---- Eq. 7 backward injection (before anything touches x_b) ----
        # reverse-edge weights  C^h_{j,i} = w(s_dst(j), s_src(i)), with the
        # out-of-batch endpoint j reconstructed from its codewords
        x_rev_hat = jax.lax.stop_gradient(
            reconstruct(fcw, vq.assignment, pack.rev_ids))   # [b, Dr, f_in]
        ghat = jax.lax.stop_gradient(
            reconstruct(gcw, vq.assignment, pack.rev_ids))   # [b, Dr, H*(fh+1)]
        ghat = ghat.reshape(b, -1, heads, fh + 1)[..., :fh]  # value-part only
        xw0 = jnp.einsum('bf,fhe->bhe', x_b, p["w"])
        s_dst0, s_src0 = _gat_scores(xw0, p["a_dst"], p["a_src"])
        xw_rev = jnp.einsum('bdf,fhe->bdhe', x_rev_hat, p["w"])
        s_dst_rev, _ = _gat_scores(xw_rev, p["a_dst"], p["a_src"])
        rev_vals = _gat_edge_weight(s_dst_rev, s_src0[:, None, :]) \
            * jnp.where(pack.rev_pos < 0, pack.rev_mask, 0.0)[..., None]
        rev_vals = jax.lax.stop_gradient(rev_vals)           # [b, Dr, H]
        dr = rev_vals.shape[1]
        # fold heads into the neighbor axis; backward weight has no W factor
        # (probe lives pre-normalization, value space is the xw space) -> the
        # injected grad must be mapped back through W^T per head.  The
        # per-head W map mixes the product-VQ branches, so the lazy
        # codeword-residual form cannot express this tensor: GAT keeps the
        # materialized injection (message_passing.py docstring).
        ghat_x = jnp.einsum('bdhe,fhe->bdhf', ghat, p["w"]
                            ).reshape(b, dr * heads, f_in)
        if inject:
            x_b = inject_context_grad_materialized(
                x_b, rev_vals.transpose(0, 2, 1).reshape(b, heads * dr),
                ghat_x.reshape(b, heads * dr, f_in), None)

        # ---- Eq. 6 forward: exact intra + codeword context, per head ----
        xw = jnp.einsum('bf,fhe->bhe', x_b, p["w"])          # [b, H, fh]
        s_dst, s_src = _gat_scores(xw, p["a_dst"], p["a_src"])
        # in-batch neighbors
        pos = jnp.maximum(pack.nbr_pos, 0)
        in_mask = (pack.nbr_pos >= 0) * pack.nbr_mask
        w_in = _gat_edge_weight(s_dst[:, None, :], s_src[pos]
                                ) * in_mask[..., None]       # [b, D, H]
        xw_in = xw[pos]                                      # [b, D, H, fh]
        # out-of-batch neighbors: reconstruct, transform, score
        x_out_hat = jax.lax.stop_gradient(
            reconstruct(fcw, vq.assignment, pack.nbr_ids))   # [b, D, f_in]
        xw_out = jnp.einsum('bdf,fhe->bdhe', x_out_hat, p["w"])
        _, s_src_out = _gat_scores(xw_out, p["a_dst"], p["a_src"])
        out_mask = (pack.nbr_pos < 0) * pack.nbr_mask
        w_out = _gat_edge_weight(s_dst[:, None, :], s_src_out
                                 ) * out_mask[..., None]
        w_self = _gat_edge_weight(s_dst, s_src)              # [b, H]

        num = jnp.einsum('bdh,bdhe->bhe', w_in, xw_in) \
            + jnp.einsum('bdh,bdhe->bhe', w_out, xw_out) \
            + w_self[..., None] * xw
        den = w_in.sum(1) + w_out.sum(1) + w_self            # [b, H]
        # probe at the augmented (pre-normalization) message level
        m_aug = jnp.concatenate([num, den[..., None]], axis=-1)
        if probe is not None:
            m_aug = m_aug + probe.reshape(b, heads, fh + 1)
        y = m_aug[..., :fh] / jnp.maximum(m_aug[..., fh:], 1e-9)
        return act(y.reshape(b, heads * fh) + p["b"])


# ===========================================================================
# GraphTransformer  (dense learnable convolution, paper Table 5 + App. G)
# ===========================================================================

class GraphTransformer:
    """Global self-attention over ALL nodes each layer.

    Sampling methods cannot scale this (O(n^2) messages, no sparsity to
    sample); VQ-GNN reduces it to attention over b in-batch nodes + k
    codewords (the same machinery the LM-side VQ-Attention uses).
    Requires a full-width codebook (f_prod = f_in); see DESIGN.md.
    """
    name = "transformer"
    heads = 4

    @staticmethod
    def init(key, f_in: int, f_out: int, heads: int = 4, **_) -> Params:
        assert f_out % heads == 0
        dh = f_out // heads
        kq, kk, kv, ko = jax.random.split(key, 4)
        return {"wq": _dense(kq, f_in, f_out).reshape(f_in, heads, dh),
                "wk": _dense(kk, f_in, f_out).reshape(f_in, heads, dh),
                "wv": _dense(kv, f_in, f_out).reshape(f_in, heads, dh),
                "wo": _dense(ko, f_out, f_out), "b": jnp.zeros((f_out,))}

    @staticmethod
    def f_grad(f_in: int, f_out: int, **_) -> int:
        return f_out          # grad codewords at the attention-output level

    @staticmethod
    def probe_shape(b, f_in, f_out, **_):
        return (b, f_out)

    @staticmethod
    def full_apply(p: Params, x, ops_: FullGraphOperands, act) -> jax.Array:
        n = x.shape[0]
        heads, dh = p["wq"].shape[1:]
        q = jnp.einsum('nf,fhe->hne', x, p["wq"]) / jnp.sqrt(dh)
        k = jnp.einsum('nf,fhe->hne', x, p["wk"])
        v = jnp.einsum('nf,fhe->hne', x, p["wv"])
        s = jnp.clip(jnp.einsum('hne,hme->hnm', q, k),
                     -SCORE_CLIP, SCORE_CLIP)
        att = jax.nn.softmax(s, axis=-1)
        y = jnp.einsum('hnm,hme->nhe', att, v).reshape(n, heads * dh)
        return act(y @ p["wo"] + p["b"])

    @staticmethod
    def vq_apply(p: Params, x_b, probe, pack: MinibatchPack,
                 vq: LayerVQState, degrees, cfg: CodebookConfig, act,
                 f_in: int, f_out: int, inject: bool = True) -> jax.Array:
        b = x_b.shape[0]
        heads, dh = p["wq"].shape[1:]
        assert vq.codebook.n_branches == 1, \
            "GraphTransformer needs a full-width codebook (f_prod=f_in)"
        dfcw, dgcw = layer_codewords(vq, f_in, cfg, dense=True)
        fcw, gcw = dfcw[0], dgcw[0]   # [k, f_in], [k, f_out]
        fcw = jax.lax.stop_gradient(fcw)
        mass = out_of_batch_cluster_mass(vq, pack.batch_ids)[0]  # [k]

        # ---- Eq. 7 injection: cluster-level reverse attention weights ----
        kk = fcw.shape[0]
        q_cl = jnp.einsum('kf,fhe->hke', fcw, p["wq"]) / jnp.sqrt(dh)
        k_cl = jnp.einsum('kf,fhe->hke', fcw, p["wk"])
        k_b0 = jnp.einsum('bf,fhe->hbe', x_b, p["wk"])
        s_cc = jnp.clip(jnp.einsum('hke,hue->hku', q_cl, k_cl),
                        -SCORE_CLIP, SCORE_CLIP)
        s_cb = jnp.clip(jnp.einsum('hke,hbe->hkb', q_cl, k_b0),
                        -SCORE_CLIP, SCORE_CLIP)
        # cluster-level row normalizer Z~_v (mass-weighted over clusters +
        # exact over in-batch keys)
        z_cl = jnp.einsum('hku,u->hk', jnp.exp(s_cc),
                          jnp.maximum(mass, 0.0)) \
            + jnp.exp(s_cb).sum(-1)                            # [h, k]
        rev_vals = jnp.exp(s_cb) * (mass[None, :, None] /
                                    jnp.maximum(z_cl, 1e-9)[..., None])
        rev_vals = jax.lax.stop_gradient(
            rev_vals.transpose(2, 0, 1).reshape(b, heads * kk))  # [b, h*k]
        # gradient codewords live at the attention-output (y) level; the
        # value path maps them back to x space per head: W_v,h G~_h.  The
        # receiving "neighbors" are the k clusters -- identical for every
        # row -- so the injection residual is the [h*k, f_in] table itself,
        # not its [b, h*k, f_in] broadcast (table-form injection).
        gcw_h = gcw.reshape(kk, heads, dh)
        ghat_x = jnp.einsum('khe,fhe->hkf', gcw_h, p["wv"])     # [h, k, f_in]
        ghat_x = jax.lax.stop_gradient(ghat_x.reshape(heads * kk, f_in))
        if inject:
            x_b = inject_context_grad_table(x_b, rev_vals, ghat_x, None)

        # ---- Eq. 6 forward: softmax over (b in-batch + k clusters) ----
        q = jnp.einsum('bf,fhe->hbe', x_b, p["wq"]) / jnp.sqrt(dh)
        k_in = jnp.einsum('bf,fhe->hbe', x_b, p["wk"])
        v_in = jnp.einsum('bf,fhe->hbe', x_b, p["wv"])
        k_cw = jnp.einsum('kf,fhe->hke', fcw, p["wk"])
        v_cw = jnp.einsum('kf,fhe->hke', fcw, p["wv"])
        s_in = jnp.clip(jnp.einsum('hbe,hue->hbu', q, k_in),
                        -SCORE_CLIP, SCORE_CLIP)                # [h, b, b]
        s_cw = jnp.clip(jnp.einsum('hbe,hke->hbk', q, k_cw),
                        -SCORE_CLIP, SCORE_CLIP) \
            + jnp.log(jnp.maximum(mass, 1e-9))[None, None, :]   # [h, b, k]
        s_cw = jnp.where(mass[None, None, :] > 0, s_cw, -jnp.inf)
        s = jnp.concatenate([s_in, s_cw], axis=-1)
        att = jax.nn.softmax(s, axis=-1)
        y = jnp.einsum('hbu,hue->bhe', att[..., :b], v_in) \
            + jnp.einsum('hbk,hke->bhe', att[..., b:], v_cw)
        y = y.reshape(b, heads * dh)
        y = y if probe is None else y + probe
        return act(y @ p["wo"] + p["b"])


BACKBONES = {c.name: c for c in [GCN, SAGE, GIN, GAT, GraphTransformer]}
