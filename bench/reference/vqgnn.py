"""Plain reference of VQ-GNN (Ding et al., NeurIPS 2021), in float32
``jax.numpy``, written from the paper and independent of the program.

One mini-batch step of Alg. 1 for the fixed convolutions GCN
(C = D~^-1/2 A~ D~^-1/2) and SAGE-Mean (C1 = I, C2 = D^-1 A):

* forward (Eq. 6): messages from in-batch neighbours exactly, from
  out-of-batch neighbours through their per-branch feature codewords;
* backward (Eq. 7): the transposed out-of-batch messages add the gradient
  codewords of the out-of-batch nodes the batch sends to, times W^T;
* the gradients of the pre-activations (the VQ update's gradient half) are
  read as the cotangents of zero inputs added to each pre-activation;
* RMSprop (alpha 0.99), then Alg. 2 for each layer: EMA whitening moments,
  nearest codeword in whitened (features || gradients) space, EMA cluster
  sizes and sums, codewords that lost their mass re-seeded on the
  worst-quantised rows, and the batch's codeword ids written back.

Inference and serving run the same layer forward without the backward
rule; serving first re-assigns every node by its feature half (Sec. 6).

Sums over neighbours are elementwise f32 loops over the neighbour slots.
Matrix products go through ``mm``: at ``"highest"`` they are float32
products; at ``"high"`` each operand is split into two bfloat16 parts and
three of the four part products are summed (the MXU's three-pass mode,
written out so that it means the same on every backend).  The ``"high"``
reference is the control that has to fail the comparison.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# matrix products at a stated precision
# ---------------------------------------------------------------------------

def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _mm3(a, b):
    ah, al = _split(a)
    bh, bl = _split(b)

    def d(x, y):
        return jnp.matmul(x, y, precision=HIGHEST)
    return d(ah, bh) + (d(ah, bl) + d(al, bh))


@jax.custom_vjp
def _mm_high(a, b):
    return _mm3(a, b)


def _mm_high_fwd(a, b):
    return _mm3(a, b), (a, b)


def _mm_high_bwd(res, g):
    a, b = res
    return _mm3(g, b.T), _mm3(a.T, g)


_mm_high.defvjp(_mm_high_fwd, _mm_high_bwd)


def mm_for(precision: str):
    if precision == "highest":
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    if precision == "high":
        return _mm_high
    raise ValueError(f"unknown reference precision {precision!r}")


# ---------------------------------------------------------------------------
# graph tables
# ---------------------------------------------------------------------------

class Tables(NamedTuple):
    nbr: jax.Array     # [n, D] in-neighbours (0 on padding)
    nmask: jax.Array   # [n, D]
    rev: jax.Array     # [n, D] out-neighbours
    rmask: jax.Array   # [n, D]
    deg: jax.Array     # [n] in-degree


def _ell(keys: np.ndarray, vals: np.ndarray, n: int, width: int):
    order = np.lexsort((vals, keys))
    k, v = keys[order], vals[order]
    first = np.searchsorted(k, k, side="left")
    rank = np.arange(len(k)) - first
    ids = np.zeros((n, width), np.int32)
    mask = np.zeros((n, width), np.float32)
    ids[k, rank] = v
    mask[k, rank] = 1.0
    return ids, mask


def tables(src: np.ndarray, dst: np.ndarray, n: int) -> Tables:
    """Padded neighbour tables of a deduplicated directed edge list."""
    indeg = np.bincount(dst, minlength=n)
    outdeg = np.bincount(src, minlength=n)
    width = int(max(indeg.max(initial=0), outdeg.max(initial=0), 1))
    nbr, nmask = _ell(dst, src, n, width)
    rev, rmask = _ell(src, dst, n, width)
    return Tables(jnp.asarray(nbr), jnp.asarray(nmask), jnp.asarray(rev),
                  jnp.asarray(rmask), jnp.asarray(indeg.astype(np.float32)))


class Batch(NamedTuple):
    ids: jax.Array
    nbr: jax.Array
    nmask: jax.Array
    npos: jax.Array
    rev: jax.Array
    rmask: jax.Array
    rpos: jax.Array


def batch(t: Tables, ids) -> Batch:
    """A batch's neighbour rows; positions of in-batch neighbours (a node
    listed twice sits at its first slot), -1 for the others."""
    b = ids.shape[0]
    n = t.nbr.shape[0]
    slot = jnp.full((n,), b, jnp.int32).at[ids].min(
        jnp.arange(b, dtype=jnp.int32))
    slot = jnp.where(slot == b, -1, slot)
    nbr, nmask, rev, rmask = t.nbr[ids], t.nmask[ids], t.rev[ids], \
        t.rmask[ids]
    npos = jnp.where(nmask != 0, slot[nbr], -1)
    rpos = jnp.where(rmask != 0, slot[rev], -1)
    return Batch(ids, nbr, nmask, npos, rev, rmask, rpos)


def edge_values(kind: str, bt: Batch, deg):
    """(in-batch, out-of-batch, reverse out-of-batch, self) values of the
    batch rows of the convolution matrix."""
    di = deg[bt.ids]
    if kind == "gcn":
        vals = bt.nmask / jnp.sqrt((di + 1.0)[:, None] * (deg[bt.nbr] + 1.0))
        rev = bt.rmask / jnp.sqrt((deg[bt.rev] + 1.0) * (di + 1.0)[:, None])
        self_v = 1.0 / (di + 1.0)
    elif kind == "sage":
        vals = bt.nmask / jnp.maximum(di, 1.0)[:, None]
        rev = bt.rmask / jnp.maximum(deg[bt.rev], 1.0)
        self_v = jnp.zeros_like(di)
    else:
        raise ValueError(kind)
    return (jnp.where(bt.npos >= 0, vals, 0.0),
            jnp.where(bt.npos < 0, vals, 0.0),
            jnp.where(bt.rpos < 0, rev, 0.0), self_v)


# ---------------------------------------------------------------------------
# message passing
# ---------------------------------------------------------------------------

def aggregate(idx, vals, table):
    """sum_d vals[:, d] * table[idx[:, d]], one neighbour slot at a time."""
    def body(acc, col):
        i, v = col
        return acc + v[:, None] * table[i], None
    acc0 = jnp.zeros((idx.shape[0], table.shape[1]), jnp.float32)
    out, _ = jax.lax.scan(body, acc0, (idx.T, vals.T))
    return out


def codeword_rows(cw, assign, ids):
    """Branch-concatenated codewords of nodes ``ids``: [len(ids), nb*fb]."""
    a = assign[:, ids]                                   # [nb, m]
    rows = jax.vmap(lambda c, i: c[i])(cw, a)            # [nb, m, fb]
    return jnp.transpose(rows, (1, 0, 2)).reshape(ids.shape[0], -1)


def context(ids, vals, cw, assign):
    """sum_d vals[:, d] * codewords(ids[:, d])."""
    def body(acc, col):
        i, v = col
        return acc + v[:, None] * codeword_rows(cw, assign, i), None
    f = cw.shape[0] * cw.shape[2]
    acc0 = jnp.zeros((ids.shape[0], f), jnp.float32)
    out, _ = jax.lax.scan(body, acc0, (ids.T, vals.T))
    return out


def unwhitened(st, fi: int):
    """(feature codewords, gradient codewords), un-whitened."""
    nb = st["codewords_w"].shape[0]
    fb = fi // nb
    cw = st["codewords_w"] * jnp.sqrt(st["var"][:, None, :] + EPS) \
        + st["mean"][:, None, :]
    return cw[:, :, :fb], cw[:, :, fb:]


EPS = 1e-5


def make_inject(mm):
    """Identity forward; the backward adds the Eq. 7 out-of-batch gradient
    messages  (sum_d rev_vals * G~[rev_ids]) @ W^T."""
    @jax.custom_vjp
    def inject(x, rev_ids, rev_vals, gcw, assign, w):
        return x

    def fwd(x, rev_ids, rev_vals, gcw, assign, w):
        return x, (rev_ids, rev_vals, gcw, assign, w)

    def bwd(res, g):
        rev_ids, rev_vals, gcw, assign, w = res
        phantom = mm(context(rev_ids, rev_vals, gcw, assign), w.T)
        return (g + phantom, None, jnp.zeros_like(rev_vals),
                jnp.zeros_like(gcw), None, jnp.zeros_like(w))
    inject.defvjp(fwd, bwd)
    return inject


def layer(backbone: str, mm, inject, p, x_b, bt: Batch, st, deg, fi: int,
          last: bool, probe=None, backward_rule: bool = True):
    fcw, gcw = unwhitened(st, fi)
    fcw, gcw = jax.lax.stop_gradient(fcw), jax.lax.stop_gradient(gcw)
    assign = st["assignment"]
    in_v, out_v, rev_v, self_v = edge_values(backbone, bt, deg)
    w = p["w"] if backbone == "gcn" else p["w2"]
    xi = x_b
    if backward_rule:
        xi = inject(x_b, bt.rev, rev_v, gcw, assign,
                    jax.lax.stop_gradient(w))
    m = aggregate(jnp.maximum(bt.npos, 0), in_v, xi) \
        + context(bt.nbr, out_v, fcw, assign)
    if backbone == "gcn":
        z = mm(m + self_v[:, None] * x_b, w) + p["b"]
    else:
        z = mm(x_b, p["w1"]) + mm(m, w) + p["b"]
    if probe is not None:
        z = z + probe
    return z if last else jax.nn.relu(z)


# ---------------------------------------------------------------------------
# Alg. 2, the codebook update
# ---------------------------------------------------------------------------

def _split_branches(x, nb):
    b, f = x.shape
    return jnp.transpose(x.reshape(b, nb, f // nb), (1, 0, 2))


def nearest(mm, v, cw):
    """Per branch: nearest codeword by squared distance, and the distance.
    v [nb, m, f], cw [nb, k, f]."""
    def one(args):
        vv, cc = args
        d = jnp.sum(cc * cc, axis=1)[None, :] - 2.0 * mm(vv, cc.T)
        i = jnp.argmin(d, axis=1).astype(jnp.int32)
        dmin = jnp.take_along_axis(d, i[:, None], 1)[:, 0]
        return i, jnp.maximum(dmin + jnp.sum(vv * vv, axis=1), 0.0)
    return jax.lax.map(one, (v, cw))


def vq_update(mm, st, feats, grads, cb: dict):
    nb = st["codewords_w"].shape[0]
    k = st["codewords_w"].shape[1]
    v = jnp.concatenate([_split_branches(feats, nb),
                         _split_branches(grads, nb)], axis=-1)
    b = v.shape[1]
    beta, gamma = cb["beta"], cb["gamma"]
    mean = st["mean"] * beta + jnp.mean(v, axis=1) * (1.0 - beta)
    var = st["var"] * beta + jnp.var(v, axis=1) * (1.0 - beta)
    vw = (v - mean[:, None, :]) * jax.lax.rsqrt(var[:, None, :] + EPS)
    idx, qerr = nearest(mm, vw, st["codewords_w"])
    counts = jax.vmap(lambda i: jnp.zeros((k,), jnp.float32).at[i].add(1.0))(
        idx)
    sums = jax.vmap(lambda i, x: jnp.zeros((k, x.shape[1]), jnp.float32)
                    .at[i].add(x))(idx, vw)
    size = st["cluster_size"] * gamma + counts * (1.0 - gamma)
    csum = st["cluster_sum"] * gamma + sums * (1.0 - gamma)
    cw = csum / jnp.maximum(size, EPS)[..., None]
    cw = jnp.where((size > 1e-3)[..., None], cw, st["codewords_w"])
    n_rev = min(k, b)
    _, worst = jax.lax.top_k(qerr, n_rev)
    worst_rows = jax.vmap(lambda x, w: x[w])(vw, worst)
    dead = size < cb["revive_threshold"]
    rank = jnp.clip(jnp.cumsum(dead.astype(jnp.int32), axis=1) - 1, 0,
                    n_rev - 1)
    repl = jax.vmap(lambda r, i: r[i])(worst_rows, rank)
    cw = jnp.where(dead[..., None], repl, cw)
    size = jnp.where(dead, 1.0, size)
    csum = jnp.where(dead[..., None], repl, csum)
    return dict(st, codewords_w=cw, cluster_size=size, cluster_sum=csum,
                mean=mean, var=var, step=st["step"] + 1), idx


def write_back(st, ids, new):
    k = st["codewords_w"].shape[1]
    old = st["assignment"][:, ids]

    def hist(a, w):
        return jax.vmap(lambda i: jnp.zeros((k,), jnp.float32)
                        .at[i].add(w))(a)
    counts = st["counts"] + hist(new, 1.0) - hist(old, 1.0)
    return dict(st, assignment=st["assignment"].at[:, ids].set(new),
                counts=counts)


# ---------------------------------------------------------------------------
# training, inference, serving
# ---------------------------------------------------------------------------

def dims(model: dict) -> list[tuple[int, int]]:
    out, f = [], model["f_in"]
    for l in range(model["layers"]):
        fo = model["classes"] if l == model["layers"] - 1 else model["hidden"]
        out.append((f, fo))
        f = fo
    return out


@functools.partial(jax.jit, static_argnames=("model", "precision"))
def train_step(params, states, opt_v, ids, smask, x, labels, train_mask,
               t: Tables, model, precision: str):
    """One Alg. 1 step.  Returns (params, states, opt_v, loss)."""
    model = _thaw(model)
    mm = mm_for(precision)
    inject = make_inject(mm)
    bt = batch(t, ids)
    dd = dims(model)
    lmask = train_mask[ids] * smask
    den = jnp.maximum(jnp.sum(lmask), 1.0)

    def loss_fn(params, probes):
        h, acts = x[ids], []
        for l, (fi, _) in enumerate(dd):
            acts.append(h)
            h = layer(model["backbone"], mm, inject, params[l], h, bt,
                      states[l], t.deg, fi, l == len(dd) - 1, probes[l])
        logp = jax.nn.log_softmax(h, axis=-1)
        per = -jnp.take_along_axis(logp, labels[ids][:, None], 1)[:, 0]
        return jnp.sum(per * lmask) / den, acts

    probes = [jnp.zeros((ids.shape[0], fo), jnp.float32) for _, fo in dd]
    (loss, acts), (gp, gz) = jax.value_and_grad(
        loss_fn, argnums=(0, 1), has_aux=True)(params, probes)
    alpha, lr, eps = model["rms_alpha"], model["lr"], model["rms_eps"]
    new_v = jax.tree_util.tree_map(lambda v, g: alpha * v + (1 - alpha) * g * g,
                                   opt_v, gp)
    new_p = jax.tree_util.tree_map(
        lambda p, g, v: p - lr * g / (jnp.sqrt(v) + eps), params, gp, new_v)
    cb = model["codebook"]
    new_states = []
    for l, st in enumerate(states):
        st2, idx = vq_update(mm, st, acts[l], gz[l], cb)
        new_states.append(write_back(st2, ids, idx))
    return new_p, new_states, new_v, loss


def train_epoch(params, states, ids, smask, x, labels, train_mask,
                t: Tables, model: dict, precision: str):
    """The steps of one epoch (``ids``/``smask`` [S, b]), one jitted step
    at a time.  Returns (params, states, RMSprop second moments, losses)."""
    key = _freeze(model)
    opt_v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for s in range(ids.shape[0]):
        params, states, opt_v, loss = train_step(
            params, states, opt_v, ids[s], smask[s], x, labels, train_mask,
            t, key, precision)
        losses.append(loss)
    return params, states, opt_v, jnp.stack(losses)


@functools.partial(jax.jit, static_argnames=("model", "precision", "l"))
def infer_batch(p, h_all, st, ids, t: Tables, model, precision: str, l: int):
    model = _thaw(model)
    mm = mm_for(precision)
    fi, _ = dims(model)[l]
    return layer(model["backbone"], mm, None, p, h_all[ids], batch(t, ids),
                 st, t.deg, fi, l == model["layers"] - 1,
                 backward_rule=False)


@functools.partial(jax.jit, static_argnames=("precision", "block"))
def assign_features(st, acts, fi: int, precision: str, block: int = 8192):
    """Every node's nearest codeword by the feature half (Sec. 6)."""
    del fi
    mm = mm_for(precision)
    nb = st["codewords_w"].shape[0]
    n, f = acts.shape
    fb = f // nb
    pad = (-n) % block
    a = jnp.pad(acts, ((0, pad), (0, 0)))
    v = _split_branches(a, nb)                              # [nb, n', fb]
    vw = (v - st["mean"][:, None, :fb]) * jax.lax.rsqrt(
        st["var"][:, None, :fb] + EPS)
    vw = vw.reshape(nb, -1, block, fb).transpose(1, 0, 2, 3)
    cw = st["codewords_w"][:, :, :fb]
    idx = jax.lax.map(lambda blk: nearest(mm, blk, cw)[0], vw)
    return idx.transpose(1, 0, 2).reshape(nb, -1)[:, :n]


def infer_sweep(params, states, slices, smask, x, t: Tables, model: dict,
                precision: str, refresh: bool = False):
    """Layer-synchronous inference over all nodes in the batches ``slices``
    [S, b] (tail slots with ``smask`` 0 are discarded).  With ``refresh``
    each layer first re-assigns every node by its feature half.  Returns
    (outputs [n, classes], the states used)."""
    key = _freeze(model)
    n = x.shape[0]
    h = x
    used = []
    for l, (fi, fo) in enumerate(dims(model)):
        st = states[l]
        if refresh:
            st = dict(st, assignment=assign_features(st, h, fi, precision))
        used.append(st)
        out = jnp.zeros((n + 1, fo), jnp.float32)
        for s in range(slices.shape[0]):
            ids = slices[s]
            y = infer_batch(params[l], h, st, ids, t, key, precision, l)
            dst = jnp.where(smask[s] > 0, ids, n)
            out = out.at[dst].set(y)
        h = out[:n]
    return h, used


@functools.partial(jax.jit, static_argnames=("model", "precision"))
def serve_step(params, states, ids, x, t: Tables, model, precision: str):
    """All layers for one serve batch of node ids (each layer feeds the
    next with the batch's own outputs)."""
    model = _thaw(model)
    mm = mm_for(precision)
    bt = batch(t, ids)
    h = x[ids]
    dd = dims(model)
    for l, (fi, _) in enumerate(dd):
        h = layer(model["backbone"], mm, None, params[l], h, bt, states[l],
                  t.deg, fi, l == len(dd) - 1, backward_rule=False)
    return h


def _freeze(model: dict):
    """Hashable form of the model dict for jit's static arguments."""
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in model.items()))


def _thaw(frozen) -> dict:
    return {k: _thaw(v) if isinstance(v, tuple) else v for k, v in frozen}
