"""Seeded weights and VQ state, made on the device in one jitted call.

The arrays follow the published VQ-GNN initialisation (App. F): dense
weights N(0, 1/f_in), zero biases, product-VQ codebooks of ``k`` whitened
codewords per branch at 0.02 N(0, 1) with the gradient half at zero, unit
cluster sizes, zero mean and unit variance, and a uniformly random codeword
id per node and branch.  They are plain dicts of arrays: the reference takes
them as they are, and :func:`to_program` packs the same arrays into the
program's containers.
"""
from __future__ import annotations

import math


def branch_layout(f_feat: int, f_grad: int, f_prod: int
                  ) -> tuple[int, int, int]:
    """(branches, feature dims per branch, gradient dims per branch): the
    largest common divisor of both widths that keeps at least ``f_prod``
    dims per branch on each side."""
    cap = min(max(1, f_feat // f_prod), max(1, f_grad // f_prod))
    g = math.gcd(f_feat, f_grad)
    nb = max(d for d in range(1, g + 1) if g % d == 0 and d <= cap)
    return nb, f_feat // nb, f_grad // nb


def layer_dims(model: dict) -> list[tuple[int, int]]:
    dims, f = [], model["f_in"]
    for l in range(model["layers"]):
        fo = model["classes"] if l == model["layers"] - 1 else model["hidden"]
        dims.append((f, fo))
        f = fo
    return dims


def make(model: dict, n: int, key):
    """Params and per-layer VQ state for ``model`` over ``n`` nodes."""
    import jax
    import jax.numpy as jnp

    k = model["k"]
    dims = layer_dims(model)
    names = ["w"] if model["backbone"] == "gcn" else ["w1", "w2"]

    @jax.jit
    def build(key):
        params, states = [], []
        for l, (fi, fo) in enumerate(dims):
            kl = jax.random.fold_in(key, l)
            kw, kc, ka = jax.random.split(kl, 3)
            p = {nm: jax.random.normal(kk, (fi, fo), jnp.float32)
                 / math.sqrt(fi)
                 for nm, kk in zip(names, jax.random.split(kw, len(names)))}
            p["b"] = jnp.zeros((fo,), jnp.float32)
            params.append(p)
            nb, fb, gb = branch_layout(fi, fo, model["f_prod"])
            cw = 0.02 * jax.random.normal(kc, (nb, k, fb + gb), jnp.float32)
            cw = cw.at[:, :, fb:].set(0.0)
            assign = jax.random.randint(ka, (nb, n), 0, k, jnp.int32)
            counts = jax.vmap(lambda a: jnp.zeros((k,), jnp.float32)
                              .at[a].add(1.0))(assign)
            states.append({
                "codewords_w": cw,
                "cluster_size": jnp.ones((nb, k), jnp.float32),
                "cluster_sum": cw,
                "mean": jnp.zeros((nb, fb + gb), jnp.float32),
                "var": jnp.ones((nb, fb + gb), jnp.float32),
                "step": jnp.zeros((), jnp.int32),
                "assignment": assign,
                "counts": counts,
            })
        return params, states

    return build(key)


def to_program(states: list[dict]):
    """The same arrays as the program's per-layer ``LayerVQState``s."""
    from repro.core.codebook import CodebookState
    from repro.core.conv import LayerVQState
    return [LayerVQState(
        CodebookState(s["codewords_w"], s["cluster_size"], s["cluster_sum"],
                      s["mean"], s["var"], s["step"]),
        s["assignment"], s["counts"]) for s in states]

