"""Optimizers as pure pytree transforms (no external deps).

Adam + RMSprop (the paper's App. E: the EMA-smoothed gradient statistics of
VQ-GNN interact badly with Adam's cumulative moments -- RMSprop is the
prescribed optimizer for VQ-GNN; Adam is used for the baselines), each with
a constant learning rate.

States are pytrees mirroring the params, so the data-parallel executors
place them exactly as they place the params.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

PyTree = Any


class OptState(NamedTuple):
    step: jax.Array
    mu: PyTree        # first moment (Adam) / unused zeros (RMSprop)
    nu: PyTree        # second moment


class Optimizer(NamedTuple):
    init: Callable[[PyTree], OptState]
    update: Callable[[PyTree, OptState, PyTree], tuple[PyTree, OptState]]


def _zeros_like_f32(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def adam(lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8) -> Optimizer:
    def init(params):
        return OptState(jnp.zeros((), jnp.int32), _zeros_like_f32(params),
                        _zeros_like_f32(params))

    def update(grads, state, params):
        step = state.step + 1
        t = step.astype(jnp.float32)
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)

        def upd(g, m, v, p):
            g32 = g.astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * g32
            v2 = b2 * v + (1 - b2) * g32 * g32
            delta = lr_t * m2 / (jnp.sqrt(v2) + eps)
            return (p.astype(jnp.float32) - delta).astype(p.dtype), m2, v2

        # three passes (XLA CSEs the shared math); tuple-unzip via tree_map
        # is unsafe because NamedTuple params are themselves tuples
        new_p = jax.tree_util.tree_map(
            lambda g, m, v, pp: upd(g, m, v, pp)[0],
            grads, state.mu, state.nu, params)
        new_m = jax.tree_util.tree_map(
            lambda g, m, v, pp: upd(g, m, v, pp)[1],
            grads, state.mu, state.nu, params)
        new_v = jax.tree_util.tree_map(
            lambda g, m, v, pp: upd(g, m, v, pp)[2],
            grads, state.mu, state.nu, params)
        return new_p, OptState(step, new_m, new_v)

    return Optimizer(init, update)


def rmsprop(lr: float = 3e-3, alpha: float = 0.99,
            eps: float = 1e-8) -> Optimizer:
    """RMSprop(alpha=0.99), the paper's optimizer for VQ-GNN (App. F)."""
    def init(params):
        return OptState(jnp.zeros((), jnp.int32), _zeros_like_f32(params),
                        _zeros_like_f32(params))

    def update(grads, state, params):
        step = state.step + 1

        def upd(g, v, p):
            g32 = g.astype(jnp.float32)
            v2 = alpha * v + (1 - alpha) * g32 * g32
            delta = lr * g32 / (jnp.sqrt(v2) + eps)
            return (p.astype(jnp.float32) - delta).astype(p.dtype), v2

        new_p = jax.tree_util.tree_map(
            lambda g, v, pp: upd(g, v, pp)[0], grads, state.nu, params)
        new_v = jax.tree_util.tree_map(
            lambda g, v, pp: upd(g, v, pp)[1], grads, state.nu, params)
        return new_p, OptState(step, state.mu, new_v)

    return Optimizer(init, update)
