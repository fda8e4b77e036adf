"""Batched LM serving with VQ-compressed KV cache vs exact cache.

The inference-side payoff of the paper: the KV state per sequence is
O(k + W) instead of O(t) -- constant memory, constant per-token latency
regardless of context length.

    PYTHONPATH=src python examples/serve_lm.py --tokens 64 --batch 4
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro import hostenv
from repro.models.lm import init_lm, init_serve_cache, serve_step


def cache_bytes(cache) -> int:
    return sum(np.asarray(x).nbytes
               for x in jax.tree_util.tree_leaves(cache))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--context", type=int, default=4096,
                    help="pre-allocated context length for the exact cache")
    args = ap.parse_args()
    hostenv.enable_compile_cache()

    base = ArchConfig(name="serve-demo", family="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=512,
                      vocab=2048, remat=False, dtype="float32")
    params = init_lm(jax.random.PRNGKey(0), base)

    step = jax.jit(lambda p, t, c: serve_step(p, t, c, base))
    vq_cfg = base.with_vq(k=128, window=64)
    step_vq = jax.jit(lambda p, t, c: serve_step(p, t, c, vq_cfg))

    for name, cfg, fn in [("exact-kv", base, step),
                          ("vq-kv", vq_cfg, step_vq)]:
        cache = init_serve_cache(cfg, args.batch, args.context)
        tok = jnp.zeros((args.batch, 1), jnp.int32)
        logits, cache = fn(params, tok, cache)  # compile
        t0 = time.time()
        outs = []
        for _ in range(args.tokens):
            logits, cache = fn(params, tok, cache)
            tok = jnp.argmax(logits, -1)[:, None]
            outs.append(np.asarray(tok[:, 0]))
        dt = time.time() - t0
        tps = args.tokens * args.batch / dt
        print(f"{name:9s}: {tps:8.1f} tok/s   cache "
              f"{cache_bytes(cache)/2**20:7.2f} MB   "
              f"sample: {[int(o[0]) for o in outs[:8]]}")
    print("\nvq-kv cache size is independent of --context; exact-kv grows "
          "linearly with it.")


if __name__ == "__main__":
    main()
