"""Times the fused context kernel (``context_ell_pallas``) alone on a TPU.

For each term shape ``NBxFxK`` (``NB`` branches of ``[K, F]`` codewords,
read through ``--deg`` slots per row over ``--rows`` rows of a graph of
``--nodes`` nodes, random ids) and each inner form it prints one JSON line:
the median of ``--reps`` calls of the whole term in ms, the XLA assignment
gather ahead of the kernel timed alone, ms per branch without that gather,
the compile time and the largest error against ``ref.context_ell`` over
the largest magnitude of the answer.

Forms: ``auto`` is ``context_ell_pallas`` as dispatched by shape (any
checkout of the repository has it); ``lookup`` and ``onehot`` force the
inner form of a kernel that has both.  The two forms' times per branch set
``context_ell.LOOKUP_COLS``.  Run on a chip, from the repository root::

    PYTHONPATH=src python benchmarks/context_kernel_chip.py \\
        --forms lookup,onehot --shapes 32x4x1024,1x128x1024 \\
        --out context_kernel.jsonl

``--interpret`` runs the kernels in interpret mode (a CPU smoke test at a
small ``--rows``); its times are not device times.
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.kernels import context_ell, ref


def _median_ms(fn, *args, reps: int) -> float:
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def _term(fn_for_form, form, nb, f_blk, k, args, reps, gather_ms):
    fn = fn_for_form(form)
    t0 = time.perf_counter()
    got = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    want = ref.context_ell(*args)
    ms = _median_ms(fn, *args, reps=reps)
    return {"form": form, "nb": nb, "f_blk": f_blk, "k": k, "ms": ms,
            "gather_ms": gather_ms, "ms_per_branch": (ms - gather_ms) / nb,
            "compile_s": compile_s,
            "rel_err": float(jnp.abs(got - want).max()
                             / jnp.abs(want).max())}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--shapes", required=True,
                   help="comma-separated NBxFxK term shapes")
    p.add_argument("--forms", default="auto",
                   help="comma-separated forms: auto, lookup, onehot")
    p.add_argument("--rows", type=int, default=42_336)
    p.add_argument("--deg", type=int, default=32)
    p.add_argument("--nodes", type=int, default=169_343)
    p.add_argument("--reps", type=int, default=10)
    p.add_argument("--tag", default="")
    p.add_argument("--out", default=None, help="append JSON lines here")
    p.add_argument("--interpret", action="store_true")
    a = p.parse_args(argv)

    def fn_for_form(form):
        if form == "auto":
            return jax.jit(functools.partial(
                context_ell.context_ell_pallas, interpret=a.interpret))
        return jax.jit(functools.partial(
            context_ell._context_ell, cw_scale=None, w_t=None, bb=128,
            interpret=a.interpret, lookup=form == "lookup"))

    gather = jax.jit(lambda assign, ids: assign[:, ids.T])
    out = open(a.out, "a") if a.out else None
    for shape in a.shapes.split(","):
        nb, f_blk, k = (int(v) for v in shape.split("x"))
        ks = jax.random.split(jax.random.PRNGKey(nb * 7919 + f_blk * 31 + k),
                              4)
        args = (jax.random.randint(ks[0], (a.rows, a.deg), 0, a.nodes),
                jax.random.uniform(ks[1], (a.rows, a.deg)),
                jax.random.randint(ks[2], (nb, a.nodes), 0, k),
                jax.random.normal(ks[3], (nb, k, f_blk)))
        jax.block_until_ready(gather(args[2], args[0]))
        gather_ms = _median_ms(gather, args[2], args[0], reps=a.reps)
        for form in a.forms.split(","):
            row = {"tag": a.tag, "rows": a.rows, "deg": a.deg,
                   **_term(fn_for_form, form, nb, f_blk, k, args, a.reps,
                           gather_ms),
                   "auto_lookup": (context_ell.uses_lookup(k, f_blk)
                                   if hasattr(context_ell, "uses_lookup")
                                   else None)}
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()


if __name__ == "__main__":
    main()
