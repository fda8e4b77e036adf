"""Share of its roofline that the codeword context term reaches in
training: the least time of every context term of the traced steps
(``kernels/context.py``) over the summed device time of the kernels that
compute them.  Per step the forward pass has one context term per layer
(feature codewords) and the backward one per layer past the first (the
Eq. 7 gradient codewords).  A term runs as the fused kernel
(``context_ell_pallas``, one launch) or, past the dispatch's VMEM budget,
as the resident SpMM kernel once per branch (``spmm_ell_pallas``), which
in training computes nothing else.  Fewer launches than terms read
nothing: some term then ran outside the kernels timed."""

from bench.harness.weights import branch_layout

KERNELS = ("context_ell_pallas", "spmm_ell_pallas")


def read(ctx):
    t, drv, c = ctx["trace"], ctx["run"], ctx["counters"]
    if t is None or not c.get("steps"):
        return None
    m = drv.model
    terms = []              # (branches, width per branch) of each term
    for l, (fi, fo) in enumerate(drv.dims()):
        nb, fb, gb = branch_layout(fi, fo, m["f_prod"])
        terms.append((nb, fb))
        if l > 0:
            terms.append((nb, gb))
    secs, launches = t.kernel(lambda s: any(k in s for k in KERNELS))
    steps = c["steps"]
    if secs <= 0 or launches < steps * len(terms):
        return None
    pk = ctx["peaks"]
    count = ctx["kernel"]("context").count
    least = 0.0
    for nb, fb in terms:
        ops, nbytes = count(drv.b, drv.width, nb, fb, drv.n, m["k"])
        least += max(ops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least * steps / secs
