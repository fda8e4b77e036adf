"""Share of its roofline that the HBM SpMM kernel reaches in training: the
least time of the in-batch SpMMs of the traced steps (``kernels/
spmm_hbm.py``, the larger of operations over peak FLOP/s and bytes over
peak bandwidth) over the summed device time of the kernel's launches.  One
launch per layer and step is expected; any other count reads nothing."""

KERNEL = "spmm_ell_hbm_pallas"


def read(ctx):
    t, drv, c = ctx["trace"], ctx["run"], ctx["counters"]
    if t is None or not c.get("steps"):
        return None
    secs, launches = t.kernel(lambda s: KERNEL in s)
    dims = drv.dims()
    if launches != c["steps"] * len(dims) or secs <= 0:
        return None
    pk = ctx["peaks"]
    count = ctx["kernel"]("spmm_hbm").count
    least = 0.0
    for fi, _ in dims:
        ops, nbytes = count(drv.b, drv.width, fi, drv.b)
        least += max(ops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least * c["steps"] / secs
