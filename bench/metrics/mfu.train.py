"""Model operations of the traced window's training steps over the traced
window and the chip's bf16 peak (``kernels/train_step.py``)."""


def read(ctx):
    t, drv, c = ctx["trace"], ctx["run"], ctx["counters"]
    if t is None or t.window_s <= 0 or not c.get("steps"):
        return None
    m = drv.model
    dims = drv.dims()
    step = ctx["kernel"]("train_step").count(m["backbone"], dims, drv.b,
                                             drv.width, m["k"])
    return 100.0 * step * c["steps"] / t.window_s / ctx["peaks"]["flops_bf16"]
