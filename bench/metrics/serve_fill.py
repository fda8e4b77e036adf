"""Share of the serve steps' id slots that held a requested id, counted by
the harness's micro-batching loop over the whole window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("slots"):
        return None
    return 100.0 * c["real_slots"] / c["slots"]
