"""Share of the producers' time spent waiting for the writer's
reservation lock (``WriterStats`` lock ``wait_ns`` over producers x
window): the paper's contention diagnosis."""


def read(ctx):
    w = ctx.window
    if not w.get("entries"):
        return None
    return 100.0 * w["lock_wait_ns"] / (w["producers"] * w["window_s"] * 1e9)
