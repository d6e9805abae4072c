"""Share of the producers' time spent blocked on storage backpressure
(``WriterStats.io_stall_ns`` over producers x window): producers waiting
for the I/O engine to drain before they may queue another cluster."""


def read(ctx):
    w = ctx.window
    if not w.get("entries"):
        return None
    return 100.0 * w["io_stall_ns"] / (w["producers"] * w["window_s"] * 1e9)
