"""Share of the producers' time spent building (compressing) pages:
``WriterStats.compress_ns`` over producers x window.  ``compress_ns`` sums
per-page build time over every thread that sealed, so it is a CPU-time
view: it can exceed the seal wall time when a compression pool works."""


def read(ctx):
    w = ctx.window
    if not w.get("entries"):
        return None
    return 100.0 * w["compress_ns"] / (w["producers"] * w["window_s"] * 1e9)
