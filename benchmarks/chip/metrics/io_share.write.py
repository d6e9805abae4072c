"""Share of the producers' time spent inside ``pwrite``/``pwritev``
(``WriterStats.io_ns`` over producers x window).  With the writer's
default ``io_inflight_bytes`` of 0 every cluster is written inside its
commit, so this is where storage costs the producers; the sync at close
is outside it and shows in the window."""


def read(ctx):
    w = ctx.window
    if not w.get("entries"):
        return None
    return 100.0 * w["io_ns"] / (w["producers"] * w["window_s"] * 1e9)
