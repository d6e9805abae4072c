"""Share of the producers' time spent appending to their cluster
builders: the program's ``writer.fill`` spans in the traced window,
summed, over producers x the time they had to fill in it, that is the
traced window less its ``writer.close`` spans (the trace runs on
through the close, when the producers are done, so a longer fsync
would otherwise read as less fill)."""


def read(ctx):
    from repro.core import stats

    records = getattr(stats, "records", None)
    if records is None or ctx.trace is None:
        return None
    recs = records()
    fill_ns = sum(r.ns for r in recs if r.name == "writer.fill")
    close_ns = sum(r.ns for r in recs if r.name == "writer.close")
    open_ns = ctx.trace.window_s * 1e9 - close_ns
    if not fill_ns or open_ns <= 0:
        return None
    return 100.0 * fill_ns / (ctx.traffic["producers"] * open_ns)
