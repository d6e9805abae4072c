"""Milliseconds the loader's consumer blocks per cluster pulled: the
program's ``loader.refill`` spans in the traced window (waiting on the
reader's prefetch, the device decode, the packing dispatch), summed,
over the refills recorded."""


def read(ctx):
    from repro.core import stats

    records = getattr(stats, "records", None)
    if records is None:
        return None
    refills = [r.ns for r in records() if r.name == "loader.refill"]
    if not refills:
        return None
    return sum(refills) / len(refills) / 1e6
