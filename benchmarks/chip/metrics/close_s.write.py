"""Seconds in the writer's ``close()``: the longest ``writer.close`` span
the program recorded in the traced window (the producers' last clusters
sealed and committed, the drain, the footer and the fsync)."""


def read(ctx):
    from repro.core import stats

    records = getattr(stats, "records", None)
    if records is None:
        return None
    closes = [r.ns for r in records() if r.name == "writer.close"]
    if not closes:
        return None
    return max(closes) / 1e9
