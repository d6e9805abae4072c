"""Host milliseconds per loader batch, refills aside: the median self
time of the program's ``loader.next`` spans (each less its
``loader.refill`` children) in the traced window: the per-batch
dispatch of the device loader."""

import statistics
from collections import defaultdict


def read(ctx):
    from repro.core import stats

    records = getattr(stats, "records", None)
    if records is None:
        return None
    recs = records()
    refill = defaultdict(int)
    for r in recs:
        if r.name == "loader.refill":
            refill[r.parent] += r.ns
    own = [r.ns - refill[r.id] for r in recs if r.name == "loader.next"]
    if not own:
        return None
    return statistics.median(own) / 1e6
