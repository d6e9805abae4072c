"""Host milliseconds of Python per train step outside the loss fetch:
the median, over the ``train.step`` spans the program recorded in the
traced window, of the step's time less its ``train.loss_wait`` child
(drawing and placing the batch, dispatching the step, bookkeeping).
It is not the time the device waits: the device starts the step
before its dispatch returns, so part of this overlaps device work,
and the device's wait for the loss to reach Python lies inside
``train.loss_wait``, which this leaves out; naming that wait takes the
device trace.  Steps without a loss wait (the one the window's
deadline cut) are left out."""

import statistics


def read(ctx):
    from repro.core import stats

    records = getattr(stats, "records", None)
    if records is None:
        return None
    recs = records()
    wait = {r.parent: r.ns for r in recs if r.name == "train.loss_wait"}
    host = [r.ns - wait[r.id] for r in recs
            if r.name == "train.step" and r.id in wait]
    if not host:
        return None
    return statistics.median(host) / 1e6
