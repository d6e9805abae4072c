"""Share of their roofline that the device decode kernels reach: for every
decode kernel call in the traced window, the least time its operations
and bytes need at the chip's peaks (``counts.py``, from the shapes in the
call's HLO instruction), summed, over the calls' summed device time.

The inverse byteshuffle (``unsplit_pages``) has no arithmetic and is
bound by memory; the fused offsets decode (``decode_offset_pages``) scans
with bf16 matmuls and is bound by compute."""

import re

from counts import offsets_decode_cost, roofline_seconds, unsplit_pages_cost

#: operand shape of the custom call: ``custom-call(u8[P,a,b]...``
OPERAND = re.compile(r"custom-call\(u8\[([0-9,]+)\]")


def cost(event):
    """Operations and bytes of one decode kernel call, or None for any
    other device op."""
    inst = event.name.split(" = ", 1)[0]
    m = OPERAND.search(event.name)
    if m is None:
        return None
    dims = [int(x) for x in m.group(1).split(",")]
    if inst.startswith("%unsplit_pages") and len(dims) == 3:
        n_pages, itemsize, per = dims
        return unsplit_pages_cost(n_pages, itemsize, per)
    if inst.startswith("%decode_offset_pages") and len(dims) == 4:
        n_pages, _planes, rows_total, lanes = dims
        return offsets_decode_cost(n_pages, rows_total * lanes,
                                   rows=min(128, rows_total), lanes=lanes)
    return None


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    least = spent = 0.0
    for e in t.ops():
        c = cost(e)
        if c is None:
            continue
        least += roofline_seconds(c, ctx.peaks)[0]
        spent += e.seconds
    if spent <= 0:
        return None
    return 100.0 * least / spent
