"""Share of their roofline that the MoE layer's grouped matmuls reach:
for every ``gmm`` and ``tgmm`` call in the traced window (the held
experts' gate, up and down projections, forward, recomputed under remat
and backward), the least time its operations and bytes need at the
chip's peaks, summed, over the calls' summed device time.  The rows each
call computed are the step's ``moe.rows_local`` (assignments computed
here, over all MoE layers, the window's mean) over the MoE layers, not
the padded operand's rows: the kernel visits only the groups' tiles."""

import re

from counts import roofline_seconds
from counts_moe import dims, gmm_cost

#: the kernels' HLO instructions: ``gmm.<n>``, ``tgmm.<n>``
GMM = re.compile(r"(^|[%:_])t?gmm(\.\d+)?$")


def read(ctx):
    t = ctx.trace
    rows_all = ctx.window.get("moe.rows_local")
    if t is None or not rows_all or rows_all != rows_all:
        return None
    cost = gmm_cost(ctx.config, rows_all / dims(ctx.config)["moe"])
    least = spent = 0.0
    for e in t.ops():
        if GMM.search(e.label.split(":", 1)[-1]):
            least += roofline_seconds(cost, ctx.peaks)[0]
            spent += e.seconds
    if spent <= 0:
        return None
    return 100.0 * least / spent
