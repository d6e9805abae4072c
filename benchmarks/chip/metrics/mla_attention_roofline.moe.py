"""Share of their roofline that the MLA attention's train-kernel calls
reach: every splash kernel call in the traced window (forward, its
recompute under remat, and the fused backward), its least time at the
chip's peaks from the layer's shapes (``counts_moe.mla_attention_cost``:
q/k head dim nope + rope, v head dim, causal half), summed, over the
calls' summed device time."""

from counts import roofline_seconds
from counts_moe import mla_attention_cost


def read(ctx):
    t = ctx.trace
    if t is None or "qk_rope_head_dim" not in ctx.config:
        return None
    b, s = ctx.traffic["batch"], ctx.traffic["seq_len"]
    fwd = roofline_seconds(mla_attention_cost(ctx.config, b, s, False),
                           ctx.peaks)[0]
    bwd = roofline_seconds(mla_attention_cost(ctx.config, b, s, True),
                           ctx.peaks)[0]
    least = spent = 0.0
    for e in t.ops():
        label = e.label
        if "splash_mqa_fwd" in label:
            least += fwd
        elif "splash_mqa_dkv" in label:
            least += bwd
        else:
            continue
        spent += e.seconds
    if spent <= 0:
        return None
    return 100.0 * least / spent
