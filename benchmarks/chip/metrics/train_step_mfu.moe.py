"""Model FLOPs utilisation of the MoE train step: forward and backward
FLOPs per token from the configuration's shapes (``counts_moe.py``: the
routed experts at ``top_k x held / router_experts`` a token, no
rematerialisation) times the window's tokens per second, over the chips'
bf16 peak (``peaks.py``)."""

from counts_moe import moe_train_flops_per_token


def read(ctx):
    rate = ctx.window.get("train_tokens_per_s")
    if not rate or "router_experts" not in ctx.config:
        return None
    flops = moe_train_flops_per_token(ctx.config, ctx.traffic["seq_len"])
    return 100.0 * flops * rate / (len(ctx.devices) * ctx.peaks.bf16_flops)
