"""Model FLOPs utilisation of the train step: forward and backward FLOPs
per token from the configuration's shapes (``counts.py``; no
rematerialisation counted) times the window's tokens per second, over
the chips' bf16 peak (``peaks.py``)."""

from counts import llama_train_flops_per_token


def read(ctx):
    rate = ctx.window.get("train_tokens_per_s")
    if not rate:
        return None
    flops = llama_train_flops_per_token(ctx.config, ctx.traffic["seq_len"])
    return 100.0 * flops * rate / (len(ctx.devices) * ctx.peaks.bf16_flops)
