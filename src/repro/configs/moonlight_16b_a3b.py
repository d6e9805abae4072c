"""Moonlight-16B-A3B — the DeepSeek-V3 block (see base.ArchConfig).

Source: hf:moonshotai/Moonlight-16B-A3B ``config.json`` (``model_type``
deepseek_v3).  27 layers, the first dense (SwiGLU 11264); MLA without
query compression (``q_lora_rank`` null: q = W_q h, 16 heads of nope 128
+ rope 64, kv latent 512, v 128); 64 routed experts of width 1408 and 2
shared ones, top-6 selected by sigmoid score plus a correction bias
(``noaux_tc``, no group limit), weights renormalised over the selected
scores and scaled by 2.446; the sequence-wise balance loss.

Not in the published config (``assumed``): the balance weight alpha 1e-4
and the bias speed gamma 1e-3, DeepSeek-V3's values (arXiv:2412.19437
§2.1.2).  The checkpoint's interleaved rope pair order only permutes
columns of W_q and W_kr; under random weights the program's rotate-half
rope is the same model.

:data:`EIGHT_CHIP_SHARE` is one chip's share of a deployment in which 8
chips share every MoE layer by expert parallelism and the vocabulary by
rows: experts 0-7 of 64, vocabulary rows 0-20479 of 163840, and the
dense layer with 5 of the 26 MoE layers (the other 21 lie on further
pipeline stages).  Attention, the router, the shared experts and the
dense layer are whole on every chip.
"""

from dataclasses import replace

from .base import ArchConfig, MLAConfig, MoEConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab_size=163_840, attention="mla",
    mla=MLAConfig(q_rank=None, kv_rank=512, d_nope=128, d_rope=64, d_v=128),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_ff=1408,
                  scoring="sigmoid", routed_scale=2.446,
                  bias_update=1e-3, aux="seq", aux_weight=1e-4),
    first_k_dense=1, tie_embeddings=False, rope_theta=50_000.0,
    norm_eps=1e-5,
    source="hf:moonshotai/Moonlight-16B-A3B (deepseek_v3: MLA, 64 routed "
           "+ 2 shared experts top-6, sigmoid noaux_tc routing)",
)

EIGHT_CHIP_SHARE = CONFIG.with_(
    n_layers=6, vocab_size=20_480,
    moe=replace(CONFIG.moe, n_held=8, first_held=0))

MOONLIGHT_16B_A3B = CONFIG
