"""Deepseek Moe 16B — exact literature config (see base.ArchConfig)."""

from .base import ArchConfig, MLAConfig, MoEConfig, SSMConfig

CONFIG = ArchConfig(
    name="deepseek-moe-16b", family="moe",
    n_layers=28, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=10944, vocab_size=102_400,
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_ff=1408),
    first_k_dense=1,
    source="arXiv:2401.06066 (a dense first layer of width 10944, then "
           "2 shared + 64 routed top-6 fine-grained experts of width 1408)",
)

DEEPSEEK_MOE_16B = CONFIG
