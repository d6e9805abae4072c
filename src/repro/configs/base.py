"""Architecture configuration model.

One frozen dataclass describes every assigned architecture; the model
builders in ``repro.models`` consume it.  Exact literature values live in
the per-arch files in this package.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int                    # routed experts the router scores
    top_k: int
    n_shared: int = 0                 # shared (always-on) experts
    d_ff: int = 0                     # expert width; 0 -> ArchConfig.d_ff
    capacity_factor: float = 1.25     # the multi-device capacity path only
    router_dtype: str = "float32"
    scoring: str = "softmax"          # softmax | sigmoid (DeepSeek-V3)
    routed_scale: float = 1.0         # routed_scaling_factor
    bias_update: float = 0.0          # gamma of the aux-loss-free correction
                                      # bias (0: no bias)
    aux: str = "switch"               # switch | seq (sequence-wise balance)
    aux_weight: float = 0.01
    n_held: int = 0                   # experts held here (0 -> all), the
    first_held: int = 0               # chip's share under expert parallelism

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3)."""

    q_rank: Optional[int]  # low-rank query compression (None: a direct W_q)
    kv_rank: int         # low-rank kv compression (this is what decode caches)
    d_nope: int          # per-head non-rotary q/k dim
    d_rope: int          # shared rotary dim
    d_v: int             # per-head value dim


@dataclass(frozen=True)
class SSMConfig:
    kind: str            # "rwkv6" | "mamba2"
    d_state: int = 64    # mamba2 N; rwkv6 uses head_dim
    head_dim: int = 64   # P (mamba2) / Dk=Dv (rwkv6)
    expand: int = 2      # d_inner = expand * d_model (mamba2)
    conv_kernel: int = 4
    chunk: int = 64


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                       # dense | ssm | moe | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    mlp: str = "swiglu"               # swiglu | geglu
    attention: str = "gqa"            # gqa | mla | none
    window: Optional[int] = None      # sliding-window attention
    qk_norm: bool = False             # chameleon-style qk layernorm
    moe: Optional[MoEConfig] = None
    first_k_dense: int = 0            # leading dense layers before the MoE stack
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    shared_attn_every: int = 0        # zamba2: shared attn block period
    n_codebooks: int = 1              # musicgen: 4 EnCodec codebooks
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # activation checkpointing over layers.  Each layer stack keeps its
    # layers' matmul outputs for the backward where, summed over all
    # stacks, they fit the device's memory limit less the train state and
    # a headroom; else, or where no limit can be read (the CPU), full
    # remat (models/backbone._run_layers).  False: no checkpoint at all
    remat: bool = True
    attn_impl: str = "ref"            # "ref" | "chunked" (§Perf variant)
    # source provenance for the config values
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads


    def with_(self, **kw) -> "ArchConfig":
        return replace(self, **kw)

    # -- parameter counting (for 6·N·D roofline bookkeeping) -------------------

    def param_count(self) -> int:
        from repro.models.registry import build  # lazy, avoids cycle
        import jax

        bundle = build(self)
        shapes = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0)))
        return sum(
            int(x.size) for x in jax.tree_util.tree_leaves(shapes)
        )

    def active_param_count(self) -> int:
        """Params touched per token (MoE: shared + top_k routed)."""
        from repro.models.registry import build
        import jax

        bundle = build(self)
        shapes = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0)))
        total = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
        if self.moe is None:
            return total

        # subtract inactive routed-expert params
        def moe_leaf_size(path, x):
            p = "/".join(str(k) for k in path)
            return int(x.size) if "experts" in p else 0

        flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
        routed = sum(moe_leaf_size([getattr(k, "key", getattr(k, "idx", k)) for k in path], x)
                     for path, x in flat)
        active_frac = self.moe.top_k / self.moe.n_experts
        return total - int(routed * (1 - active_frac))


@dataclass(frozen=True)
class ShapeCell:
    """One assigned (input-shape) cell."""

    name: str            # train_4k | prefill_32k | decode_32k | long_500k
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch
