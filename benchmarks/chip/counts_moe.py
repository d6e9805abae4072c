"""Operations and bytes of the MoE train cells, computed from shapes.

A DeepSeek-V3-style decoder as the configuration file states it (one
chip's share: ``n_routed_experts`` experts held of ``router_experts``).
Every count is what the algorithm needs, from the shapes alone; no
recomputation under activation checkpointing is counted.
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "nope": cfg["qk_nope_head_dim"],
            "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "e": cfg["router_experts"], "held": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "shared": cfg["n_shared_experts"],
            "dense": cfg["first_k_dense_replace"],
            "moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"]}


def moe_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token: 2 FLOPs per weight per token
    forward and 4 backward for every matmul (MLA's projections, the dense
    SwiGLU, the router, the shared experts, the head), the routed experts
    at ``top_k x held / router_experts`` of a token each (the picks that
    fall to the experts held here, on average), and causal attention's
    ``QK^T`` (q/k head dim) and ``PV`` (v head dim) over the half of the
    ``seq_len x seq_len`` scores that the mask keeps."""
    n = dims(cfg)
    d, h = n["d"], n["h"]
    mla = (d * h * n["qk"] + d * n["rank"] + d * n["rope"]
           + n["rank"] * h * n["nope"] + n["rank"] * h * n["dv"] + h * n["dv"] * d)
    expert = 3 * d * n["fe"]
    moe = (d * n["e"] + n["k"] * n["held"] / n["e"] * expert
           + n["shared"] * expert)
    layers = n["dense"] + n["moe"]
    weights = (layers * mla + n["dense"] * 3 * d * n["f"] + n["moe"] * moe
               + d * n["vocab"])
    attn_fwd = layers * 2 * h * (n["qk"] + n["dv"]) * seq_len / 2
    return 6.0 * weights + 3.0 * attn_fwd


def gmm_cost(cfg: dict, rows: float) -> dict:
    """One grouped-matmul call of a held-expert projection over ``rows``
    computed rows: ``gmm`` (rows x d by d x fe, or rows x fe by fe x d)
    or ``tgmm`` (the weight gradient, d x rows by rows x fe).  Each is
    ``2 rows d fe`` FLOPs; its bytes are the rows in and out (bf16) and
    the held experts' weights (bf16)."""
    n = dims(cfg)
    d, fe = n["d"], n["fe"]
    return {"flops": 2.0 * rows * d * fe,
            "bytes": 2.0 * (rows * (d + fe) + n["held"] * d * fe)}


def mla_attention_cost(cfg: dict, batch: int, seq_len: int,
                       backward: bool) -> dict:
    """One call of the train kernel over a layer's causal MLA attention
    (every head of every row): forward ``QK^T`` and ``PV``; the fused
    backward recomputes ``QK^T`` and takes ``dP = dO V^T``, ``dV``,
    ``dK`` and ``dQ``.  Over the causal half of the scores.  Bytes: q, k,
    v and the output (and in the backward their gradients and dO) in
    bf16, and the f32 softmax statistics."""
    n = dims(cfg)
    qk, dv = n["qk"], n["dv"]
    heads = batch * n["h"]
    half = seq_len * seq_len / 2
    per = (3 * qk + 2 * dv) if backward else (qk + dv)
    io = seq_len * (2 * qk + 2 * dv) * 2 + seq_len * 4
    if backward:
        io += seq_len * (2 * qk + dv) * 2 + seq_len * dv * 2
    return {"flops": 2.0 * heads * half * per, "bytes": float(heads * io)}
