"""Operations and bytes computed from shapes, for utilisation metrics.

Every count here is what the algorithm needs, worked out from the shapes
alone: no compiler cost model and no trace statistic enters it.
"""

from __future__ import annotations

def llama_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token of a Llama-style decoder.

    The matmuls of every layer (q, k, v, o projections and the gated MLP)
    and of the output head count 2 FLOPs per weight per token forward and
    4 backward; causal attention counts ``QK^T`` and ``PV`` over the half
    of the ``seq_len x seq_len`` score matrix that the mask keeps.
    Recomputation under activation checkpointing does not count.
    """
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    g = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    f = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    vocab = cfg["vocab_size"]
    per_layer = d * h * hd + 2 * d * g * hd + h * hd * d + 3 * d * f
    matmul_params = layers * per_layer + d * vocab
    # forward: QK^T and PV are 2*S*hd FLOPs per head each per token over
    # the full matrix; the causal half keeps S/2 keys on average
    attn_fwd = layers * 2 * 2 * h * hd * seq_len / 2
    return 6.0 * matmul_params + 3.0 * attn_fwd


def unsplit_pages_cost(n_pages: int, itemsize: int, per: int) -> dict:
    """``decode_pages.unsplit_pages``: an inverse byte transpose.

    Reads and writes every byte once; no arithmetic.
    """
    nbytes = n_pages * itemsize * per
    return {"flops": 0.0, "bytes": 2.0 * nbytes}


def offsets_decode_cost(n_pages: int, per: int, rows: int,
                        lanes: int = 128) -> dict:
    """``decode_pages.decode_offset_pages``: zigzag deltas to int32 ends.

    Reads the four low byte planes of each uint64 and writes one int32 per
    element (padded to whole ``rows x lanes`` tiles).  The scan is built
    from bf16 matmuls against 0/1 masks (``offsets_scan.block_scan``): per
    tile of ``rows x lanes`` elements, four byte planes each of two
    ``(rows, lanes) @ (lanes, lanes)`` and two ``(rows, rows) @ (rows,
    lanes)`` products.
    """
    block = rows * lanes
    tiles = -(-per // block)
    elems = n_pages * tiles * block
    flops_per_tile = 4 * (2 * 2 * rows * lanes * lanes + 2 * 2 * rows * rows * lanes)
    return {"flops": float(n_pages * tiles * flops_per_tile),
            "bytes": float(elems * 4 + elems * 4)}


def roofline_seconds(cost: dict, peaks) -> tuple:
    """-> (least seconds, bound): the larger of the compute and memory times."""
    t_flops = cost["flops"] / peaks.bf16_flops
    t_bytes = cost["bytes"] / peaks.hbm_bytes_per_s
    return (t_flops, "compute") if t_flops > t_bytes else (t_bytes, "memory")

