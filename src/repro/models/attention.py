"""Attention mixers: GQA/MQA (+ sliding window, qk-norm) and MLA.

Each mixer exposes::

    init(rng, cfg)                          -> params
    apply(params, x, cfg, positions)        -> y                (train/prefill)
    init_cache(cfg, batch, max_len, dtype)  -> cache            (per layer)
    apply_decode(params, x, cfg, cache, pos)-> (y, new_cache)   (one token)

Caches are per-layer pytrees; the backbone stacks them along a leading
layer axis for the scan.  The attention math itself goes through
``repro.kernels.ops`` (Pallas on TPU, jnp oracle elsewhere).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.kernels import ops

from .layers import apply_rope, cdtype, dense_init, pdtype, rms_norm


# ---------------------------------------------------------------------------
# GQA / MQA


def gqa_init(rng, cfg: ArchConfig) -> Dict:
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = pdtype(cfg)
    ks = jax.random.split(rng, 6)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, g * hd, dt),
        "wv": dense_init(ks[2], d, g * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt, scale=1.0 / np.sqrt(h * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _qkv(params: Dict, x: jax.Array, cfg: ArchConfig, positions: jax.Array):
    dt = cdtype(cfg)
    b, t, _ = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    x = x.astype(dt)
    q = jnp.einsum("btd,dk->btk", x, params["wq"].astype(dt)).reshape(b, t, h, hd)
    k = jnp.einsum("btd,dk->btk", x, params["wk"].astype(dt)).reshape(b, t, g, hd)
    v = jnp.einsum("btd,dk->btk", x, params["wv"].astype(dt)).reshape(b, t, g, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q.swapaxes(1, 2), positions[:, None], cfg.rope_theta)  # (B,H,T,hd)
    k = apply_rope(k.swapaxes(1, 2), positions[:, None], cfg.rope_theta)  # (B,G,T,hd)
    v = v.swapaxes(1, 2)
    q = shard(q, "dp", "tp", "sp_attn", None)
    k = shard(k, "dp", "tp_kv", None, None)
    v = shard(v, "dp", "tp_kv", None, None)
    return q, k, v


def gqa_apply(params: Dict, x: jax.Array, cfg: ArchConfig,
              positions: jax.Array) -> jax.Array:
    b, t, _ = x.shape
    q, k, v = _qkv(params, x, cfg, positions)
    y = ops.flash_attention(q, k, v, causal=True, window=cfg.window,
                            impl=cfg.attn_impl)
    y = shard(y, "dp", "tp", "sp_attn", None)
    y = y.swapaxes(1, 2).reshape(b, t, cfg.n_heads * cfg.resolved_head_dim)
    out = jnp.einsum("btk,kd->btd", y, params["wo"].astype(y.dtype))
    return shard(out, "dp", "sp", None)


def gqa_init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> Dict:
    g, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    cache_len = min(max_len, cfg.window) if cfg.window else max_len
    return {
        "k": jnp.zeros((batch, g, cache_len, hd), dtype),
        "v": jnp.zeros((batch, g, cache_len, hd), dtype),
    }


def gqa_apply_decode(params: Dict, x: jax.Array, cfg: ArchConfig,
                     cache: Dict, pos: jax.Array) -> Tuple[jax.Array, Dict]:
    """x: (B, 1, D); pos: (B,) current absolute position; ring-buffered SWA."""
    b = x.shape[0]
    q, k, v = _qkv(params, x, cfg, positions=pos[:, None])
    cache_len = cache["k"].shape[2]
    slot = pos % cache_len if cfg.window else pos              # (B,)
    k_new = jax.vmap(
        lambda c, kn, s: jax.lax.dynamic_update_slice(c, kn, (0, s, 0))
    )(cache["k"], k, slot)
    v_new = jax.vmap(
        lambda c, vn, s: jax.lax.dynamic_update_slice(c, vn, (0, s, 0))
    )(cache["v"], v, slot)
    k_new = shard(k_new, "dp", "tp_kv", "sp_kv", None)
    v_new = shard(v_new, "dp", "tp_kv", "sp_kv", None)
    if cfg.window:
        # ring buffer holds the last `cache_len` tokens; attend to all valid
        length = jnp.minimum(pos + 1, cache_len)
        y = _ring_decode_attention(q[:, :, 0], k_new, v_new, pos, cache_len, cfg)
    else:
        length = pos + 1
        y = ops.decode_attention(q[:, :, 0], k_new, v_new, length=length)
    y = y.reshape(b, 1, cfg.n_heads * cfg.resolved_head_dim)
    out = jnp.einsum("btk,kd->btd", y, params["wo"].astype(y.dtype))
    return out, {"k": k_new, "v": v_new}


def _ring_decode_attention(q, k, v, pos, cache_len, cfg):
    """Decode over a ring-buffered window cache.

    Every slot is valid once pos+1 >= cache_len; before that only slots
    < pos+1.  Positions inside the window need no causal order for softmax
    (decode attends to the whole window), so a validity mask suffices.
    """
    n_valid = jnp.minimum(pos + 1, cache_len)                 # (B,)
    return _masked_decode(q, k, v, n_valid, cache_len)


def _masked_decode(q, k, v, n_valid, cache_len):
    """jnp decode attention with per-slot validity (ring semantics)."""
    b, h, d = q.shape
    g = k.shape[1]
    if g != h:
        k = jnp.repeat(k, h // g, axis=1)
        v = jnp.repeat(v, h // g, axis=1)
    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhd,bhkd->bhk", q * scale, k)
    slots = jnp.arange(cache_len)[None, :]
    valid = slots < n_valid[:, None]
    logits = jnp.where(valid[:, None, :], logits.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention, MiniCPM3 / DeepSeek-V2)
#
# Queries:  q = W_uq norm(W_dq x), or q = W_q x without query compression
#           (q_rank None, DeepSeek-V3 / Moonlight); per head (d_nope | d_rope)
# KV:       c = norm(W_dkv x)  (kv_rank)  +  k_rope = W_kr x (d_rope, shared)
#           k_nope = W_uk c ; v = W_uv c  per head
# The decode cache stores ONLY (c, k_rope): rank+d_rope floats per token.


def mla_init(rng, cfg: ArchConfig) -> Dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dt = pdtype(cfg)
    ks = jax.random.split(rng, 8)
    qd = m.d_nope + m.d_rope
    if m.q_rank is None:
        q = {"wq": dense_init(ks[0], d, h * qd, dt)}
    else:
        q = {"w_dq": dense_init(ks[0], d, m.q_rank, dt),
             "q_norm": jnp.ones((m.q_rank,), dt),
             "w_uq": dense_init(ks[1], m.q_rank, h * qd, dt)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], d, m.kv_rank, dt),
        "kv_norm": jnp.ones((m.kv_rank,), dt),
        "w_kr": dense_init(ks[3], d, m.d_rope, dt),
        "w_uk": dense_init(ks[4], m.kv_rank, h * m.d_nope, dt),
        "w_uv": dense_init(ks[5], m.kv_rank, h * m.d_v, dt),
        "wo": dense_init(ks[6], h * m.d_v, d, dt),
    }


def _mla_qckr(params, x, cfg, positions):
    m = cfg.mla
    dt = cdtype(cfg)
    b, t, _ = x.shape
    h = cfg.n_heads
    x = x.astype(dt)
    if m.q_rank is None:
        q = jnp.einsum("btd,dk->btk", x, params["wq"].astype(dt))
    else:
        cq = rms_norm(jnp.einsum("btd,dr->btr", x, params["w_dq"].astype(dt)),
                      params["q_norm"], cfg.norm_eps)
        q = jnp.einsum("btr,rk->btk", cq, params["w_uq"].astype(dt))
    q = q.reshape(b, t, h, m.d_nope + m.d_rope)
    q_nope, q_rope = q[..., : m.d_nope], q[..., m.d_nope:]
    q_rope = apply_rope(q_rope.swapaxes(1, 2), positions[:, None],
                        cfg.rope_theta).swapaxes(1, 2)
    c = rms_norm(jnp.einsum("btd,dr->btr", x, params["w_dkv"].astype(dt)),
                 params["kv_norm"], cfg.norm_eps)                 # (B,T,rank)
    k_rope = jnp.einsum("btd,dr->btr", x, params["w_kr"].astype(dt))
    k_rope = apply_rope(k_rope[:, None], positions[:, None],
                        cfg.rope_theta)[:, 0]                     # (B,T,d_rope)
    return q_nope, q_rope, c, k_rope


def _mla_attend(params, q_nope, q_rope, c, k_rope, cfg, causal_offset=0):
    """Full-form MLA attention (materializes per-head k/v from latents)."""
    m = cfg.mla
    h = cfg.n_heads
    dt = q_nope.dtype
    b, tq = q_nope.shape[:2]
    tk = c.shape[1]
    k_nope = jnp.einsum("btr,rk->btk", c, params["w_uk"].astype(dt)).reshape(
        b, tk, h, m.d_nope)
    v = jnp.einsum("btr,rk->btk", c, params["w_uv"].astype(dt)).reshape(
        b, tk, h, m.d_v)
    q = jnp.concatenate([q_nope, q_rope], axis=-1).swapaxes(1, 2)  # (B,H,Tq,·)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, tk, h, m.d_rope))],
        axis=-1,
    ).swapaxes(1, 2)
    v = v.swapaxes(1, 2)
    q = shard(q, "dp", "tp", "sp_attn", None)
    k = shard(k, "dp", "tp", None, None)
    y = ops.flash_attention(q, k, v, causal=True,
                            scale=1.0 / np.sqrt(m.d_nope + m.d_rope),
                            impl=cfg.attn_impl)
    y = y.swapaxes(1, 2).reshape(b, tq, h * m.d_v)
    return jnp.einsum("btk,kd->btd", y, params["wo"].astype(dt))


def mla_apply(params: Dict, x: jax.Array, cfg: ArchConfig,
              positions: jax.Array) -> jax.Array:
    q_nope, q_rope, c, k_rope = _mla_qckr(params, x, cfg, positions)
    out = _mla_attend(params, q_nope, q_rope, c, k_rope, cfg)
    return shard(out, "dp", "sp", None)


def mla_init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> Dict:
    m = cfg.mla
    return {
        "c": jnp.zeros((batch, max_len, m.kv_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.d_rope), dtype),
    }


def mla_apply_decode(params: Dict, x: jax.Array, cfg: ArchConfig,
                     cache: Dict, pos: jax.Array) -> Tuple[jax.Array, Dict]:
    b = x.shape[0]
    q_nope, q_rope, c_new, kr_new = _mla_qckr(params, x, cfg, pos[:, None])
    c = jax.vmap(
        lambda cc, cn, s: jax.lax.dynamic_update_slice(cc, cn, (s, 0))
    )(cache["c"], c_new, pos)
    kr = jax.vmap(
        lambda cc, cn, s: jax.lax.dynamic_update_slice(cc, cn, (s, 0))
    )(cache["k_rope"], kr_new, pos)
    m = cfg.mla
    h = cfg.n_heads
    dt = q_nope.dtype
    tk = c.shape[1]
    # latent-space attention: fold W_uk into q (the MLA decode trick) so the
    # cache is read once in compressed form.
    w_uk = params["w_uk"].astype(dt).reshape(m.kv_rank, h, m.d_nope)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)      # (B,H,rank)
    logits = (
        jnp.einsum("bhr,btr->bht", q_lat, c)
        + jnp.einsum("bhe,bte->bht", q_rope[:, 0], kr)
    )
    logits = logits * (1.0 / np.sqrt(m.d_nope + m.d_rope))
    valid = jnp.arange(tk)[None, :] < (pos + 1)[:, None]
    logits = jnp.where(valid[:, None, :], logits.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(dt)
    ctx = jnp.einsum("bht,btr->bhr", p, c)                       # (B,H,rank)
    w_uv = params["w_uv"].astype(dt).reshape(m.kv_rank, h, m.d_v)
    y = jnp.einsum("bhr,rhv->bhv", ctx, w_uv).reshape(b, 1, h * m.d_v)
    out = jnp.einsum("btk,kd->btd", y, params["wo"].astype(dt))
    return out, {"c": c, "k_rope": kr}
