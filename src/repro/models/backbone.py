"""Unified decoder backbone for all ten assigned architectures.

Layer stack is a ``jax.lax.scan`` over stacked per-layer params (O(1) HLO
size for 95-layer models, remat-compatible); the zamba2 hybrid unrolls its
9 groups of (6 mamba layers -> shared attention block).

Three entry points per model (see registry.ModelBundle):
  * ``loss``        — next-token CE for train_4k
  * ``prefill``     — full-sequence forward + KV/state cache build (prefill_32k)
  * ``decode_step`` — one token against the cache (decode_32k / long_500k)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import Literal

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.kernels import ops, ref as kref

from . import attention as A
from . import mamba2 as M2
from . import moe as MOE
from . import rwkv6 as R6
from .layers import (
    cdtype, cross_entropy_loss, embed_tokens, embedding_init, lm_logits,
    mlp_apply, mlp_init, pdtype, rms_norm,
)

# ---------------------------------------------------------------------------
# per-layer block: init / apply / prefill / decode


def _is_attn_block(cfg: ArchConfig) -> bool:
    return cfg.family in ("dense", "vlm", "audio", "moe")


def block_init(rng, cfg: ArchConfig) -> Dict:
    if cfg.family == "ssm" and cfg.ssm.kind == "rwkv6":
        return R6.rwkv6_block_init(rng, cfg)
    if cfg.family == "hybrid" or (cfg.ssm and cfg.ssm.kind == "mamba2"):
        return M2.mamba2_block_init(rng, cfg)
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    dt = pdtype(cfg)
    p = {
        "norm1": jnp.ones((cfg.d_model,), dt),
        "norm2": jnp.ones((cfg.d_model,), dt),
        "attn": A.mla_init(k1, cfg) if cfg.attention == "mla" else A.gqa_init(k1, cfg),
    }
    if cfg.moe is not None:
        p["moe"] = MOE.moe_init(k2, cfg)
    else:
        p["mlp"] = mlp_init(k2, cfg)
    return p


def block_apply(p: Dict, x: jax.Array, cfg: ArchConfig,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array, Dict]:
    """-> (x, aux_loss, MoE stats: ``moe.moe_apply``'s, or {})."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm" and cfg.ssm.kind == "rwkv6":
        return R6.rwkv6_block_apply(p, x, cfg, positions), aux, {}
    if cfg.family == "hybrid":
        return M2.mamba2_block_apply(p, x, cfg, positions), aux, {}
    xn = rms_norm(x, p["norm1"], cfg.norm_eps)
    attn = A.mla_apply if cfg.attention == "mla" else A.gqa_apply
    x = x + attn(p["attn"], xn, cfg, positions)
    xn = rms_norm(x, p["norm2"], cfg.norm_eps)
    stats = {}
    if cfg.moe is not None:
        y, aux, stats = MOE.moe_apply(p["moe"], xn, cfg)
    else:
        y = mlp_apply(p["mlp"], xn, cfg)
    return shard(x + y, "dp", "sp", None), aux, stats


def block_init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> Dict:
    if cfg.family == "ssm" and cfg.ssm.kind == "rwkv6":
        return R6.rwkv6_init_cache(cfg, batch, max_len, dtype)
    if cfg.family == "hybrid":
        return M2.mamba2_init_cache(cfg, batch, max_len, dtype)
    if cfg.attention == "mla":
        return A.mla_init_cache(cfg, batch, max_len, dtype)
    return A.gqa_init_cache(cfg, batch, max_len, dtype)


def block_decode(p: Dict, x: jax.Array, cfg: ArchConfig, cache: Dict,
                 pos: jax.Array) -> Tuple[jax.Array, Dict]:
    if cfg.family == "ssm" and cfg.ssm.kind == "rwkv6":
        return R6.rwkv6_block_decode(p, x, cfg, cache, pos)
    if cfg.family == "hybrid":
        return M2.mamba2_block_decode(p, x, cfg, cache, pos)
    xn = rms_norm(x, p["norm1"], cfg.norm_eps)
    dec = A.mla_apply_decode if cfg.attention == "mla" else A.gqa_apply_decode
    y, new_cache = dec(p["attn"], xn, cfg, cache, pos)
    x = x + y
    xn = rms_norm(x, p["norm2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, _, _ = MOE.moe_apply(p["moe"], xn, cfg)
    else:
        y = mlp_apply(p["mlp"], xn, cfg)
    return x + y, new_cache


def block_prefill(p: Dict, x: jax.Array, cfg: ArchConfig, positions: jax.Array,
                  max_len: int, dtype) -> Tuple[jax.Array, Dict]:
    """Full-sequence forward that also builds this layer's decode cache."""
    b, s, _ = x.shape
    if cfg.family == "ssm" and cfg.ssm.kind == "rwkv6":
        return _rwkv6_prefill(p, x, cfg)
    if cfg.family == "hybrid":
        return _mamba2_prefill(p, x, cfg)
    if cfg.attention == "mla":
        return _mla_prefill(p, x, cfg, positions, max_len, dtype)
    return _gqa_prefill(p, x, cfg, positions, max_len, dtype)


def _finish_block(p, x, attn_out, cfg):
    x = x + attn_out
    xn = rms_norm(x, p["norm2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, _, _ = MOE.moe_apply(p["moe"], xn, cfg)
    else:
        y = mlp_apply(p["mlp"], xn, cfg)
    return x + y


def _gqa_prefill(p, x, cfg, positions, max_len, dtype):
    b, s, _ = x.shape
    xn = rms_norm(x, p["norm1"], cfg.norm_eps)
    q, k, v = A._qkv(p["attn"], xn, cfg, positions)
    y = ops.flash_attention(q, k, v, causal=True, window=cfg.window,
                            impl=cfg.attn_impl)
    y = y.swapaxes(1, 2).reshape(b, s, cfg.n_heads * cfg.resolved_head_dim)
    attn_out = jnp.einsum("btk,kd->btd", y, p["attn"]["wo"].astype(y.dtype))
    x = _finish_block(p, x, attn_out, cfg)

    cache = A.gqa_init_cache(cfg, b, max_len, dtype)
    cache_len = cache["k"].shape[2]
    if cache_len >= s:
        kc = jnp.pad(k, ((0, 0), (0, 0), (0, cache_len - s), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, 0), (0, cache_len - s), (0, 0)))
    else:
        # ring buffer (SWA): keep the last cache_len tokens at slots pos % len
        last_pos = np.arange(0, 0) if False else jnp.arange(s - cache_len, s)
        slots = last_pos % cache_len
        kc = jnp.zeros_like(cache["k"]).at[:, :, slots].set(k[:, :, -cache_len:])
        vc = jnp.zeros_like(cache["v"]).at[:, :, slots].set(v[:, :, -cache_len:])
    return x, {"k": kc.astype(dtype), "v": vc.astype(dtype)}


def _mla_prefill(p, x, cfg, positions, max_len, dtype):
    b, s, _ = x.shape
    xn = rms_norm(x, p["norm1"], cfg.norm_eps)
    q_nope, q_rope, c, k_rope = A._mla_qckr(p["attn"], xn, cfg, positions)
    attn_out = A._mla_attend(p["attn"], q_nope, q_rope, c, k_rope, cfg)
    x = _finish_block(p, x, attn_out, cfg)
    pad = max_len - s
    cache = {
        "c": jnp.pad(c, ((0, 0), (0, pad), (0, 0))).astype(dtype),
        "k_rope": jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))).astype(dtype),
    }
    return x, cache


def _rwkv6_prefill(p, x, cfg):
    """Run the block via the state-returning ref path to seed decode."""
    dt = cdtype(cfg)
    d = cfg.d_model
    hd = cfg.ssm.head_dim
    b, t, _ = x.shape
    x = x.astype(dt)
    xn = rms_norm(x, p["norm1"], cfg.norm_eps)
    prev = jnp.zeros((b, d), dt)
    r, k, v, w, g = R6._time_mix(p, xn, R6._token_shift(xn, prev), cfg)
    o, wkv_state = ops.rwkv6(r, k, v, w, p["u"].astype(dt), chunk=cfg.ssm.chunk)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, d).astype(dt)
    o = rms_norm(o, p["ln_x"], cfg.norm_eps) * g
    x = x + jnp.einsum("btd,de->bte", o, p["wo"].astype(dt))
    shift_tm = xn[:, -1]

    xn2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    xs2 = R6._token_shift(xn2, jnp.zeros((b, d), dt))
    mu = p["mu_ffn"].astype(dt)
    xk = xn2 + (xs2 - xn2) * mu[0]
    xr = xn2 + (xs2 - xn2) * mu[1]
    kf = jnp.square(jax.nn.relu(
        jnp.einsum("btd,df->btf", xk, p["wk_ffn"].astype(dt))))
    vf = jnp.einsum("btf,fd->btd", kf, p["wv_ffn"].astype(dt))
    rf = jax.nn.sigmoid(jnp.einsum("btd,de->bte", xr, p["wr_ffn"].astype(dt)))
    x = x + rf * vf
    return x, {"wkv": wkv_state, "shift_tm": shift_tm, "shift_cm": xn2[:, -1]}


def _mamba2_prefill(p, x, cfg):
    dt_ = cdtype(cfg)
    d_inner, h, n, pdim, kk = M2._dims(cfg)
    b, t, _ = x.shape
    x = x.astype(dt_)
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    zxbcdt = jnp.einsum("btd,de->bte", xn, p["w_in"].astype(dt_))
    z, xr, B, C, dt_raw = M2._split_proj(zxbcdt, d_inner, n, h)
    xbc_pre = jnp.concatenate([xr, B, C], axis=-1)
    xbc = M2._causal_conv(xbc_pre, p["conv_w"].astype(dt_), p["conv_b"].astype(dt_))
    xr, B, C = (xbc[..., :d_inner], xbc[..., d_inner : d_inner + n],
                xbc[..., d_inner + n :])
    delta = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    Aa = -jnp.exp(p["A_log"].astype(jnp.float32))
    log_a = (delta * Aa).transpose(0, 2, 1)
    xh = xr.reshape(b, t, h, pdim).transpose(0, 2, 1, 3)
    xh = xh * delta.transpose(0, 2, 1)[..., None].astype(dt_)
    y, ssd_state = ops.mamba2(xh, log_a.astype(jnp.float32),
                              B.astype(jnp.float32), C.astype(jnp.float32),
                              chunk=cfg.ssm.chunk)
    y = y + p["D"].astype(y.dtype)[None, :, None, None] * xh
    y = y.transpose(0, 2, 1, 3).reshape(b, t, d_inner).astype(dt_)
    y = rms_norm(y, p["norm_gate"], cfg.norm_eps) * jax.nn.silu(z)
    out = x + jnp.einsum("bte,ed->btd", y, p["w_out"].astype(dt_))
    conv_state = xbc_pre[:, -(kk - 1):] if t >= kk - 1 else jnp.pad(
        xbc_pre, ((0, 0), (kk - 1 - t, 0), (0, 0)))
    return out, {"conv": conv_state, "ssd": ssd_state}


# ---------------------------------------------------------------------------
# model init


def dense_cfg(cfg: ArchConfig) -> ArchConfig:
    """The config of the leading dense layers (``first_k_dense``): the
    same block with the dense MLP of width ``d_ff``."""
    return cfg.with_(moe=None)


def _stacks(cfg: ArchConfig):
    """(params key, layer config) of each scanned stack, in order: the
    leading dense layers (where the config has them), then the rest."""
    if cfg.first_k_dense:
        return [("dense_layers", dense_cfg(cfg)), ("layers", cfg)]
    return [("layers", cfg)]


def init_params(rng, cfg: ArchConfig) -> Dict:
    k_embed, k_layers, k_shared = jax.random.split(rng, 3)
    params: Dict[str, Any] = {"embedding": embedding_init(k_embed, cfg)}
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    k0 = cfg.first_k_dense
    if k0:
        params["dense_layers"] = jax.vmap(
            lambda k: block_init(k, dense_cfg(cfg)))(layer_keys[:k0])
    params["layers"] = jax.vmap(lambda k: block_init(k, cfg))(layer_keys[k0:])
    if cfg.shared_attn_every:
        shared_cfg = _shared_attn_cfg(cfg)
        ks1, ks2 = jax.random.split(k_shared)
        params["shared_attn"] = {
            "norm1": jnp.ones((cfg.d_model,), pdtype(cfg)),
            "norm2": jnp.ones((cfg.d_model,), pdtype(cfg)),
            "attn": A.gqa_init(ks1, shared_cfg),
            "mlp": mlp_init(ks2, shared_cfg),
        }
    return params


def _shared_attn_cfg(cfg: ArchConfig) -> ArchConfig:
    """Zamba2's shared transformer block config (full attention + MLP)."""
    return cfg.with_(family="dense", attention="gqa", moe=None, ssm=None,
                     shared_attn_every=0)


def _shared_attn_apply(p, x, cfg, positions):
    sc = _shared_attn_cfg(cfg)
    xn = rms_norm(x, p["norm1"], sc.norm_eps)
    x = x + A.gqa_apply(p["attn"], xn, sc, positions)
    xn = rms_norm(x, p["norm2"], sc.norm_eps)
    return x + mlp_apply(p["mlp"], xn, sc)


def _shared_attn_decode(p, x, cfg, cache, pos, window: Optional[int]):
    sc = _shared_attn_cfg(cfg)
    if window is not None:
        sc = sc.with_(window=window)
    xn = rms_norm(x, p["norm1"], sc.norm_eps)
    y, new_cache = A.gqa_apply_decode(p["attn"], xn, sc, cache, pos)
    x = x + y
    xn = rms_norm(x, p["norm2"], sc.norm_eps)
    return x + mlp_apply(p["mlp"], xn, sc), new_cache


def _shared_attn_prefill(p, x, cfg, positions, max_len, dtype, window):
    sc = _shared_attn_cfg(cfg)
    if window is not None:
        sc = sc.with_(window=window)
    fake = {"norm1": p["norm1"], "norm2": p["norm2"], "attn": p["attn"],
            "mlp": p["mlp"]}
    return _gqa_prefill(fake, x, sc, positions, max_len, dtype)


# ---------------------------------------------------------------------------
# forward passes


def _layer_slice(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _run_stack(body, carry, stacked, cfg: ArchConfig):
    """scan-over-layers, or a python unroll when cfg.scan_layers=False.

    The unrolled form exists for the dry-run cost probes: XLA's
    HloCostAnalysis counts a while-loop body ONCE regardless of trip
    count, so true per-layer flops/bytes/collectives are extrapolated
    from small unrolled compiles (launch/dryrun.py).
    """
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, stacked)
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, _layer_slice(stacked, i))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


#: the saving policy: a layer keeps the outputs of its matmuls (dot
#: products without batch dimensions: projections, MLP, router and shared
#: experts; not attention scores, not Pallas kernels) for the backward,
#: which then recomputes only elementwise work (norms, rope, SiLU, adds)
SAVE_DOTS = jax.checkpoint_policies.dots_with_no_batch_dims_saveable


def _nbytes(tree) -> int:
    """Bytes of the arrays (or shapes) in ``tree``."""
    return sum(a.size * np.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(tree))


def _saved_bytes(body, carry, layer_p) -> int:
    """Bytes one layer keeps for its backward under :data:`SAVE_DOTS`:
    the residuals of ``jax.linearize`` through the checkpointed body that
    are not the layer's parameters (held anyway) or constants, so its
    input (which full remat keeps too) and its matmul outputs.  Traced
    from shapes; nothing runs, and no path counter counts."""
    # a function of its own: jax caches a checkpointed function's trace by
    # the function, and the stack's own trace must not reuse this
    # uncounted one
    saving = jax.checkpoint(lambda c, p: body(c, p), policy=SAVE_DOTS,
                            prevent_cse=False)

    def residuals(carry, layer_p):
        return jax.linearize(saving, carry, layer_p)[1]

    with ops.uncounted():
        jaxpr = jax.make_jaxpr(residuals)(carry, layer_p).jaxpr
    n_carry = len(jax.tree_util.tree_leaves(carry))
    held = set(jaxpr.invars[n_carry:]) | set(jaxpr.constvars)
    return _nbytes([v.aval for v in jaxpr.outvars
                    if not isinstance(v, Literal) and v not in held])


def remat_budget(params, x, cfg: ArchConfig, limit: int) -> int:
    """The bytes the layer stacks may keep for the backward on a device
    of ``limit`` bytes, at the layers' input ``x``: the limit less the
    train state (the parameters and their gradients in the parameters'
    dtype, AdamW's two moments in f32) and less a headroom for what else
    is live while a step runs: one more copy of the parameters (a
    caller's snapshot: a checkpoint being written, a checker's start
    point) and the loss head's logits with their gradient, in f32."""
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    state = 2 * _nbytes(params) + 2 * 4 * n
    logits = x.shape[0] * x.shape[1] * cfg.vocab_size * cfg.n_codebooks
    return limit - state - _nbytes(params) - 2 * 4 * logits


def _remat_policy(params, stacks, carry, cfg: ArchConfig):
    """-> (policy, saved bytes, budget) for every remat'd stack of one
    step: :data:`SAVE_DOTS` where the bytes it keeps, summed over every
    stack's layers, fit :func:`remat_budget` of the device's memory
    limit; otherwise None, full remat (nothing inside a layer kept).
    Where no limit can be read (the CPU) the stacks keep full remat and
    nothing is counted (saved and budget None).  Shapes are global: under
    a mesh that shards them the count is too high, which errs toward full
    remat."""
    limit = ops.device_memory_limit()
    if limit is None:
        return None, None, None
    budget = remat_budget(params, carry[0], cfg, limit)
    saved = 0
    for body, stacked in stacks:
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        layer_p = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stacked)
        saved += n * _saved_bytes(body, carry, layer_p)
    return (SAVE_DOTS if saved <= budget else None), saved, budget


def _scan_layers(params, x, cfg: ArchConfig, positions):
    """(x, total_aux, per-layer MoE stats of the last stack) after the
    layer stacks.

    With ``cfg.remat`` every stack's layer is a ``jax.checkpoint``; the
    policy follows the device's memory (:func:`_remat_policy`): a layer
    keeps its matmul outputs (:data:`SAVE_DOTS`) where all stacks' saved
    outputs fit the memory limit less the train state and a headroom,
    and keeps nothing (full remat: the backward runs the layer's forward
    again) where they do not fit or no limit can be read.  ``ops.REMAT``
    counts the policy of each stack lowered.  Without ``cfg.remat`` no
    layer is checkpointed."""

    def body_for(layer_cfg):
        def body(carry, layer_p):
            x, aux = carry
            x, a, stats = block_apply(layer_p, x, layer_cfg, positions)
            return (x, aux + a), stats

        return body

    carry = (x, jnp.zeros((), jnp.float32))
    if cfg.shared_attn_every:
        stacks = [(body_for(cfg), params["layers"])]
    else:
        stacks = [(body_for(layer_cfg), params[key])
                  for key, layer_cfg in _stacks(cfg)]
    if cfg.remat:
        policy, saved, budget = _remat_policy(params, stacks, carry, cfg)
        path = "full" if policy is None else "dots"

        def remat(body, stacked):
            ops.REMAT.count(path, (
                tuple(x.shape), jax.tree_util.tree_leaves(stacked)[0].shape[0],
                _nbytes(stacked), saved, budget))
            return jax.checkpoint(body, policy=policy, prevent_cse=False)

        stacks = [(remat(body, stacked), stacked) for body, stacked in stacks]

    if cfg.shared_attn_every:
        body = stacks[0][0]
        n_groups = cfg.n_layers // cfg.shared_attn_every
        for g in range(n_groups):
            group_p = jax.tree_util.tree_map(
                lambda a, g=g: a[g * cfg.shared_attn_every:(g + 1) * cfg.shared_attn_every],
                params["layers"],
            )
            carry, _ = _run_stack(body, carry, group_p, cfg)
            carry = (_shared_attn_apply(params["shared_attn"], carry[0], cfg,
                                        positions), carry[1])
        x, aux = carry
        return x, aux, {}

    stats = {}
    for body, stacked in stacks:
        carry, stats = _run_stack(body, carry, stacked, cfg)
    x, aux = carry
    return x, aux, stats


def _forward(params: Dict, tokens: jax.Array, cfg: ArchConfig):
    b, s = tokens.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = embed_tokens(params["embedding"], tokens, cfg)
    x, aux, stats = _scan_layers(params, x, cfg, positions)
    return lm_logits(params["embedding"], x, cfg), aux, stats


def forward(params: Dict, tokens: jax.Array, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """tokens (B,S[,Q]) -> (logits, aux_loss)."""
    logits, aux, _ = _forward(params, tokens, cfg)
    return logits, aux


def loss_fn(params: Dict, batch: Dict, cfg: ArchConfig) -> Tuple[jax.Array, Dict]:
    """Cross-entropy plus the MoE balance loss at the config's weight.
    The metrics carry each MoE layer's assignment counts (``moe.picks``
    over all experts, ``moe.held`` over the held ones) for
    :func:`after_step`."""
    logits, aux, stats = _forward(params, batch["tokens"], cfg)
    ce = cross_entropy_loss(logits, batch["labels"])
    weight = cfg.moe.aux_weight if cfg.moe is not None else 0.0
    metrics = {"ce": ce, "aux": aux}
    metrics.update({f"moe.{k}": v for k, v in stats.items()})
    return ce + weight * aux, metrics


def state_mask(params: Dict) -> Dict:
    """A tree of bools like ``params``: True at the leaves that are the
    model's state, not trained (the MoE routers' correction biases, which
    :func:`after_step` moves).  The optimizer leaves them as they are."""
    def one(path, _):
        return bool(path) and getattr(path[-1], "key", None) == MOE.BIAS

    return jax.tree_util.tree_map_with_path(one, params)


def after_step(params: Dict, metrics: Dict, cfg: ArchConfig):
    """After the optimizer's update: the routers' correction biases take
    their step (aux-loss-free balancing, outside AdamW), and the step's
    MoE counters (``moe.step_counters``) join ``metrics``, where the
    per-layer ``moe.picks`` (L, E) stay and ``moe.held`` gives way to
    them.  Anything else passes through."""
    if "moe.picks" not in metrics:
        return params, metrics
    metrics = dict(metrics)
    picks, held = metrics["moe.picks"], metrics.pop("moe.held")
    moe_p = params["layers"]["moe"]
    bias = None
    if MOE.BIAS in moe_p:
        bias = MOE.update_bias(moe_p[MOE.BIAS], picks, cfg)
        params = {**params, "layers": {**params["layers"],
                                       "moe": {**moe_p, MOE.BIAS: bias}}}
    metrics.update(MOE.step_counters(held, bias))
    return params, metrics


def prefill(params: Dict, tokens: jax.Array, cfg: ArchConfig,
            max_len: int, cache_dtype=None) -> Tuple[jax.Array, Any]:
    """-> (last-token logits (B,1,V[,Q]), stacked cache)."""
    cache_dtype = cache_dtype or cdtype(cfg)
    b, s = tokens.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = embed_tokens(params["embedding"], tokens, cfg)

    def body_for(layer_cfg):
        def body(x, layer_p):
            return block_prefill(layer_p, x, layer_cfg, positions, max_len,
                                 cache_dtype)

        return body

    body = body_for(cfg)

    if cfg.shared_attn_every:
        every = cfg.shared_attn_every
        n_groups = cfg.n_layers // every
        window = cfg.window or _zamba_shared_window(max_len)
        caches, shared_caches = [], []
        for g in range(n_groups):
            group_p = jax.tree_util.tree_map(
                lambda a, g=g: a[g * every:(g + 1) * every], params["layers"])
            x, cache = _run_stack(body, x, group_p, cfg)
            caches.append(cache)
            x, sc = _shared_attn_prefill(params["shared_attn"], x, cfg,
                                         positions, max_len, cache_dtype, window)
            shared_caches.append(sc)
        cache = jax.tree_util.tree_map(
            lambda *cs: jnp.concatenate(cs, axis=0), *caches)
        shared = jax.tree_util.tree_map(
            lambda *cs: jnp.stack(cs, axis=0), *shared_caches)
        full_cache = {"layers": cache, "shared": shared}
    else:
        full_cache = {}
        for key, layer_cfg in _stacks(cfg):
            x, full_cache[key] = _run_stack(body_for(layer_cfg), x,
                                            params[key], cfg)

    logits = lm_logits(params["embedding"], x[:, -1:], cfg)
    return logits, full_cache


def _zamba_shared_window(max_len: int) -> Optional[int]:
    """At long context the zamba2 shared-attn blocks run windowed (4096) to
    keep cache memory bounded — documented approximation (DESIGN.md §6)."""
    return 4096 if max_len > 65536 else None


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or cdtype(cfg)

    one = block_init_cache(cfg, batch, max_len, dtype)
    k0 = cfg.first_k_dense
    full = {key: jax.tree_util.tree_map(
        lambda x, n=n: jnp.zeros((n,) + x.shape, x.dtype), one)
        for key, n in (("dense_layers", k0), ("layers", cfg.n_layers - k0))
        if n}
    if cfg.shared_attn_every:
        window = cfg.window or _zamba_shared_window(max_len)
        sc = _shared_attn_cfg(cfg)
        if window is not None:
            sc = sc.with_(window=window)
        n_groups = cfg.n_layers // cfg.shared_attn_every
        shared = A.gqa_init_cache(sc, batch, max_len, dtype)
        full["shared"] = jax.tree_util.tree_map(
            lambda x: jnp.zeros((n_groups,) + x.shape, x.dtype), shared)
    return full


def decode_step(params: Dict, tokens: jax.Array, cache, pos: jax.Array,
                cfg: ArchConfig) -> Tuple[jax.Array, Any]:
    """tokens (B,1[,Q]), pos (B,) -> (logits (B,1,V[,Q]), new cache)."""
    x = embed_tokens(params["embedding"], tokens, cfg)

    def body_for(layer_cfg):
        def body(x, xs):
            layer_p, layer_cache = xs
            return block_decode(layer_p, x, layer_cfg, layer_cache, pos)

        return body

    body = body_for(cfg)

    if cfg.shared_attn_every:
        every = cfg.shared_attn_every
        n_groups = cfg.n_layers // every
        # ring semantics with window == cache_len are exact when the cache
        # was not truncated, and give the documented windowed behaviour when
        # it was (long_500k).
        window = int(jax.tree_util.tree_leaves(cache["shared"])[0].shape[3])
        new_layer_caches, new_shared = [], []
        for g in range(n_groups):
            group = jax.tree_util.tree_map(
                lambda a, g=g: a[g * every:(g + 1) * every], params["layers"])
            gcache = jax.tree_util.tree_map(
                lambda a, g=g: a[g * every:(g + 1) * every], cache["layers"])
            x, nc = _run_stack(body, x, (group, gcache), cfg)
            new_layer_caches.append(nc)
            scache = jax.tree_util.tree_map(lambda a, g=g: a[g], cache["shared"])
            x, nsc = _shared_attn_decode(params["shared_attn"], x, cfg,
                                         scache, pos, window)
            new_shared.append(nsc)
        new_cache = {
            "layers": jax.tree_util.tree_map(
                lambda *cs: jnp.concatenate(cs, axis=0), *new_layer_caches),
            "shared": jax.tree_util.tree_map(
                lambda *cs: jnp.stack(cs, axis=0), *new_shared),
        }
    else:
        new_cache = {}
        for key, layer_cfg in _stacks(cfg):
            x, new_cache[key] = _run_stack(body_for(layer_cfg), x,
                                           (params[key], cache[key]), cfg)

    logits = lm_logits(params["embedding"], x, cfg)
    return logits, new_cache
