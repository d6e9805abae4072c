"""Mixture-of-Experts FFN: routing, then the experts held here.

Routing is whole on every device: the router scores all ``n_experts``
(softmax, or DeepSeek-V3's sigmoid), picks ``top_k`` (by score, plus the
correction bias of aux-loss-free balancing where the config keeps one)
and weighs the picks by their scores, renormalised and scaled as the
config says.  The bias only selects; it never enters a weight.

Two ways to run the experts:

* the **dropless expert-share layer** (every single-device call): the
  layer holds experts ``first_held .. first_held + held - 1`` (all of
  them unless the config says it is one chip's share of an
  expert-parallel deployment), sorts the (token, k) assignments that
  fall to them by expert, runs the three SwiGLU matmuls as grouped
  matmuls over those groups (``ops.grouped_matmul``: the Pallas ``gmm``,
  in interpret mode off a TPU) and gathers the rows back,
  weighted, in f32.  Shapes are static (room for every assignment);
  only the group sizes vary, so no assignment to a held expert is ever
  dropped.  What experts held elsewhere would add is left out;
* the **GShard capacity dispatch** of a multi-device mesh: one-hot
  dispatch and combine tensors per routing group, the expert axis
  sharded over ``ep``, tokens beyond an expert's capacity dropped.

Shared experts (DeepSeekMoE) are one SwiGLU of width ``n_shared * d_ff``
that every token passes through, whole on every device.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.kernels import ops

from .layers import cdtype, dense_init, mlp_apply, mlp_init, pdtype

#: the one parameter of a MoE layer that is state, not trained: the
#: router's correction bias (no gradient, no AdamW moments, no decay)
BIAS = "router_bias"


def expert_width(cfg: ArchConfig) -> int:
    return cfg.moe.d_ff or cfg.d_ff


def moe_init(rng, cfg: ArchConfig) -> Dict:
    m = cfg.moe
    d, f = cfg.d_model, expert_width(cfg)
    dt = pdtype(cfg)
    ks = jax.random.split(rng, 5)
    k1, k2, k3 = jax.random.split(ks[1], 3)
    n = m.held
    p = {
        "router": dense_init(ks[0], d, m.n_experts, dt),
        "experts": {
            "w_gate": (jax.random.normal(k1, (n, d, f)) / np.sqrt(d)).astype(dt),
            "w_up": (jax.random.normal(k2, (n, d, f)) / np.sqrt(d)).astype(dt),
            "w_down": (jax.random.normal(k3, (n, f, d)) / np.sqrt(f)).astype(dt),
        },
    }
    if m.bias_update:
        p[BIAS] = jnp.zeros((m.n_experts,), jnp.float32)
    if m.n_shared:
        p["shared"] = mlp_init(ks[2], cfg.with_(d_ff=m.n_shared * f))
    return p


# ---------------------------------------------------------------------------
# routing


def route(params: Dict, x: jax.Array, cfg: ArchConfig):
    """x (N, D) -> (scores (N, E) f32, picks (N, K) int32, weights (N, K)
    f32).  The router's matmul runs in f32 at full precision."""
    m = cfg.moe
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    select = scores
    if BIAS in params:
        select = scores + jax.lax.stop_gradient(params[BIAS])[None]
    _, picks = jax.lax.top_k(select, m.top_k)
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return scores, picks, weights * m.routed_scale


def balance_loss(scores: jax.Array, picks: jax.Array, cfg: ArchConfig,
                 batch: int) -> jax.Array:
    """The auxiliary balance loss, before its weight.

    ``seq`` (DeepSeek-V3): per sequence, ``sum_i f_i P_i`` with
    ``f_i = E / (K T) * #{t : i picked}`` and ``P_i`` the mean over the
    sequence of ``s_i / sum_j s_j``, averaged over the sequences.
    ``switch``: ``E * sum_i mean(p_i) * mean(picked_i)`` over the batch."""
    m = cfg.moe
    n, e = scores.shape
    picked = jax.nn.one_hot(picks, e, dtype=jnp.float32).sum(1)    # (N, E)
    if m.aux == "seq":
        t = n // batch
        share = scores / jnp.sum(scores, axis=-1, keepdims=True)
        f = picked.reshape(batch, t, e).sum(1) * (e / (m.top_k * t))
        p = share.reshape(batch, t, e).mean(1)
        return jnp.mean(jnp.sum(f * p, axis=-1))
    return e * jnp.sum(scores.mean(0) * picked.mean(0))


# ---------------------------------------------------------------------------
# the dropless expert-share layer


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(x, token_of_row, inv, held, k):
    """Rows of ``x`` in expert order: row ``r`` is token
    ``token_of_row[r]``.  The transpose is a gather too (no scatter): each
    token sums the rows of its held assignments (``inv`` maps assignment
    ``n * k + j`` to its row)."""
    return x[token_of_row]


def _dispatch_fwd(x, token_of_row, inv, held, k):
    return x[token_of_row], (inv, held)


def _dispatch_bwd(k, res, g):
    inv, held = res
    rows = jnp.where(held[:, None], g[inv].astype(jnp.float32), 0.0)
    gx = rows.reshape(held.shape[0] // k, k, -1).sum(1)
    return gx.astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y_rows, inv, held, order, row_ok):
    """Each assignment's row of expert output, 0 where the assignment's
    expert is not held here; the transpose gathers in the other order."""
    return jnp.where(held[:, None], y_rows[inv], 0)


def _combine_fwd(y_rows, inv, held, order, row_ok):
    return jnp.where(held[:, None], y_rows[inv], 0), (order, row_ok)


def _combine_bwd(res, g):
    order, row_ok = res
    return jnp.where(row_ok[:, None], g[order], 0), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def dropless_experts(experts: Dict, x: jax.Array, picks: jax.Array,
                     weights: jax.Array, cfg: ArchConfig):
    """The held experts' part of the layer for x (N, D): (y (N, D) f32,
    assignments per held expert (held,) int32).

    The (token, k) assignments are sorted by held expert (the rest after
    them); the grouped matmuls compute the held groups' rows only.  Rows
    past the groups are never read: the combine and the transposes mask
    them out, whatever the kernel left there."""
    m = cfg.moe
    dt = cdtype(cfg)
    n, d = x.shape
    k, held = m.top_k, m.held
    local = picks - m.first_held
    is_held = ((local >= 0) & (local < held)).reshape(-1)          # (N K,)
    key = jnp.where(is_held, local.reshape(-1), held)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)        # row -> asg
    inv = jnp.argsort(order).astype(jnp.int32)                     # asg -> row
    rows = -(-n * k // ops.GROUPED_ROWS) * ops.GROUPED_ROWS
    order = jnp.pad(order, (0, rows - n * k))
    row_ok = jnp.arange(rows) < jnp.sum(sizes)

    xs = _dispatch(x.astype(dt), order // k, inv, is_held, k)      # (rows, D)
    g = ops.grouped_matmul(xs, experts["w_gate"].astype(dt), sizes)
    u = ops.grouped_matmul(xs, experts["w_up"].astype(dt), sizes)
    ys = ops.grouped_matmul(jax.nn.silu(g) * u, experts["w_down"].astype(dt),
                            sizes)
    out = _combine(ys, inv, is_held, order, row_ok).reshape(n, k, d)
    w = jnp.where(is_held.reshape(n, k), weights, 0.0)
    # an elementwise product and a sum, which fuse: the f32 (N, K, D)
    # products and their cotangent are never stored
    y = jnp.sum(w[..., None] * out, axis=1)
    return y, sizes


def moe_apply(params: Dict, x: jax.Array, cfg: ArchConfig
              ) -> Tuple[jax.Array, jax.Array, Dict]:
    """x: (B, T, D) -> (y, aux_loss, stats).

    ``stats``: ``picks`` (E,), the step's assignments to every expert
    (the bias update counts them all), and ``held`` (held,), those
    computed here.  A single device runs the dropless expert-share
    layer; a multi-device mesh the capacity dispatch."""
    m = cfg.moe
    b, t, d = x.shape
    xt = x.astype(cdtype(cfg))
    path = ops.moe_path()
    ops.MOE.count(path, (tuple(x.shape), m.n_experts, m.held, m.top_k,
                         str(xt.dtype)))
    x2 = xt.reshape(b * t, d)
    scores, picks, weights = route(params, x2, cfg)
    aux = balance_loss(scores, picks, cfg, b)
    counts = jnp.bincount(picks.reshape(-1), length=m.n_experts)
    if path == "capacity":
        y, held = _capacity_experts(params["experts"], xt, picks, weights, cfg)
    else:
        y, held = dropless_experts(params["experts"], x2, picks, weights, cfg)
        y = y.reshape(b, t, d)
    y = y.astype(xt.dtype)
    if m.n_shared:
        y = y + mlp_apply(params["shared"], xt, cfg)
    stats = {"picks": counts.astype(jnp.int32), "held": held}
    return shard(y, "dp", "sp", None), aux.astype(jnp.float32), stats


# ---------------------------------------------------------------------------
# the GShard capacity dispatch (multi-device meshes)


def _capacity(group_tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    cap = int(np.ceil(m.top_k * group_tokens * m.capacity_factor / m.n_experts))
    return max(4, ((cap + 3) // 4) * 4)  # pad to multiple of 4 for layout


def _capacity_experts(we: Dict, xt: jax.Array, picks: jax.Array,
                      weights: jax.Array, cfg: ArchConfig):
    """GShard-style grouped dispatch: each sequence is a routing group, so
    the dispatch one-hots are (B, T, E, C) with C = k·T·cf/E; the expert
    axis shards over ``ep`` and GSPMD turns the dispatch einsums into
    all-to-alls.  Assignments beyond an expert's capacity are dropped.
    Every expert is held: the mesh holds the whole layer."""
    m = cfg.moe
    dt = cdtype(cfg)
    b, t, d = xt.shape
    xt = shard(xt, "dp", None, None)
    gate_idx = picks.reshape(b, t, m.top_k)
    gate_vals = weights.reshape(b, t, m.top_k)
    cap = _capacity(t, cfg)
    onehot = jax.nn.one_hot(gate_idx, m.n_experts, dtype=jnp.float32)  # (B,T,K,E)
    # position of each (token, k) within its expert's bucket, per group
    flat = onehot.reshape(b, t * m.top_k, m.n_experts)
    pos_in_expert = (jnp.cumsum(flat, axis=1) - 1.0).reshape(
        b, t, m.top_k, m.n_experts)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)                   # (B,T,K)
    keep = pos < cap                                                 # capacity drop
    gate_vals = gate_vals * keep.astype(jnp.float32)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    masked = onehot * keep[..., None].astype(jnp.float32)
    dispatch = jnp.einsum("btke,btkc->btec", masked, pos_oh)
    combine = jnp.einsum("btk,btke,btkc->btec", gate_vals, onehot, pos_oh)
    dispatch = shard(dispatch.astype(dt), "dp", None, "ep", "ep2")
    combine = shard(combine.astype(dt), "dp", None, "ep", "ep2")

    xe = jnp.einsum("btec,btd->ebcd", dispatch, xt)                  # (E,B,C,D)
    xe = shard(xe, "ep", None, "ep2", None)
    g = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", xe, we["w_gate"].astype(dt)))
    u = jnp.einsum("ebcd,edf->ebcf", xe, we["w_up"].astype(dt))
    h = shard(g * u, "ep", None, "ep2", None)
    ye = jnp.einsum("ebcf,efd->ebcd", h, we["w_down"].astype(dt))
    ye = shard(ye, "ep", None, "ep2", None)
    y = jnp.einsum("btec,ebcd->btd", combine, ye)                    # (B,T,D)
    held = jnp.sum(masked, axis=(0, 1, 2)).astype(jnp.int32)
    return y, held


# ---------------------------------------------------------------------------
# after the optimizer step


def update_bias(bias: jax.Array, picks: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Aux-loss-free balancing (DeepSeek-V3 §2.1.2): ``b_i += gamma *
    sign(mean_j c_j - c_i)`` with ``c_i`` the step's assignments to expert
    ``i``.  ``bias`` and ``picks`` may carry a leading layer axis."""
    c = picks.astype(jnp.float32)
    step = jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)
    return bias + cfg.moe.bias_update * step


def step_counters(held: jax.Array, bias) -> Dict[str, jax.Array]:
    """A step's MoE counters from the per-layer ``held`` assignment counts
    (L, held) and the updated biases (L, E) or None:
    ``moe.rows_local`` (assignments computed here, over all layers),
    ``moe.load_max_over_mean`` (the busiest held expert over the held
    experts' mean, in the worst layer) and ``moe.bias_abs_mean``."""
    h = held.astype(jnp.float32)
    ratio = jnp.max(h, axis=-1) / jnp.maximum(jnp.mean(h, axis=-1), 1e-9)
    out = {"moe.rows_local": jnp.sum(h),
           "moe.load_max_over_mean": jnp.max(ratio)}
    if bias is not None:
        out["moe.bias_abs_mean"] = jnp.mean(jnp.abs(bias))
    return out

