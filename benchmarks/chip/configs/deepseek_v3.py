"""Plain reference of the DeepSeek-V3 decoder block (Moonlight-16B-A3B is
one), for the MoE train cells' check: its loss, gradients, AdamW steps
and router-bias steps in straightforward ``jax.numpy``, with nothing of
the program imported.

Follows the published architecture (DeepSeek-V2, arXiv:2405.04434 §2.1;
DeepSeek-V3, arXiv:2412.19437 §2.1.2) at the configuration's sizes:

* token embedding; per layer RMSNorm -> MLA -> residual -> RMSNorm ->
  FFN -> residual; a final RMSNorm and the untied output head; the loss
  is the mean next-token cross-entropy plus ``aux_weight`` times the
  sequence-wise balance loss of every MoE layer;
* MLA without query compression: ``q = W_q h`` (heads x (nope + rope)),
  ``c = RMSNorm(W_dkv h)``, ``k_R = RoPE(W_kr h)`` shared by the heads,
  ``k_C = W_uk c``, ``v = W_uv c``; each head takes
  ``causal softmax([q_C; RoPE(q_R)] . [k_C; k_R] / sqrt(nope + rope)) v``,
  computed a block of queries at a time under ``jax.checkpoint``; rotary
  embeddings on the rotate-half convention;
* the first ``first_k_dense_replace`` layers' FFN is a SwiGLU of
  ``intermediate_size``; the others are MoE: ``s_i = sigmoid(u . e_i)``
  over all ``router_experts``; the picks are ``TopK(s_i + b_i)``; the
  weights ``s_i / sum_picked s_j * routed_scaling_factor`` (the bias never
  enters a weight); ``y = sum_held g_i FFN_i(u) + FFN_shared(u)``, each
  held expert computed densely on every token and masked by its weight
  (no sort); the shared experts are one SwiGLU of ``n_shared_experts x
  moe_intermediate_size``;
* balance: per sequence ``sum_i f_i P_i`` with ``f_i = E / (K T) #{t: i
  picked}``, ``P_i`` the mean of ``s_i / sum_j s_j``; after each step
  ``b_i += gamma sign(mean_j c_j - c_i)`` with ``c_i`` the step's picks of
  expert ``i``; the bias gets no gradient, no moments and no decay.

One chip's share: ``n_routed_experts`` experts are held, from
``first_expert_held`` on, and only they exist and add to ``y``; the
router scores all ``router_experts``.

``dot`` decides the arithmetic of every matrix product, as in
``llama.py``: :func:`dot_f32` (the reference) or :func:`dot_fp8` (the
control).  The parameter tree is laid out as the program stores it.  Rows
are processed one at a time, each layer rematerialised, the optimizer's
moments kept on the host a leaf at a time, so that the reference fits
beside nothing else on one chip.
"""

from __future__ import annotations

import importlib.util
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "reference_llama_for_v3", Path(__file__).resolve().parent / "llama.py")
llama = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(llama)

HIGHEST = jax.lax.Precision.HIGHEST
dot_f32, dot_fp8, learning_rate, leaf_norms = (
    llama.dot_f32, llama.dot_fp8, llama.learning_rate, llama.leaf_norms)
_rms, _rope = llama._rms, llama._rope
#: queries per block of the attention
Q_BLOCK = 1024
#: the state leaf: no gradient, no AdamW
BIAS = "router_bias"


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "e": cfg.get("router_experts") or cfg["n_routed_experts"],
            "held": cfg["n_routed_experts"],
            "first": cfg.get("first_expert_held", 0),
            "k": cfg["num_experts_per_tok"], "shared": cfg["n_shared_experts"],
            "dense": cfg["first_k_dense_replace"],
            "moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"]}


def init_params(cfg: dict, key) -> dict:
    """Seeded float32 weights in the program's layout: normal with std
    ``1/sqrt(fan_in)`` for projections and the router, 0.02 for the
    embedding, ones for the norms, zeros for the router bias."""
    n = dims(cfg)
    d, h = n["d"], n["h"]
    ks = iter(jax.random.split(key, 32))

    def w(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) / np.sqrt(fan_in)

    def attn(layers):
        return {"wq": w((layers, d, h * (n["nope"] + n["rope"])), d),
                "w_dkv": w((layers, d, n["rank"]), d),
                "kv_norm": jnp.ones((layers, n["rank"]), jnp.float32),
                "w_kr": w((layers, d, n["rope"]), d),
                "w_uk": w((layers, n["rank"], h * n["nope"]), n["rank"]),
                "w_uv": w((layers, n["rank"], h * n["dv"]), n["rank"]),
                "wo": w((layers, h * n["dv"], d), h * n["dv"])}

    def swiglu(shape_in, f):
        return {"w_gate": w(shape_in + (d, f), d), "w_up": w(shape_in + (d, f), d),
                "w_down": w(shape_in + (f, d), f)}

    def norms(layers):
        return {"norm1": jnp.ones((layers, d), jnp.float32),
                "norm2": jnp.ones((layers, d), jnp.float32)}

    L = n["moe"]
    p = {
        "embedding": {
            "embed": jax.random.normal(next(ks), (n["vocab"], d), jnp.float32) * 0.02,
            "lm_head": w((d, n["vocab"]), d),
            "final_norm": jnp.ones((d,), jnp.float32)},
        "layers": {**norms(L), "attn": attn(L), "moe": {
            "router": w((L, d, n["e"]), d),
            BIAS: jnp.zeros((L, n["e"]), jnp.float32),
            "experts": swiglu((L, n["held"]), n["fe"]),
            "shared": swiglu((L,), n["shared"] * n["fe"])}},
    }
    if n["dense"]:
        p["dense_layers"] = {**norms(n["dense"]), "attn": attn(n["dense"]),
                             "mlp": swiglu((n["dense"],), n["f"])}
    return p


def _swiglu(p, x, dot, pre=""):
    g = dot(f"{pre}sd,{pre}df->{pre}sf", x, p["w_gate"])
    u = dot(f"{pre}sd,{pre}df->{pre}sf", x, p["w_up"])
    return jax.nn.silu(g) * u


def _mla(p, a, cfg, n, dot):
    s = a.shape[0]
    h, nope, rope, dv = n["h"], n["nope"], n["rope"], n["dv"]
    theta = cfg["rope_theta"]
    q = dot("sd,dk->sk", a, p["wq"]).reshape(s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    c = _rms(dot("sd,dr->sr", a, p["w_dkv"]), p["kv_norm"], cfg["rms_norm_eps"])
    k_r = _rope(dot("sd,dr->sr", a, p["w_kr"])[:, None], theta)      # (S,1,rope)
    k = jnp.concatenate([dot("sr,rk->sk", c, p["w_uk"]).reshape(s, h, nope),
                         jnp.broadcast_to(k_r, (s, h, rope))], -1)
    v = dot("sr,rk->sk", c, p["w_uv"]).reshape(s, h, dv)
    qb = min(Q_BLOCK, s)
    scale = 1.0 / np.sqrt(nope + rope)

    @jax.checkpoint
    def block(args):
        q_blk, start = args
        sc = dot("qhd,khd->hqk", q_blk, k) * scale
        rows = start + jnp.arange(qb)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= rows, sc, -jnp.inf)
        return dot("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    starts = jnp.arange(0, s, qb)
    o = jax.lax.map(block, (q.reshape(s // qb, qb, h, nope + rope), starts))
    return dot("sk,kd->sd", o.reshape(s, h * dv), p["wo"])


def _moe(p, m, cfg, n, dot):
    """-> (y, balance loss of the row, picks per expert (E,))."""
    s = m.shape[0]
    e, k = n["e"], n["k"]
    scores = jax.nn.sigmoid(dot("sd,de->se", m, p["router"]))
    _, picks = jax.lax.top_k(scores + jax.lax.stop_gradient(p[BIAS]), k)
    g = jnp.take_along_axis(scores, picks, axis=-1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    onehot = jax.nn.one_hot(picks, e, dtype=jnp.float32)          # (S,K,E)
    gate = jnp.einsum("sk,ske->se", g, onehot)[:, n["first"]:n["first"] + n["held"]]
    hid = _swiglu(p["experts"], jnp.broadcast_to(m, (n["held"],) + m.shape), dot,
                  pre="e")                                          # (E_h,S,fe)
    y_e = dot("esf,efd->esd", hid, p["experts"]["w_down"])
    y = jnp.einsum("se,esd->sd", gate, y_e)
    y = y + dot("sf,fd->sd", _swiglu(p["shared"], m, dot), p["shared"]["w_down"])
    picked = onehot.sum((0, 1))                                     # (E,)
    f = picked * e / (k * s)
    share = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), 0)
    return y, jnp.sum(f * share), picked


def row_loss(params, tokens, labels, cfg: dict, dot=dot_f32):
    """(cross-entropy + aux_weight * balance loss, picks (L, E)) of one
    row (tokens, labels: (S,) int32)."""
    n = dims(cfg)
    eps = cfg["rms_norm_eps"]
    emb = params["embedding"]
    x = emb["embed"][tokens]

    def dense(x, p):
        x = x + _mla(p["attn"], _rms(x, p["norm1"], eps), cfg, n, dot)
        m = _rms(x, p["norm2"], eps)
        return x + dot("sf,fd->sd", _swiglu(p["mlp"], m, dot),
                       p["mlp"]["w_down"]), None

    def moe(carry, p):
        x, aux = carry
        x = x + _mla(p["attn"], _rms(x, p["norm1"], eps), cfg, n, dot)
        y, a, picked = _moe(p["moe"], _rms(x, p["norm2"], eps), cfg, n, dot)
        return (x + y, aux + a), picked

    if n["dense"]:
        x, _ = jax.lax.scan(jax.checkpoint(dense), x, params["dense_layers"])
    (x, aux), picks = jax.lax.scan(jax.checkpoint(moe), (x, jnp.zeros(())),
                                   params["layers"])
    logits = dot("sd,dv->sv", _rms(x, emb["final_norm"], eps), emb["lm_head"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold) + cfg["aux_weight"] * aux, picks


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def loss_and_grad(params, tokens, labels, cfg_items, precision="f32"):
    """Mean loss, gradient and picks (L, E) over a (B, S) batch, a row at
    a time."""
    cfg = dict(cfg_items)
    dot = dot_f32 if precision == "f32" else dot_fp8
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)

    def body(carry, row):
        total, acc, picks = carry
        (loss, p), grad = jax.value_and_grad(row_loss, has_aux=True)(
            params, row[0], row[1], cfg, dot)
        return (total + loss, jax.tree_util.tree_map(jnp.add, acc, grad),
                picks + p), None

    n = dims(cfg)
    picks0 = jnp.zeros((n["moe"], n["e"]), jnp.float32)
    (total, acc, picks), _ = jax.lax.scan(
        body, (jnp.zeros(()), zero, picks0), (tokens, labels))
    b = tokens.shape[0]
    return total / b, jax.tree_util.tree_map(lambda x: x / b, acc), picks


@jax.jit
def _global_norm(grads):
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))


@partial(jax.jit, static_argnames=("decay",), donate_argnums=(0,))
def _adamw_leaf(p, g, m, v, step, scale, lr, b1, b2, eps, wd, decay):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    u = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step)) + eps)
    if decay:
        u = u + wd * p
    return p - lr * u, m, v


def is_state(path) -> bool:
    return getattr(path[-1], "key", None) == BIAS


def adamw(params, grads, m, v, step: int, opt: dict):
    """One AdamW step with global-norm clipping (weight decay on every
    array of rank ``decay_min_rank`` or more), a leaf at a time; the
    moments ``m`` and ``v`` are host arrays (dicts by leaf path), updated
    in place.  The bias is state: left as it is."""
    gnorm = float(_global_norm(grads))
    scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    lr = float(learning_rate(jnp.asarray(step), opt))
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    gflat = jax.tree_util.tree_leaves(grads)
    out = []
    for (path, p), g in zip(flat, gflat):
        key = jax.tree_util.keystr(path)
        if is_state(path):
            out.append(p)
            continue
        p, mm, vv = _adamw_leaf(p, g, m[key], v[key], step, scale, lr,
                                opt["b1"], opt["b2"], opt["eps"],
                                opt["weight_decay"],
                                p.ndim >= opt["decay_min_rank"])
        m[key], v[key] = np.asarray(mm), np.asarray(vv)
        out.append(p)
    return jax.tree_util.tree_unflatten(tree, out)


def update_bias(params, picks, cfg: dict):
    c = picks
    step = jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)
    moe = dict(params["layers"]["moe"])
    moe[BIAS] = moe[BIAS] + cfg["bias_update_speed"] * step
    return {**params, "layers": {**params["layers"], "moe": moe}}


def frozen(d: dict) -> tuple:
    return llama.frozen(d)


def run_steps(params, batches, cfg: dict, opt: dict, precision="f32"):
    """The reference's first steps from ``params`` over ``batches`` (a list
    of (tokens, labels) pairs).  Returns the loss of each step, the
    per-leaf norms of the first step's clipped gradient (as AdamW's first
    moment holds it), of the parameters' change over all the steps, the
    first step's picks per MoE layer and expert (L, E), and the routers'
    bias after the steps (L, E)."""
    p0 = jax.tree_util.tree_map(np.asarray, params)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    m = {jax.tree_util.keystr(k): np.zeros(x.shape, np.float32) for k, x in flat}
    v = {k: np.zeros_like(x) for k, x in m.items()}
    losses, g1, picks1 = [], None, None
    for i, (tokens, labels) in enumerate(batches):
        loss, grads, picks = loss_and_grad(
            params, jnp.asarray(tokens), jnp.asarray(labels), frozen(cfg),
            precision)
        params = adamw(params, grads, m, v, i + 1, opt)
        del grads
        params = update_bias(params, picks, cfg)
        losses.append(float(loss))
        if g1 is None:
            g1 = {k: float(np.linalg.norm(x)) / (1.0 - opt["b1"])
                  for k, x in m.items()}
            picks1 = np.asarray(picks)
    delta = host_delta_norms(params, p0)
    return losses, g1, delta, picks1, np.asarray(params["layers"]["moe"][BIAS])


def host_delta_norms(params, p0) -> dict:
    """{leaf path: norm of (params - p0)}, with ``p0`` on the host."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    flat0 = jax.tree_util.tree_leaves(p0)
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(x, np.float32) - np.asarray(x0, np.float32)))
        for (k, x), x0 in zip(flat, flat0)}
