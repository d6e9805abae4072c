"""Plain reference of a Llama-architecture decoder (SmolLM is one), for
the train cells' check: its loss, gradients and AdamW steps in
straightforward ``jax.numpy``, with nothing of the program imported.

Follows the published architecture (``LlamaForCausalLM``): token
embedding; per layer RMSNorm -> grouped-query attention with rotary
embeddings on the rotate-half convention and query head ``h`` reading
key/value head ``h // (heads / kv_heads)`` -> residual -> RMSNorm -> SiLU
gated MLP -> residual; a final RMSNorm and the output head, tied to the
embedding where ``tie_word_embeddings`` says so.  The loss is the mean
next-token cross-entropy.

``dot`` decides the arithmetic of every matrix product: :func:`dot_f32`
(float32 at the highest precision, the reference) or :func:`dot_fp8`
(operands rounded to float8 e4m3 with a per-tensor scale, float32
accumulation: the control, one precision below the bfloat16 that the
configuration states).  The parameter tree is laid out as the program
stores it (layers stacked on a leading axis), so both can be fed the
same weights.  Rows are processed one at a time and each layer is
rematerialised, so that the reference fits beside nothing else on one
chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dot_f32(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


@jax.custom_vjp
def _fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


# the backward pass multiplies the rounded operands in float32; the
# cotangent is passed through as it is (an unscaled cast of a gradient to
# float8 would flush it to zero)
_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


def dot_fp8(spec, a, b):
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST)


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h,
            cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def init_params(cfg: dict, key) -> dict:
    """Seeded float32 weights in the program's layout: normal with std
    ``1/sqrt(fan_in)`` for projections, 0.02 for the embedding, ones for
    the norms."""
    d, h, g, hd, f, layers, vocab = dims(cfg)
    ks = iter(jax.random.split(key, 9))

    def w(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) / np.sqrt(fan_in)

    emb = {"embed": jax.random.normal(next(ks), (vocab, d), jnp.float32) * 0.02,
           "final_norm": jnp.ones((d,), jnp.float32)}
    if not cfg["tie_word_embeddings"]:
        emb["lm_head"] = w((d, vocab), d)
    lay = {
        "norm1": jnp.ones((layers, d), jnp.float32),
        "norm2": jnp.ones((layers, d), jnp.float32),
        "attn": {"wq": w((layers, d, h * hd), d),
                 "wk": w((layers, d, g * hd), d),
                 "wv": w((layers, d, g * hd), d),
                 "wo": w((layers, h * hd, d), h * hd)},
        "mlp": {"w_gate": w((layers, d, f), d), "w_up": w((layers, d, f), d),
                "w_down": w((layers, f, d), f)},
    }
    return {"embedding": emb, "layers": lay}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (S, heads, hd), positions 0..S-1, rotate-half convention."""
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_loss(params, tokens, labels, cfg: dict, dot=dot_f32):
    """Mean cross-entropy of one row (tokens, labels: (S,) int32)."""
    d, h, g, hd, f, layers, vocab = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    emb = params["embedding"]
    x = emb["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a = _rms(x, p["norm1"], eps)
        q = _rope(dot("sd,dk->sk", a, p["attn"]["wq"]).reshape(s, h, hd), theta)
        k = _rope(dot("sd,dk->sk", a, p["attn"]["wk"]).reshape(s, g, hd), theta)
        v = dot("sd,dk->sk", a, p["attn"]["wv"]).reshape(s, g, hd)
        k = jnp.repeat(k, h // g, axis=1)
        v = jnp.repeat(v, h // g, axis=1)
        sc = dot("qhd,khd->hqk", q, k) / np.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = dot("hqk,khd->qhd", pr, v).reshape(s, h * hd)
        x = x + dot("sk,kd->sd", o, p["attn"]["wo"])
        m = _rms(x, p["norm2"], eps)
        gate = dot("sd,df->sf", m, p["mlp"]["w_gate"])
        up = dot("sd,df->sf", m, p["mlp"]["w_up"])
        x = x + dot("sf,fd->sd", jax.nn.silu(gate) * up, p["mlp"]["w_down"])
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = _rms(x, emb["final_norm"], eps)
    head = emb["embed"].T if cfg["tie_word_embeddings"] else emb["lm_head"]
    logits = dot("sd,dv->sv", x, head)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def loss_and_grad(params, tokens, labels, cfg: dict, dot=dot_f32):
    """Mean loss and gradient over a (B, S) batch, one row at a time."""
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)

    def body(carry, row):
        total, acc = carry
        loss, grad = jax.value_and_grad(row_loss)(params, row[0], row[1],
                                                  cfg, dot)
        return (total + loss, jax.tree_util.tree_map(jnp.add, acc, grad)), None

    (total, acc), _ = jax.lax.scan(body, (jnp.zeros(()), zero),
                                   (tokens, labels))
    b = tokens.shape[0]
    return total / b, jax.tree_util.tree_map(lambda x: x / b, acc)


def learning_rate(step, opt: dict):
    """Linear warm-up to ``peak_lr`` over ``warmup_steps``, then cosine to
    ``min_lr_frac * peak_lr`` at ``total_steps``."""
    s = step.astype(jnp.float32)
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    frac = opt["min_lr_frac"]
    prog = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (frac + (1 - frac) * 0.5 * (1 + jnp.cos(np.pi * prog)))
    return jnp.where(s < warm, peak * s / max(warm, 1), cos)


def adamw(params, grads, m, v, step, opt: dict):
    """One AdamW step with global-norm clipping; weight decay applies to
    every stored array of rank ``decay_min_rank`` or more."""
    step = step + 1
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    lr = learning_rate(step, opt)
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def one(p, g, mm, vv):
        g = g * scale
        mm = b1 * mm + (1 - b1) * g
        vv = b2 * vv + (1 - b2) * g * g
        u = (mm / bc1) / (jnp.sqrt(vv / bc2) + eps)
        if p.ndim >= opt["decay_min_rank"]:
            u = u + wd * p
        return p - lr * u, mm, vv

    tree = jax.tree_util.tree_map(one, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda t: t[i], tree, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), step


@partial(jax.jit, static_argnames=("cfg_items", "opt_items", "precision"),
         donate_argnums=(0, 1, 2))
def train_step(params, m, v, step, tokens, labels, cfg_items, opt_items,
               precision="f32"):
    cfg, opt = dict(cfg_items), dict(opt_items)
    dot = dot_f32 if precision == "f32" else dot_fp8
    loss, grads = loss_and_grad(params, tokens, labels, cfg, dot)
    params, m, v, step = adamw(params, grads, m, v, step, opt)
    return params, m, v, step, loss


def frozen(d: dict) -> tuple:
    """A hashable form of a flat configuration dict (for static args)."""
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, str, bool))))


def run_steps(params, batches, cfg: dict, opt: dict, precision="f32"):
    """The reference's first steps from ``params`` over ``batches`` (a list
    of (tokens, labels) pairs).  Returns the loss of each step, the
    per-leaf norms of the first step's clipped gradient (as AdamW's first
    moment holds it) and of the parameters' change over all the steps."""
    p0 = params
    params = jax.tree_util.tree_map(jnp.array, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = jnp.zeros((), jnp.int32)
    losses, g1 = [], None
    for tokens, labels in batches:
        params, m, v, step, loss = train_step(
            params, m, v, step, jnp.asarray(tokens), jnp.asarray(labels),
            frozen(cfg), frozen(opt), precision)
        losses.append(float(loss))
        if g1 is None:
            g1 = leaf_norms(m, 1.0 / (1.0 - opt["b1"]))
    delta = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return losses, g1, delta


def leaf_norms(tree, scale: float = 1.0) -> dict:
    """{leaf path: float32 norm} of a parameter-shaped tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = _norms([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) * scale
            for (p, _), n in zip(flat, np.asarray(norms))}


@jax.jit
def _norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in leaves])
