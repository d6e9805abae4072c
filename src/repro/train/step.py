"""Step builders: sharded train / prefill / serve steps for any arch x cell.

Produces jitted functions with explicit in/out shardings for a given mesh:
  * params + optimizer state: FSDP auto-sharding (largest dim over
    pod x data, second over model) — ZeRO-3 style
  * activations: logical-axis constraints inside the model code
  * KV/state caches: generic [stack, dp, tp_kv, sp_kv, ...] pattern whose
    divisibility fallback picks head- or sequence-sharding per arch
  * donation: params/opt_state (train), cache (serve) — in-place buffers

These are exactly the functions the multi-pod dry-run lowers and compiles.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ShapeCell
from repro.distributed.sharding import (
    AxisRules, auto_param_sharding, axis_rules, shard,
)
from repro.models.registry import ModelBundle

from .grad_compress import compress_grads, init_error_state
from .optimizer import AdamW, AdamWState, make_optimizer


def _ns(mesh, *parts):
    return NamedSharding(mesh, P(*parts))


def batch_sharding(mesh: Mesh, shapes: Dict, rules: AxisRules):
    """tokens/labels (B, S[, Q]) -> batch sharded over dp."""
    def one(leaf):
        spec = rules.spec(["dp"] + [None] * (len(leaf.shape) - 1), leaf.shape)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(one, shapes)


def cache_sharding(mesh: Mesh, cache_shapes, rules: AxisRules):
    """Generic cache rule: [stack, dp, tp_kv, sp_kv, None...].

    The AxisRules divisibility+dedup logic resolves this per tensor: kv
    heads shard over model when they divide it, otherwise the cache
    sequence dim takes the model axis (S-sharded decode), otherwise
    replicate — every assigned arch lowers with this one pattern.
    """
    def one(leaf):
        rank = len(leaf.shape)
        logical = [None, "dp", "tp_kv", "sp_kv"][:rank]
        logical += [None] * (rank - len(logical))
        return NamedSharding(mesh, rules.spec(logical, leaf.shape))
    return jax.tree_util.tree_map(one, cache_shapes)


# ---------------------------------------------------------------------------
# train


def make_train_step(
    bundle: ModelBundle,
    mesh: Mesh,
    optimizer: Optional[AdamW] = None,
    grad_compression: bool = False,
    microbatches: int = 1,
    rules_mapping: Optional[Dict] = None,
    fsdp_axes: Optional[Tuple] = None,
) -> Tuple[Callable, Dict]:
    """-> (jitted step, shardings dict). step(params, opt, batch) -> ..."""
    rules = AxisRules(mesh, rules_mapping)
    opt = optimizer or make_optimizer()

    def loss_fn(params, batch):
        loss, metrics = bundle.loss(params, batch)
        return loss, metrics

    def train_step(params, opt_state, error_state, batch):
        with axis_rules(rules):
            if microbatches > 1:
                grads, loss, metrics = _accumulated_grads(
                    loss_fn, params, batch, microbatches)
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
            if grad_compression:
                grads, error_state = compress_grads(grads, error_state)
            new_params, new_opt = opt.update(grads, opt_state, params,
                                             bundle.state_mask(params))
            new_params, metrics = bundle.after_step(new_params, metrics)
        return new_params, new_opt, error_state, {
            "loss": loss.astype(jnp.float32), **{
                k: v.astype(jnp.float32) for k, v in metrics.items()},
        }

    param_shapes = bundle.param_shapes()
    p_sh = auto_param_sharding(param_shapes, mesh, fsdp_axes=fsdp_axes)
    opt_sh = AdamWState(_ns(mesh), p_sh, p_sh)
    err_sh = p_sh if grad_compression else _ns(mesh)
    cell_like = {"tokens": None, "labels": None}

    def in_shardings_for(batch_shapes):
        return (p_sh, opt_sh, err_sh, batch_sharding(mesh, batch_shapes, rules))

    shardings = {
        "params": p_sh,
        "opt": opt_sh,
        "err": err_sh,
        "in_shardings_for": in_shardings_for,
        "rules": rules,
    }

    def jitted(batch_shapes):
        return jax.jit(
            train_step,
            in_shardings=in_shardings_for(batch_shapes),
            out_shardings=(p_sh, opt_sh, err_sh, _ns(mesh)),
            donate_argnums=(0, 1, 2),
        )

    return jitted, shardings


def _accumulated_grads(loss_fn, params, batch, n: int):
    """Gradient accumulation over n microbatches (scan, constant memory)."""
    def split(x):
        b = x.shape[0]
        return x.reshape(n, b // n, *x.shape[1:])

    micro = jax.tree_util.tree_map(split, batch)

    def body(carry, mb):
        acc, loss_sum = carry
        (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree_util.tree_map(jnp.add, acc, g)
        return (acc, loss_sum + loss), metrics

    zero = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (gsum, loss_sum), metrics = jax.lax.scan(body, (zero, jnp.zeros(())), micro)
    grads = jax.tree_util.tree_map(lambda g: g / n, gsum)
    last_metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
    return grads, loss_sum / n, last_metrics


def init_train_state(bundle: ModelBundle, mesh: Mesh, seed: int = 0,
                     optimizer: Optional[AdamW] = None,
                     grad_compression: bool = False):
    """Materialize sharded params + optimizer state on the mesh."""
    opt = optimizer or make_optimizer()
    param_shapes = bundle.param_shapes()
    p_sh = auto_param_sharding(param_shapes, mesh)

    params = jax.jit(
        lambda: bundle.init(jax.random.PRNGKey(seed)), out_shardings=p_sh
    )()
    opt_state = jax.jit(lambda p: opt.init(p),
                        out_shardings=AdamWState(_ns(mesh), p_sh, p_sh))(params)
    err = (jax.jit(init_error_state, out_shardings=p_sh)(params)
           if grad_compression else jnp.zeros(()))
    return params, opt_state, err


# ---------------------------------------------------------------------------
# serving


def make_prefill_step(bundle: ModelBundle, mesh: Mesh, max_len: int,
                      rules_mapping: Optional[Dict] = None,
                      fsdp_axes: Optional[Tuple] = None):
    rules = AxisRules(mesh, rules_mapping)

    def prefill_step(params, tokens):
        with axis_rules(rules):
            return bundle.prefill(params, tokens, max_len=max_len)

    p_sh = auto_param_sharding(bundle.param_shapes(), mesh,
                               fsdp_axes=fsdp_axes)

    def jitted(token_shapes):
        cache_shapes = jax.eval_shape(
            lambda: bundle.init_cache(token_shapes.shape[0], max_len))
        return jax.jit(
            prefill_step,
            in_shardings=(p_sh, batch_sharding(mesh, token_shapes, rules)),
            out_shardings=(_ns(mesh), cache_sharding(mesh, cache_shapes, rules)),
        )

    return jitted, {"params": p_sh, "rules": rules}


def make_serve_step(bundle: ModelBundle, mesh: Mesh, cell: ShapeCell,
                    rules_mapping: Optional[Dict] = None,
                    fsdp_axes: Optional[Tuple] = None):
    """One-token decode step with a seq_len-sized cache (decode cells)."""
    rules = AxisRules(mesh, rules_mapping)

    def serve_step(params, tokens, cache, pos):
        with axis_rules(rules):
            logits, new_cache = bundle.decode_step(params, tokens, cache, pos)
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_cache

    p_sh = auto_param_sharding(bundle.param_shapes(), mesh,
                               fsdp_axes=fsdp_axes)
    cache_shapes = jax.eval_shape(
        lambda: bundle.init_cache(cell.global_batch, cell.seq_len))
    c_sh = cache_sharding(mesh, cache_shapes, rules)
    multi_q = bundle.cfg.n_codebooks > 1
    tok_shape = (
        (cell.global_batch, 1, bundle.cfg.n_codebooks) if multi_q
        else (cell.global_batch, 1)
    )
    tok_sh = NamedSharding(
        mesh, rules.spec(["dp"] + [None] * (len(tok_shape) - 1), tok_shape))
    pos_sh = NamedSharding(mesh, rules.spec(["dp"], (cell.global_batch,)))

    jitted = jax.jit(
        serve_step,
        in_shardings=(p_sh, tok_sh, c_sh, pos_sh),
        out_shardings=(tok_sh, _ns(mesh), c_sh),
        donate_argnums=(2,),
    )
    return jitted, {"params": p_sh, "cache": c_sh, "rules": rules}
