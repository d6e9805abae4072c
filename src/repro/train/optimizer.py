"""AdamW optimizer + LR schedules (pure pytree implementation).

Optimizer states share the parameter sharding (ZeRO: m/v are FSDP-sharded
exactly like their parameters), so the dry-run memory analysis reflects a
real sharded-optimizer training step.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class AdamWState(NamedTuple):
    step: jax.Array          # ()
    m: Dict                  # like params
    v: Dict                  # like params


@dataclass(frozen=True)
class AdamW:
    schedule: Callable[[jax.Array], jax.Array]
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0

    def init(self, params) -> AdamWState:
        zeros = lambda p: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), p
        )
        return AdamWState(jnp.zeros((), jnp.int32), zeros(params), zeros(params))

    def update(self, grads, state: AdamWState, params,
               frozen=None) -> Tuple[Dict, AdamWState]:
        """One step.  ``frozen``, a tree of bools like ``params`` (or
        None), marks the leaves that are the model's state, not trained:
        they are returned as they are, never updated nor decayed."""
        step = state.step + 1
        gf = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        if self.clip_norm is not None:
            gnorm = global_norm(gf)
            scale = jnp.minimum(1.0, self.clip_norm / (gnorm + 1e-9))
            gf = jax.tree_util.tree_map(lambda g: g * scale, gf)
        m = jax.tree_util.tree_map(
            lambda mm, g: self.b1 * mm + (1 - self.b1) * g, state.m, gf)
        v = jax.tree_util.tree_map(
            lambda vv, g: self.b2 * vv + (1 - self.b2) * g * g, state.v, gf)
        bc1 = 1 - self.b1 ** step.astype(jnp.float32)
        bc2 = 1 - self.b2 ** step.astype(jnp.float32)
        lr = self.schedule(step)

        def upd(p, mm, vv):
            mhat = mm / bc1
            vhat = vv / bc2
            u = mhat / (jnp.sqrt(vhat) + self.eps)
            if self.weight_decay and p.ndim >= 2:
                u = u + self.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

        if frozen is None:
            frozen = jax.tree_util.tree_map(lambda _: False, params)
        new_params = jax.tree_util.tree_map(
            lambda keep, p, mm, vv: p if keep else upd(p, mm, vv),
            frozen, params, m, v)
        return new_params, AdamWState(step, m, v)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree_util.tree_leaves(tree))
    )


def cosine_schedule(peak_lr: float, warmup: int, total: int,
                    floor_frac: float = 0.1) -> Callable:
    def f(step):
        s = step.astype(jnp.float32)
        warm = peak_lr * s / max(warmup, 1)
        prog = jnp.clip((s - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = peak_lr * (floor_frac + (1 - floor_frac) * 0.5 *
                         (1 + jnp.cos(np.pi * prog)))
        return jnp.where(s < warmup, warm, cos)
    return f


def make_optimizer(peak_lr: float = 3e-4, warmup: int = 100,
                   total: int = 10_000, **kw) -> AdamW:
    return AdamW(schedule=cosine_schedule(peak_lr, warmup, total), **kw)
