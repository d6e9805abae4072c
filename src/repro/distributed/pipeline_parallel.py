"""Pipeline parallelism: GPipe-style microbatching over a mesh axis.

``pipelined`` runs a homogeneous layer stack as P pipeline stages over the
``stage`` mesh axis inside one shard_map: every stage holds n_layers/P
layers; microbatches stream through with ``ppermute`` boundary transfers.
The classic rotation trick runs stages for (M + P - 1) ticks, each device
computing on the microbatch currently resident — bubble fraction
(P-1)/(M+P-1).

The production configs default to FSDP+TP (every assigned model fits), but
this module is wired into the step builders via ``pp_stages`` and carries
the multi-pod story where a model would NOT fit one pod's HBM: stage the
layer stack across pods ("pod" becomes the stage axis) so each pod holds
1/P of the parameters, trading bubble for memory.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipelined(
    layer_fn: Callable,       # (layer_params, x) -> x
    mesh: Mesh,
    stage_axis: str,
    n_microbatches: int,
):
    """Build a pipelined stack applier.

    Returns ``apply(stacked_params, x)`` where ``stacked_params`` leaves
    have leading dim n_layers (n_layers % n_stages == 0) and ``x`` is
    (batch, ...) with batch % n_microbatches == 0.
    """
    n_stages = mesh.shape[stage_axis]

    def stage_body(params_stage, x_stage):
        """Runs inside shard_map: params_stage has this stage's layers."""
        my_stage = lax.axis_index(stage_axis)
        m = n_microbatches
        mb = x_stage.reshape((m, x_stage.shape[0] // m) + x_stage.shape[1:])
        n_ticks = m + n_stages - 1
        # the carries differ per stage: type them as varying over the axis
        outputs = lax.pcast(jnp.zeros_like(mb), stage_axis, to="varying")

        def run_layers(x):
            def body(x, lp):
                return layer_fn(lp, x), None
            x, _ = lax.scan(body, x, params_stage)
            return x

        def tick(carry, t):
            buf, outputs = carry
            # which microbatch is entering stage 0 this tick
            feed = jnp.where(t < m, t, 0)
            x_in = jnp.where(my_stage == 0,
                             mb[feed],
                             buf)
            active = (t - my_stage >= 0) & (t - my_stage < m)
            y = run_layers(x_in)
            y = jnp.where(active, y, x_in)
            # pass to next stage; last stage's output wraps to 0 (ignored)
            nxt = lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            write = (my_stage == n_stages - 1) & (t >= n_stages - 1)
            outputs = lax.cond(
                write,
                lambda o: o.at[out_idx].set(y),
                lambda o: o,
                outputs,
            )
            return (nxt, outputs), None

        buf0 = lax.pcast(jnp.zeros_like(mb[0]), stage_axis, to="varying")
        (_, outputs), _ = lax.scan(tick, (buf0, outputs),
                                   jnp.arange(n_ticks))
        # only the last stage's outputs are real: broadcast them to every
        # stage so the result is replicated over the stage axis
        last = jnp.where(my_stage == n_stages - 1, outputs, 0)
        return lax.psum(last, stage_axis).reshape(x_stage.shape)

    def apply(stacked_params, x):
        param_specs = jax.tree_util.tree_map(
            lambda _: P(stage_axis), stacked_params)
        fn = jax.shard_map(
            stage_body, mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
        )
        with jax.set_mesh(mesh):
            return fn(stacked_params, x)

    return apply
