"""Production mesh definitions.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the "pod"
axis carries inter-pod data parallelism (gradient all-reduce crosses the
pod boundary; everything bandwidth-heavy stays intra-pod).

Functions, not module constants: importing this module never touches jax
device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the logical-axis rules
    of ``distributed/sharding.py`` place arrays through
    ``with_sharding_constraint`` and leave the rest to the partitioner."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model: int = 1, devices=None):
    """A mesh over ``devices`` (default: every device there is)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    assert n % model == 0, (n, model)
    return auto_mesh((n // model, model), ("data", "model"), devices=devices)


def describe(mesh) -> str:
    return " x ".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)
