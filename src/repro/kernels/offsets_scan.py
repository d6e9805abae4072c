"""Pallas TPU kernel: collection sizes -> cluster-relative end offsets.

The offset-column construction (inclusive prefix sum) is the central
nested-data transform of the paper's format (§3): every variable-length
collection's sizes are integrated into cluster-relative offsets at seal
time.  On TPU this runs as a single-pass blocked scan over a lane-dense
``(rows, 128)`` layout: the grid is sequential on a TensorCore, so the
running carry lives in VMEM scratch and flows across block invocations.

Mosaic has no ``cumsum``, so :func:`block_scan` builds the tile's scan
from matmuls against 0/1 triangular masks on the MXU.  Each int32 is fed
as its four byte planes (0..255, exact in bf16) with f32 accumulation
(exact below 2**24), and the planes recombine with int32 shifts and adds —
so the scan is exact modulo 2**32, bit-identical to the numpy reference.

This is also exactly the primitive a *distributed* writer needs to turn
per-host cluster sizes into file extents (DESIGN.md §3.2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

LANES = 128
#: sublane rows per grid step (a block holds ROWS * 128 elements)
DEFAULT_ROWS = 128


def _mask(n: int, m: int, keep=None) -> jax.Array:
    """(n, m) bf16 0/1 matrix: ones where ``keep(row_iota, col_iota)``,
    all ones without ``keep``."""
    if keep is None:
        return jnp.ones((n, m), jnp.bfloat16)
    i = jax.lax.broadcasted_iota(jnp.int32, (n, m), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, m), 1)
    return jnp.where(keep(i, j), 1.0, 0.0).astype(jnp.bfloat16)


def _exact_dot(x: jax.Array, m: jax.Array, left: bool) -> jax.Array:
    """``m @ x`` (left) or ``x @ m`` for int32 ``x`` and a 0/1 matrix ``m``,
    exact modulo 2**32 while each byte plane's sums stay below 2**24."""
    acc = None
    for k in range(4):
        b = ((x >> (8 * k)) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        p = (jnp.dot(m, b, preferred_element_type=jnp.float32) if left
             else jnp.dot(b, m, preferred_element_type=jnp.float32))
        term = p.astype(jnp.int32) << (8 * k)
        acc = term if acc is None else acc + term
    return acc


def block_scan(x: jax.Array, carry: jax.Array):
    """Row-major inclusive prefix sum of an int32 ``(R, 128)`` tile.

    ``carry`` is an ``(R, 128)`` tile holding the running total in every
    element.  Returns ``(scan + carry, carry + tile total)``.
    """
    r = x.shape[0]
    lane_scan = _exact_dot(x, _mask(LANES, LANES, lambda i, j: i <= j), False)
    row_total = _exact_dot(x, _mask(LANES, LANES), False)
    rows_before = _exact_dot(row_total, _mask(r, r, lambda i, j: j < i), True)
    tile_total = _exact_dot(row_total, _mask(r, r), True)
    return lane_scan + rows_before + carry, carry + tile_total


def _scan_kernel(x_ref, o_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    o_ref[...], carry_ref[...] = block_scan(x_ref[...], carry_ref[...])


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def offsets_scan(
    lengths: jax.Array, rows: int = DEFAULT_ROWS, interpret: bool = False
) -> jax.Array:
    """Inclusive int32 scan over a 1-D array of collection sizes."""
    (n,) = lengths.shape
    block = rows * LANES
    pad = (-n) % block
    x = jnp.pad(lengths.astype(jnp.int32), (0, pad)).reshape(-1, LANES)
    out = pl.pallas_call(
        _scan_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
        grid=(x.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x)
    return out.reshape(-1)[:n]


def offsets_scan_host(sizes: np.ndarray) -> np.ndarray:
    """Numpy-in / numpy-out entry point for the write hot path.

    Accepts a 1-D array of collection sizes and returns int64
    cluster-relative end offsets.  The kernel runs in int32; callers must
    ensure the total fits — the write path guards this and keeps numpy
    otherwise.  On a CPU-only jax backend the kernel runs in interpret
    mode (tests only: the dispatcher in ``repro.core.encoding`` does not
    select this path on CPU unless forced).
    """
    x = jnp.asarray(np.ascontiguousarray(sizes), dtype=jnp.int32)
    interpret = jax.default_backend() == "cpu"
    out = offsets_scan(x, interpret=interpret)
    return np.asarray(out, dtype=np.int64)
