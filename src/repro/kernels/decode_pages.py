"""Pallas TPU kernels: the fused per-column page *decode* chain.

The read-side inverse of the write path's preconditioning kernels
(``byteshuffle_pages``, ``offsets_scan``): stored page
bytes upload to the device ONCE and columns materialize directly as JAX
device arrays — no host unsplit, no host zigzag/delta pass, no host
offset integration (DESIGN.md §9).

Two kernels:

* :func:`unsplit_pages` — inverse page-batched byteshuffle,
  ``(P, itemsize, per) uint8 -> (P, per, itemsize) uint8``.  Bandwidth
  bound, same tiling as the forward kernel.
* :func:`decode_offset_pages` — the FUSED offset-column chain: split
  uint64 zigzag deltas (the on-disk ``delta+zigzag+split`` encoding with
  per-page delta restart) decode in one pass to int32 cluster-relative
  end offsets: byte-plane gather -> zigzag inverse -> blocked inclusive
  scan (:func:`~repro.kernels.offsets_scan.block_scan`) with a VMEM
  carry that resets at every page boundary.

Both run in 32-bit lanes: the read engine only dispatches an offset
column here when the cluster's element total is below 2**31 (known from
the cluster metadata before any byte is read), which makes the int32
offsets EXACT and leaves byte planes 4..7 of the stored uint64 all zero.
The jnp oracles live in :mod:`repro.kernels.ref`; the numpy ground truth
is ``repro.core.encoding.unprecondition_pages_into``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .offsets_scan import DEFAULT_ROWS, LANES, block_scan

DEFAULT_BLOCK = 2048


def _unsplit_kernel(x_ref, o_ref):
    # x block: (1, itemsize, BN) uint8 -> out block (1, BN, itemsize)
    o_ref[...] = jnp.swapaxes(x_ref[...], 1, 2)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def unsplit_pages(
    planes: jax.Array, block: int = DEFAULT_BLOCK, interpret: bool = False
) -> jax.Array:
    """(P, itemsize, per) uint8 -> (P, per, itemsize): inverse byteshuffle.

    Page ``p``'s byte planes land back as that page's contiguous
    little-endian elements in ``out[p]`` — the exact inverse of
    ``byteshuffle_pages``.  Blocks never cross page boundaries (a page is
    its own independent transpose).
    """
    n_pages, itemsize, per = planes.shape
    blk = min(block, per)
    pad = (-per) % blk
    x = jnp.pad(planes, ((0, 0), (0, 0), (0, pad)))
    out = pl.pallas_call(
        _unsplit_kernel,
        out_shape=jax.ShapeDtypeStruct(
            (n_pages, x.shape[2], itemsize), jnp.uint8
        ),
        grid=(n_pages, x.shape[2] // blk),
        in_specs=[pl.BlockSpec((1, itemsize, blk), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((1, blk, itemsize), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(x)
    return out[:, :per, :]


def _offsets_decode_kernel(x_ref, o_ref, carry_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        # per-page delta restart: the carry resets at every page start
        carry_ref[...] = jnp.zeros_like(carry_ref)

    # (1, 4, R, 128) uint8: the low byte planes of the stored uint64 —
    # the dispatch guard proves planes 4..7 are zero
    z = x_ref[0, 0].astype(jnp.int32)
    for k in range(1, 4):
        z = z | (x_ref[0, k].astype(jnp.int32) << (8 * k))
    # zigzag inverse: (z >>> 1) ^ -(z & 1), in int32 (magnitudes fit by
    # the same guard)
    d = jax.lax.shift_right_logical(z, 1) ^ -(z & 1)
    o_ref[0], carry_ref[...] = block_scan(d, carry_ref[...])


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def decode_offset_pages(
    planes: jax.Array, rows: int = DEFAULT_ROWS, interpret: bool = False
) -> jax.Array:
    """(P, 8, per) uint8 split zigzag deltas -> (P, per) int32 end offsets.

    The fused offset-column decode: one kernel launch per column replaces
    the host's unsplit + zigzag decode + per-page ``integrate_sizes``
    loop.  Each page is laid out lane-dense as ``(rows, 128)`` tiles; the
    grid walks (page, tile-within-page) and the scan carry resets at each
    page's first tile (per-page delta restart), so pages integrate
    independently exactly like the numpy reference.
    """
    n_pages, itemsize, per = planes.shape
    assert itemsize == 8, "offset columns store uint64 planes"
    # uint8 tiles are (32, 128): a block holds a multiple of 32 rows
    rows = min(rows, -(-per // (32 * LANES)) * 32)
    block = rows * LANES
    pad = (-per) % block
    x = jnp.pad(planes, ((0, 0), (0, 0), (0, pad)))
    x = x.reshape(n_pages, itemsize, -1, LANES)
    out = pl.pallas_call(
        _offsets_decode_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pages,) + x.shape[2:], jnp.int32),
        grid=(n_pages, x.shape[2] // rows),
        in_specs=[pl.BlockSpec((1, 4, rows, LANES),
                               lambda i, j: (i, 0, j, 0))],
        out_specs=pl.BlockSpec((1, rows, LANES), lambda i, j: (i, j, 0)),
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x)
    return out.reshape(n_pages, -1)[:, :per]


# ---------------------------------------------------------------------------
# the device decode chain (jitted drivers used by the read engine)
#
# ``raw`` is a flat uint8 device array holding one column's stored page
# payloads in the sealed-cluster layout: page p of k <= per elements at
# byte range [p*per*itemsize, p*per*itemsize + k*itemsize).  The drivers
# below decode it to the column's element array entirely on device;
# ``use_pallas`` switches between the Pallas kernels and the jnp oracle
# ops (both run on the device — the oracle path is what "auto" compiles
# through XLA on CPU backends, the kernels engage on TPU or when forced).


def _tail_split(raw: jax.Array, head: int, n: int, nb: int) -> jax.Array:
    """Unsplit the final partial page ((nb, k) planes -> (k, nb) bytes)."""
    k = n - head
    t = jax.lax.dynamic_slice(raw, (head * nb,), (k * nb,))
    return jnp.swapaxes(t.reshape(nb, k), 0, 1)


def _bitcast_elems(rows: jax.Array, dtype) -> jax.Array:
    """(N, itemsize) uint8 little-endian rows -> (N,) dtype elements."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.uint8:
        return rows.reshape(-1)
    return jax.lax.bitcast_convert_type(rows, dtype)


@functools.partial(
    jax.jit, static_argnames=("n", "per", "dtype", "use_pallas", "interpret")
)
def device_decode_none(raw: jax.Array, n: int, per: int, dtype,
                       use_pallas: bool = False,
                       interpret: bool = False) -> jax.Array:
    """ENC_NONE: reinterpret the stored bytes as elements (pure bitcast)."""
    nb = jnp.dtype(dtype).itemsize
    return _bitcast_elems(raw[: n * nb].reshape(n, nb), dtype)


@functools.partial(
    jax.jit, static_argnames=("n", "per", "dtype", "use_pallas", "interpret")
)
def device_decode_split(raw: jax.Array, n: int, per: int, dtype,
                        use_pallas: bool = False,
                        interpret: bool = False) -> jax.Array:
    """ENC_SPLIT: page-batched inverse byteshuffle -> (n,) dtype elements."""
    from . import ref

    nb = jnp.dtype(dtype).itemsize
    n_full = n // per
    head = n_full * per
    parts = []
    if n_full:
        src = raw[: head * nb].reshape(n_full, nb, per)
        if use_pallas:
            rows = unsplit_pages(src, interpret=interpret)
        else:
            rows = ref.unsplit_pages_ref(src)
        parts.append(rows.reshape(head, nb))
    if head < n:
        parts.append(_tail_split(raw, head, n, nb))
    rows = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return _bitcast_elems(rows, dtype)


@functools.partial(
    jax.jit, static_argnames=("n", "per", "use_pallas", "interpret")
)
def device_decode_offsets(raw: jax.Array, n: int, per: int,
                          use_pallas: bool = False,
                          interpret: bool = False) -> jax.Array:
    """ENC_DELTA_ZIGZAG_SPLIT: fused decode to (n,) int32 end offsets.

    Exact (not approximate) under the reader's dispatch guard: every
    offset in the cluster is below 2**31, so the int32 device column is
    bit-identical to the int64 host reference after widening.
    """
    from . import ref

    n_full = n // per
    head = n_full * per
    parts = []
    if n_full:
        src = raw[: head * 8].reshape(n_full, 8, per)
        if use_pallas:
            offs = decode_offset_pages(src, interpret=interpret)
        else:
            offs = ref.decode_offset_pages_ref(src)
        parts.append(offs.reshape(head))
    if head < n:
        rows = _tail_split(raw, head, n, 8)  # (k, 8) uint8
        z = (
            rows[:, 0].astype(jnp.uint32)
            | (rows[:, 1].astype(jnp.uint32) << 8)
            | (rows[:, 2].astype(jnp.uint32) << 16)
            | (rows[:, 3].astype(jnp.uint32) << 24)
        )
        d = ref.unzigzag_ref(z)
        parts.append(jnp.cumsum(d))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)
