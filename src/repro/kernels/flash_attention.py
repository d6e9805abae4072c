"""Pallas TPU flash attention: the train kernel and a forward-only kernel.

:func:`flash_attention_train` is the attention the differentiated train
step runs on a TPU: the splash attention kernels shipped with jax
(``jax.experimental.pallas.ops.tpu.splash_attention``), Pallas in the
forward and in the backward (one fused kernel for dq, dk and dv), so the
``(S, S)`` scores never reach HBM in the forward, its recompute under
remat, or the backward.  One MQA kernel per kv group over a causal
mask, vmapped over batch and kv groups; bf16 operands into the MXU with
f32 accumulation and f32 softmax statistics.  ``ops.flash_attention``
routes to it where the shapes allow (see there).

:func:`flash_attention` is the repo's own forward-only kernel (no VJP),
run only when asked for (``use_pallas=True``, e.g. by serving).
Online-softmax tiled attention with GQA/MQA head grouping, causal masking
and optional sliding-window (SWA) masking.  Grid is
(batch, q_head, q_block, kv_block) with the kv dimension innermost —
sequential on a TensorCore — so the running (m, l, acc) statistics live in
VMEM scratch and are finalized on the last kv step.

Block sizes default to 128×128, MXU-aligned; head_dim is kept whole in
VMEM (D <= 256 -> at most 128·256·4 B = 128 KiB per operand tile).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import splash_attention as splash

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, q_offset: int, n_kv_blocks: int,
):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (BK, D)

    iq = pl.program_id(2)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == n_kv_blocks - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,                 # (B, H, Sq, D)
    k: jax.Array,                 # (B, G, Sk, D)
    v: jax.Array,                 # (B, G, Sk, D)
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, d = q.shape
    _, g, sk, _ = k.shape
    assert h % g == 0, (h, g)
    q_per_kv = h // g
    scale = scale if scale is not None else float(1.0 / np.sqrt(d))
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = qp.shape[2] // block_q
    nk = kp.shape[2] // block_k
    # Padded kv columns must stay masked: they sit at positions >= sk and a
    # causal mask with q_offset = sk - sq keeps every real q row below them
    # ... except the padded q rows, which we slice off anyway.  For the
    # non-causal case mask via window=None + explicit validity below.
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        q_offset=sk - sq,
        n_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h_, iq, ik, q_per_kv=q_per_kv: (b_, h_ // q_per_kv, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h_, iq, ik, q_per_kv=q_per_kv: (b_, h_ // q_per_kv, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :sq]


# ---------------------------------------------------------------------------
# the train kernel (forward and backward in Pallas)

#: the train kernel's tile along both sequence axes, in order of
#: preference: the first that divides S.  Tuned at S 2048, d 64 on a v5e,
#: where 1024 with the fused backward (dq, dk and dv from one kernel) ran
#: a layer's forward, remat forward and backward fastest of 128/256/512/
#: 1024, fused or not
TRAIN_BLOCKS = (1024, 512, 256, 128)
#: (q/k head dim, v head dim) pairs the train kernel is compiled and
#: tested for; (192, 128) is MLA's (nope 128 + rope 64, v 128)
TRAIN_HEAD_DIMS = ((64, 64), (128, 128), (192, 128))


def train_block(s: int) -> Optional[int]:
    """The train kernel's block at sequence length ``s``; None when no
    block divides ``s``."""
    return next((blk for blk in TRAIN_BLOCKS if s % blk == 0), None)


@functools.lru_cache(maxsize=None)
def _splash_kernel(q_per_kv: int, s: int, block: int, interpret: bool):
    mask = splash.MultiHeadMask([splash.CausalMask((s, s))] * q_per_kv)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)
    # the mask tables are built as constants, not as tracers of the trace
    # that first asks for the kernel
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            mask, block_sizes=sizes, interpret=interpret)


def flash_attention_train(
    q: jax.Array,                 # (B, H, S, D)
    k: jax.Array,                 # (B, G, S, D)
    v: jax.Array,                 # (B, G, S, Dv)
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal GQA attention with a Pallas forward and backward.

    q head ``h`` attends kv head ``h // (H // G)``, as in
    ``ref.flash_attention_ref``.  S must be a multiple of a block
    (:func:`train_block`).  The value head dim may differ from the
    query/key one (MLA); the output takes the value's."""
    b, h, s, d = q.shape
    g, dv = k.shape[1], v.shape[3]
    assert h % g == 0 and k.shape == (b, g, s, d) \
        and v.shape == (b, g, s, dv), (q.shape, k.shape, v.shape)
    block = train_block(s)
    assert block, s
    # a Python float, so that q keeps its dtype (a NumPy scalar would
    # promote bf16 to f32)
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d))
    kern = _splash_kernel(h // g, s, block, interpret)
    qg = (q * scale).reshape(b, g, h // g, s, d)
    return jax.vmap(jax.vmap(kern))(qg, k, v).reshape(b, h, s, dv)
