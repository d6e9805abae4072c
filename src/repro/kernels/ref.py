"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth the kernels are property-tested against
(``interpret=True`` on CPU), and they double as the portable fallback the
models use when not running on TPU.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Columnar encoders (the paper's serialization hot spots)


def offsets_scan_ref(lengths: jax.Array) -> jax.Array:
    """Collection sizes -> cluster-relative end offsets (inclusive scan)."""
    return jnp.cumsum(lengths, axis=-1)


def byteshuffle_ref(planes: jax.Array) -> jax.Array:
    """Split encoding: (N, itemsize) uint8 byte planes -> (itemsize, N)."""
    return planes.T


# -- the decode chain (read-side inverses, DESIGN.md §9) --------------------


def unsplit_pages_ref(planes: jax.Array) -> jax.Array:
    """Inverse page-batched byteshuffle: (P, itemsize, per) -> (P, per, itemsize)."""
    return jnp.swapaxes(planes, 1, 2)


def unzigzag_ref(z: jax.Array) -> jax.Array:
    """zigzag inverse on uint32 lanes -> int32: (z >> 1) ^ -(z & 1)."""
    z = z.astype(jnp.uint32)
    return (z >> 1).astype(jnp.int32) ^ -(z & 1).astype(jnp.int32)


def decode_offset_pages_ref(planes: jax.Array) -> jax.Array:
    """Fused offset-column decode oracle, (P, 8, per) uint8 -> (P, per) int32.

    Byte planes of the stored uint64 zigzag deltas (low 32 bits only —
    the dispatcher guards that offsets fit) -> zigzag inverse -> per-page
    inclusive scan (per-page delta restart: each page integrates from 0).
    """
    p = planes.astype(jnp.uint32)
    z = p[:, 0] | (p[:, 1] << 8) | (p[:, 2] << 16) | (p[:, 3] << 24)
    return jnp.cumsum(unzigzag_ref(z), axis=-1)


# ---------------------------------------------------------------------------
# Attention


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, G, S, D) -> (B, H, S, D) by repeating each kv head H//G times."""
    b, g, s, d = k.shape
    if g == n_heads:
        return k
    return jnp.repeat(k, n_heads // g, axis=1)


def flash_attention_ref(
    q: jax.Array,            # (B, H, Sq, D)
    k: jax.Array,            # (B, G, Sk, D)
    v: jax.Array,            # (B, G, Sk, D)
    causal: bool = True,
    window: Optional[int] = None,     # sliding-window attention size
    scale: Optional[float] = None,
) -> jax.Array:
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    kk = _expand_kv(k, h)
    vv = _expand_kv(v, h)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q * scale, kk)
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)   # align ends (prefill/decode)
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(vv.dtype), vv)


def flash_attention_chunked(
    q: jax.Array,            # (B, H, Sq, D)
    k: jax.Array,            # (B, G, Sk, D)
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block: int = 1024,
) -> jax.Array:
    """Pure-JAX online-softmax attention (scan over kv blocks).

    The §Perf optimization for the memory roofline term: never materializes
    the (Sq, Sk) score matrix — per-iteration footprint is (Sq, block).
    Mathematically identical to :func:`flash_attention_ref`; on TPU the
    Pallas kernel replaces it, on CPU/dry-run this IS the compiled form.
    """
    b, h, sq, d = q.shape
    g, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]                     # may differ from d (MLA)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    block = min(block, sk)
    pad = (-sk) % block
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = kp.shape[2] // block
    kk = _expand_kv(kp, h).reshape(b, h, nk, block, d)
    vv = _expand_kv(vp, h).reshape(b, h, nk, block, dv)
    q32 = (q * scale).astype(jnp.float32)
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, ik = xs                      # (B,H,block,D) x2, ()
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb.astype(jnp.float32))
        k_pos = ik * block + jnp.arange(block)[None, :]
        mask = k_pos < sk
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask[None, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (jnp.moveaxis(kk, 2, 0), jnp.moveaxis(vv, 2, 0), jnp.arange(nk)),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,            # (B, H, D) — one new token
    k: jax.Array,            # (B, G, S, D) — KV cache
    v: jax.Array,
    length: Optional[jax.Array] = None,   # (B,) valid cache lengths
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    b, h, d = q.shape
    s = k.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    kk = _expand_kv(k, h)
    vv = _expand_kv(v, h)
    logits = jnp.einsum("bhd,bhkd->bhk", q * scale, kk)
    pos = jnp.arange(s)[None, :]
    valid = jnp.ones((b, s), dtype=bool)
    if length is not None:
        valid &= pos < length[:, None]
        last = length[:, None]
    else:
        last = jnp.full((b, 1), s)
    if window is not None:
        valid &= pos >= last - window
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p.astype(vv.dtype), vv)


# ---------------------------------------------------------------------------
# RWKV-6 (Finch) wkv recurrence
#
#   S_t = diag(w_t) S_{t-1} + k_t^T v_t        S: (Dk, Dv) per (batch, head)
#   o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
#
# w_t in (0,1) is the data-dependent decay; u is the per-channel bonus.


def rwkv6_ref(
    r: jax.Array,    # (B, H, T, Dk)
    k: jax.Array,    # (B, H, T, Dk)
    v: jax.Array,    # (B, H, T, Dv)
    w: jax.Array,    # (B, H, T, Dk) decay in (0, 1)
    u: jax.Array,    # (H, Dk) bonus
    initial_state: Optional[jax.Array] = None,  # (B, H, Dk, Dv)
):
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    r_, k_, v_, w_ = (x.astype(f32) for x in (r, k, v, w))
    u_ = u.astype(f32)
    s0 = (
        initial_state.astype(f32)
        if initial_state is not None
        else jnp.zeros((b, h, dk, dv), f32)
    )

    def step(S, xs):
        rt, kt, vt, wt = xs          # (B,H,Dk),(B,H,Dk),(B,H,Dv),(B,H,Dk)
        kv = kt[..., :, None] * vt[..., None, :]          # (B,H,Dk,Dv)
        ot = jnp.einsum(
            "bhk,bhkv->bhv", rt, S + u_[None, :, :, None] * kv
        )
        S = wt[..., :, None] * S + kv
        return S, ot

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (r_, k_, v_, w_))
    S, out = jax.lax.scan(step, s0, xs)
    out = jnp.moveaxis(out, 0, 2)    # (B, H, T, Dv)
    return out.astype(v.dtype), S


def rwkv6_decode_ref(r, k, v, w, u, state):
    """One-token RWKV6 step: inputs (B,H,Dk)... state (B,H,Dk,Dv)."""
    out, new_state = rwkv6_ref(
        r[:, :, None], k[:, :, None], v[:, :, None], w[:, :, None], u, state
    )
    return out[:, :, 0], new_state


# ---------------------------------------------------------------------------
# Mamba-2 (SSD): scalar-decay per head state-space recurrence
#
#   H_t = exp(a_t) H_{t-1} + B_t^T (dt_t * x_t)    H: (N, P) per (batch, head)
#   y_t = C_t H_t + D x_t
#
# a_t = -softplus-parameterized decay * dt (precomputed by caller as log-decay)


def mamba2_ref(
    x: jax.Array,        # (B, H, T, P) head channels
    log_a: jax.Array,    # (B, H, T) log decay (<= 0)
    Bm: jax.Array,       # (B, T, N) input projection (shared across heads)
    Cm: jax.Array,       # (B, T, N) output projection
    D: jax.Array,        # (H,) skip
    initial_state: Optional[jax.Array] = None,  # (B, H, N, P)
):
    b, h, t, p = x.shape
    n = Bm.shape[-1]
    f32 = jnp.float32
    x_, la, B_, C_ = (a.astype(f32) for a in (x, log_a, Bm, Cm))
    s0 = (
        initial_state.astype(f32)
        if initial_state is not None
        else jnp.zeros((b, h, n, p), f32)
    )

    def step(H, xs):
        xt, lat, bt, ct = xs         # (B,H,P),(B,H),(B,N),(B,N)
        H = jnp.exp(lat)[..., None, None] * H + jnp.einsum(
            "bn,bhp->bhnp", bt, xt
        )
        yt = jnp.einsum("bn,bhnp->bhp", ct, H)
        return H, yt

    xs = (
        jnp.moveaxis(x_, 2, 0),
        jnp.moveaxis(la, 2, 0),
        jnp.moveaxis(B_, 1, 0),
        jnp.moveaxis(C_, 1, 0),
    )
    Hf, y = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(y, 0, 2) + D[None, :, None, None].astype(f32) * x_
    return y.astype(x.dtype), Hf


def mamba2_decode_ref(x, log_a, Bm, Cm, D, state):
    """One-token Mamba2 step: x (B,H,P), log_a (B,H), Bm/Cm (B,N)."""
    y, new_state = mamba2_ref(
        x[:, :, None], log_a[:, :, None], Bm[:, None], Cm[:, None], D, state
    )
    return y[:, :, 0], new_state
