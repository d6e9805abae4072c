"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles.

Sweeps shapes and dtypes per kernel; also cross-checks the encoder kernels
against the numpy host implementations in repro.core.encoding (the writer's
actual serialization path must be bit-identical to the TPU kernels).
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import encoding as E
from repro.core import stats
from repro.kernels import ops, ref
from repro.kernels.byteshuffle import byteshuffle
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import (flash_attention,
                                          flash_attention_train)
from repro.kernels.mamba2_ssd import mamba2_ssd
from repro.kernels.offsets_scan import offsets_scan
from repro.kernels.rwkv6_scan import rwkv6_scan

jax.config.update("jax_platform_name", "cpu")

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# columnar encoder kernels


@pytest.mark.parametrize("n", [1, 7, 128, 1000, 5000])
@pytest.mark.parametrize("rows", [8, 32])
def test_offsets_scan_matches_ref_and_host(n, rows):
    lengths = jnp.asarray(RNG.poisson(5, n), dtype=jnp.int32)
    out = offsets_scan(lengths, rows=rows, interpret=True)
    np.testing.assert_array_equal(out, ref.offsets_scan_ref(lengths))
    host = E.sizes_to_offsets(np.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(out, dtype=np.int64), host)


@pytest.mark.parametrize("itemsize", [2, 4, 8])
@pytest.mark.parametrize("n", [1, 100, 2048, 6000])
def test_byteshuffle_matches_ref_and_host(itemsize, n):
    planes = jnp.asarray(RNG.integers(0, 256, (n, itemsize)), dtype=jnp.uint8)
    out = byteshuffle(planes, block=512, interpret=True)
    np.testing.assert_array_equal(out, ref.byteshuffle_ref(planes))
    # host split_encode of an array with this itemsize
    arr = np.frombuffer(np.asarray(planes).tobytes(), dtype=f"<u{itemsize}")
    host = E.split_encode(arr)
    assert np.asarray(out).tobytes() == host


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("b,h,g,sq,sk,d", [
    (1, 4, 4, 128, 128, 64),      # MHA square
    (2, 8, 2, 256, 256, 64),      # GQA
    (1, 8, 1, 128, 128, 128),     # MQA
    (1, 4, 4, 64, 256, 64),       # decode-ish: short q, long kv
    (1, 4, 2, 200, 200, 80),      # non-divisible by blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, h, g, sq, sk, d, dtype):
    q = jnp.asarray(RNG.normal(0, 1, (b, h, sq, d)), dtype=dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, g, sk, d)), dtype=dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, g, sk, d)), dtype=dtype)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [64, 128, 1024])
def test_flash_attention_sliding_window(window):
    b, h, g, s, d = 1, 4, 2, 256, 64
    q = jnp.asarray(RNG.normal(0, 1, (b, h, s, d)), dtype=jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, g, s, d)), dtype=jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, g, s, d)), dtype=jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_naive_softmax():
    """Independent oracle: hand-rolled masked softmax."""
    b, h, s, d = 1, 2, 64, 32
    q = jnp.asarray(RNG.normal(0, 1, (b, h, s, d)), dtype=jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, h, s, d)), dtype=jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, h, s, d)), dtype=jnp.float32)
    logits = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    mask = np.tril(np.ones((s, s), bool))
    logits = np.where(mask, logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    expect = np.einsum("bhqk,bhkd->bhqd", p, v)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the train kernel (Pallas forward and backward) and its dispatch rule


def _attn_grads(attn, q, k, v, w):
    """Output and (dq, dk, dv) of sum(attn(q, k, v) * w)."""
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

    return attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("h,g,s,d,dv", [
    (3, 1, 256, 64, 64), (4, 4, 256, 64, 64), (6, 2, 256, 64, 64),
    (3, 1, 512, 64, 64), (4, 4, 512, 64, 64), (6, 2, 512, 64, 64),
    (6, 2, 256, 128, 128), (4, 4, 256, 192, 128),
], ids=["gqa3-256", "mha4-256", "gqa3x2-256", "gqa3-512", "mha4-512",
        "gqa3x2-512", "gqa3x2-256-d128", "mla-256-d192-v128"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_train_kernel_matches_ref_with_grads(h, g, s, d, dv, dtype):
    """Output and dq, dk, dv of the train kernel (interpret mode) against
    the XLA attention in float32; bf16 inputs round q, k, v, p and the
    outputs to bf16, hence the looser bound.  MLA's q/k head dim 192
    (nope 128 + rope 64) with v head dim 128 among the shapes, at its
    scale 1/sqrt(192)."""
    b = 1
    q = jnp.asarray(RNG.normal(0, 1, (b, h, s, d)), dtype=dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, g, s, d)), dtype=dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, g, s, dv)), dtype=dtype)
    w = jnp.asarray(RNG.normal(0, 1, (b, h, s, dv)), dtype=jnp.float32)
    scale = 1.0 / np.sqrt(d)
    out, grads = _attn_grads(
        lambda q, k, v: flash_attention_train(q, k, v, scale=scale,
                                              interpret=True),
        q, k, v, w)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        want, want_grads = _attn_grads(
            lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True,
                                                    scale=scale),
            *f32, w)
    assert out.dtype == dtype and [x.dtype for x in grads] == [dtype] * 3
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for got, exp in zip([out, *grads], [want, *want_grads]):
        got = np.asarray(got, np.float32)
        exp = np.asarray(exp, np.float32)
        assert np.max(np.abs(got - exp)) <= tol * np.max(np.abs(exp))


@pytest.fixture
def attention_records(monkeypatch):
    """Records kept as under a profiler session, counters from zero."""
    assert stats._bind_jax()
    monkeypatch.setattr(stats, "_is_enabled", lambda: True)
    monkeypatch.setattr(ops.ATTENTION, "calls",
                        dict.fromkeys(ops.ATTENTION.calls, 0))
    stats.clear()
    yield
    stats.clear()


# (q, k, v shapes, window, path on a TPU)
SMOLLM = ((4, 15, 2048, 64), (4, 5, 2048, 64), (4, 5, 2048, 64))
DISPATCH = {
    "smollm": (SMOLLM, None, "kernel"),
    "mha-256": (((2, 4, 256, 128),) * 3, None, "kernel"),
    "sq!=sk": (((1, 4, 128, 64), (1, 2, 256, 64), (1, 2, 256, 64)), None,
               "xla"),
    "s-off-block": (((1, 4, 200, 64), (1, 2, 200, 64), (1, 2, 200, 64)),
                    None, "xla"),
    "dv!=d": (((1, 4, 256, 96), (1, 4, 256, 96), (1, 4, 256, 64)), None,
              "xla"),
    "mla-192-128": (((2, 16, 1024, 192),) * 2 + ((2, 16, 1024, 128),), None,
                    "kernel"),
    "head-dim-80": (((1, 4, 256, 80),) * 3, None, "xla"),
    "window": (SMOLLM, 1024, "xla"),
}


@pytest.mark.parametrize("case", list(DISPATCH))
def test_flash_attention_dispatch_rule(case, monkeypatch, attention_records):
    """On a TPU the train kernel takes the causal Sq == Sk shapes it
    supports; everything else stays on the XLA attention, and the counter
    and the records name the path."""
    shapes, window, path = DISPATCH[case]
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    q, k, v = (jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in shapes)
    out = jax.eval_shape(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            window=window), q, k, v)
    assert out.shape == shapes[0][:3] + shapes[2][3:]
    assert ops.ATTENTION.calls == {"kernel": int(path == "kernel"),
                                   "forward_kernel": 0,
                                   "xla": int(path == "xla")}
    recs = [r for r in stats.records() if r.name.startswith("attention.")]
    assert [(r.name, r.key) for r in recs] == [
        (f"attention.{path}", (*shapes, "bfloat16"))]


@pytest.mark.parametrize("where", ["cpu", "mesh", "no-profiler"])
def test_flash_attention_dispatch_off_tpu_and_on_a_mesh(where, monkeypatch,
                                                        attention_records):
    """The smollm shape stays on XLA on a CPU backend and under sharding
    rules over several devices; without a profiler session the counter
    still counts and no record is kept."""
    if where != "cpu":
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    if where == "mesh":
        from repro.distributed import sharding

        four = SimpleNamespace(mesh=SimpleNamespace(size=4))
        monkeypatch.setattr(sharding, "current_rules", lambda: four)
    if where == "no-profiler":
        monkeypatch.setattr(stats, "_is_enabled", lambda: False)
    q, k, v = (jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in SMOLLM)
    jax.eval_shape(lambda q, k, v: ops.flash_attention(q, k, v), q, k, v)
    path = "kernel" if where == "no-profiler" else "xla"
    calls = ops.ATTENTION.calls
    assert calls[path] == 1 and sum(calls.values()) == 1
    names = [r.name for r in stats.records()]
    assert names == ([] if where == "no-profiler" else ["attention.xla"])


# ---------------------------------------------------------------------------
# decode attention


@pytest.mark.parametrize("b,h,g,s,d", [
    (2, 4, 4, 512, 64),
    (2, 8, 2, 1024, 64),
    (1, 8, 1, 777, 128),     # MQA, ragged length
])
def test_decode_attention_full(b, h, g, s, d):
    q = jnp.asarray(RNG.normal(0, 1, (b, h, d)), dtype=jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, g, s, d)), dtype=jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, g, s, d)), dtype=jnp.float32)
    out = decode_attention(q, k, v, block_k=256, interpret=True)
    expect = ref.decode_attention_ref(q, k, v)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_decode_attention_lengths_and_window():
    b, h, g, s, d = 3, 4, 2, 640, 64
    q = jnp.asarray(RNG.normal(0, 1, (b, h, d)), dtype=jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, g, s, d)), dtype=jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, g, s, d)), dtype=jnp.float32)
    length = jnp.asarray([100, 640, 333], dtype=jnp.int32)
    for window in (None, 64):
        out = decode_attention(q, k, v, length=length, window=window,
                               block_k=128, interpret=True)
        expect = ref.decode_attention_ref(q, k, v, length=length, window=window)
        np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# rwkv6


@pytest.mark.parametrize("b,h,t,dk,dv,chunk", [
    (1, 2, 64, 32, 32, 16),
    (2, 2, 128, 64, 64, 32),
    (1, 4, 96, 48, 64, 32),
])
def test_rwkv6_scan_vs_ref(b, h, t, dk, dv, chunk):
    r = jnp.asarray(RNG.normal(0, 1, (b, h, t, dk)), dtype=jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, h, t, dk)), dtype=jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, h, t, dv)), dtype=jnp.float32)
    # realistic rwkv6 decay range: w = exp(-exp(x)), x in [-4, 1]
    w = jnp.exp(-jnp.exp(jnp.asarray(RNG.uniform(-4, 1, (b, h, t, dk)),
                                     dtype=jnp.float32)))
    u = jnp.asarray(RNG.normal(0, 1, (h, dk)), dtype=jnp.float32)
    out, state = rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    expect, state_ref = ref.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(out, expect, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(state, state_ref, atol=5e-4, rtol=5e-4)


def test_rwkv6_strong_decay_stable():
    """Near-zero decay must not overflow (the naive factorization does)."""
    b, h, t, dk, dv = 1, 1, 64, 16, 16
    r = jnp.ones((b, h, t, dk)) * 0.1
    k = jnp.ones((b, h, t, dk)) * 0.1
    v = jnp.ones((b, h, t, dv))
    w = jnp.full((b, h, t, dk), 1e-6)       # extremely strong decay
    u = jnp.zeros((h, dk))
    out, _ = rwkv6_scan(r, k, v, w, u, chunk=32, interpret=True)
    expect, _ = ref.rwkv6_ref(r, k, v, w, u)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, expect, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# mamba2 SSD


@pytest.mark.parametrize("b,h,t,p,n,chunk", [
    (1, 2, 128, 32, 16, 32),
    (2, 4, 128, 64, 64, 64),
    (1, 2, 256, 64, 32, 64),
])
def test_mamba2_ssd_vs_ref(b, h, t, p, n, chunk):
    x = jnp.asarray(RNG.normal(0, 1, (b, h, t, p)), dtype=jnp.float32)
    log_a = -jnp.exp(jnp.asarray(RNG.uniform(-3, 0.5, (b, h, t)), dtype=jnp.float32))
    Bm = jnp.asarray(RNG.normal(0, 1, (b, t, n)), dtype=jnp.float32)
    Cm = jnp.asarray(RNG.normal(0, 1, (b, t, n)), dtype=jnp.float32)
    out, state = mamba2_ssd(x, log_a, Bm, Cm, chunk=chunk, interpret=True)
    D0 = jnp.zeros((h,), jnp.float32)
    expect, state_ref = ref.mamba2_ref(x, log_a, Bm, Cm, D0)
    np.testing.assert_allclose(out, expect, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(state, state_ref, atol=1e-3, rtol=1e-3)


def test_mamba2_state_continuity():
    """Chunked kernel must equal ref across chunk boundaries (state carry)."""
    b, h, t, p, n = 1, 1, 192, 16, 8
    x = jnp.asarray(RNG.normal(0, 1, (b, h, t, p)), dtype=jnp.float32)
    log_a = jnp.full((b, h, t), -0.05)
    Bm = jnp.asarray(RNG.normal(0, 1, (b, t, n)), dtype=jnp.float32)
    Cm = jnp.asarray(RNG.normal(0, 1, (b, t, n)), dtype=jnp.float32)
    out_c64, _ = mamba2_ssd(x, log_a, Bm, Cm, chunk=64, interpret=True)
    out_c32, _ = mamba2_ssd(x, log_a, Bm, Cm, chunk=32, interpret=True)
    np.testing.assert_allclose(out_c64, out_c32, atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# hypothesis: offsets kernel == host encoder over random size distributions


@given(st.lists(st.integers(0, 1000), min_size=1, max_size=500))
@settings(max_examples=20, deadline=None)
def test_offsets_scan_property(sizes):
    lengths = jnp.asarray(sizes, dtype=jnp.int32)
    out = offsets_scan(lengths, rows=8, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out, np.int64), E.sizes_to_offsets(np.asarray(sizes))
    )


# ---------------------------------------------------------------------------
# chunked (online-softmax) attention — the §Perf pure-JAX flash variant


@pytest.mark.parametrize("b,h,g,sq,sk,d,window", [
    (1, 4, 2, 128, 128, 32, None),
    (2, 2, 1, 64, 192, 16, None),
    (1, 2, 2, 100, 100, 32, 48),
    (1, 8, 8, 256, 256, 64, None),
])
def test_flash_chunked_matches_ref(b, h, g, sq, sk, d, window):
    q = jnp.asarray(RNG.normal(0, 1, (b, h, sq, d)), dtype=jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, g, sk, d)), dtype=jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, g, sk, d)), dtype=jnp.float32)
    a = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    c = ref.flash_attention_chunked(q, k, v, causal=True, window=window,
                                    block=32)
    np.testing.assert_allclose(a, c, atol=3e-5, rtol=3e-5)


def test_flash_chunked_never_materializes_full_scores():
    """Structural check: peak temp of chunked << ref for long sequences."""
    b, h, s, d, blk = 1, 2, 2048, 32, 256
    q = jnp.zeros((b, h, s, d), jnp.float32)
    k = jnp.zeros((b, h, s, d), jnp.float32)
    v = jnp.zeros((b, h, s, d), jnp.float32)
    cref = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v)).lower(
        q, k, v).compile()
    cchk = jax.jit(lambda q, k, v: ref.flash_attention_chunked(
        q, k, v, block=blk)).lower(q, k, v).compile()
    t_ref = cref.memory_analysis().temp_size_in_bytes
    t_chk = cchk.memory_analysis().temp_size_in_bytes
    assert t_chk < t_ref / 2, (t_chk, t_ref)


def test_flash_chunked_mla_dims():
    """v head-dim may differ from q/k head-dim (MLA): d_v != d_qk."""
    b, h, s, dqk, dv = 1, 4, 96, 24, 16
    q = jnp.asarray(RNG.normal(0, 1, (b, h, s, dqk)), dtype=jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, h, s, dqk)), dtype=jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, h, s, dv)), dtype=jnp.float32)
    a = ref.flash_attention_ref(q, k, v, causal=True)
    c = ref.flash_attention_chunked(q, k, v, causal=True, block=32)
    assert c.shape == (b, h, s, dv)
    np.testing.assert_allclose(a, c, atol=3e-5, rtol=3e-5)
