"""Per-architecture smoke tests (reduced configs, CPU) + decode consistency.

Every assigned arch: one forward/train step asserting output shapes and
finiteness, plus prefill->decode equivalence against the full forward pass
(the KV-cache/state path must reproduce teacher-forced logits).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import build, make_batch

ALL_ARCHS = sorted(ARCHS)
RNG = np.random.default_rng(7)


def _bundle(name, **over):
    # a single device runs the dropless MoE layer: nothing is dropped, so
    # decode consistency is exact with each config's own routing
    cfg = smoke_config(name)
    if over:
        cfg = cfg.with_(**over)
    return build(cfg)


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_train_step_shapes_and_finite(name):
    bundle = _bundle(name)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = make_batch(bundle, RNG, batch=2, seq=32)
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(bundle.loss, has_aux=True)
    )(params, batch)
    assert np.isfinite(float(loss))
    assert np.isfinite(float(metrics["ce"]))
    # gradients flow everywhere and are finite
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in leaves)
    nonzero = sum(int(np.any(np.asarray(g) != 0)) for g in leaves)
    assert nonzero > len(leaves) * 0.5, f"{nonzero}/{len(leaves)} grads nonzero"


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_forward_logit_shape(name):
    bundle = _bundle(name)
    cfg = bundle.cfg
    params = bundle.init(jax.random.PRNGKey(1))
    batch = make_batch(bundle, RNG, batch=2, seq=16)
    logits, aux = jax.jit(bundle.forward)(params, batch["tokens"])
    if cfg.n_codebooks > 1:
        assert logits.shape == (2, 16, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_prefill_decode_matches_forward(name):
    """logits(decode after prefill(t)) == logits(forward(t+1))[:, -1].

    Runs in f32 compute so the cache path must match teacher forcing to
    tight tolerance (bf16 would only blur the comparison).
    """
    bundle = _bundle(name, compute_dtype="float32")
    params = bundle.init(jax.random.PRNGKey(2))
    seq = 17
    batch = make_batch(bundle, RNG, batch=2, seq=seq)
    toks = batch["tokens"]

    full_logits, _ = jax.jit(bundle.forward)(params, toks)
    _, cache = jax.jit(lambda p, t: bundle.prefill(p, t, max_len=32))(
        params, toks[:, : seq - 1]
    )
    pos = jnp.full((2,), seq - 1, jnp.int32)
    dec_logits, _ = jax.jit(bundle.decode_step)(
        params, toks[:, seq - 1 : seq], cache, pos
    )
    np.testing.assert_allclose(
        np.asarray(dec_logits[:, 0], np.float32),
        np.asarray(full_logits[:, -1], np.float32),
        atol=2e-3, rtol=2e-3,
    )


@pytest.mark.parametrize("name", ["mixtral-8x22b", "zamba2-2.7b"])
def test_windowed_decode_ring_buffer(name):
    """Decoding past the window keeps working (ring-buffer cache)."""
    bundle = _bundle(name, window=8) if name == "mixtral-8x22b" else _bundle(name)
    params = bundle.init(jax.random.PRNGKey(3))
    cache = bundle.init_cache(batch=2, max_len=8 if name == "mixtral-8x22b" else 32)
    step = jax.jit(bundle.decode_step)
    tok_shape = (2, 1) if bundle.cfg.n_codebooks == 1 else (2, 1, bundle.cfg.n_codebooks)
    for t in range(12):
        tok = jnp.asarray(RNG.integers(0, bundle.cfg.vocab_size, tok_shape),
                          dtype=jnp.int32)
        logits, cache = step(params, tok, cache, jnp.full((2,), t, jnp.int32))
        assert np.isfinite(np.asarray(logits, np.float32)).all(), f"t={t}"


@pytest.mark.parametrize("name", ["rwkv6-7b", "zamba2-2.7b"])
def test_ssm_state_decode_is_o1_memory(name):
    """SSM/hybrid cache size must not scale with max_len (long_500k path)."""
    bundle = _bundle(name)
    c_small = jax.eval_shape(lambda: bundle.init_cache(1, 1024))
    c_large = jax.eval_shape(lambda: bundle.init_cache(1, 1 << 19))
    def nbytes(tree, skip_shared=False):
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if skip_shared and any(getattr(k, "key", None) == "shared" for k in path):
                continue
            total += leaf.size * leaf.dtype.itemsize
        return total
    if name == "rwkv6-7b":
        assert nbytes(c_large) == nbytes(c_small)
    else:
        # zamba2: mamba states O(1); shared-attn cache capped at window 4096
        assert nbytes(c_large, skip_shared=True) == nbytes(c_small, skip_shared=True)
        shared_large = jax.tree_util.tree_leaves(c_large["shared"])[0]
        assert shared_large.shape[3] == 4096  # windowed, not 524288


def test_musicgen_multicodebook_loss():
    bundle = _bundle("musicgen-large")
    params = bundle.init(jax.random.PRNGKey(4))
    batch = make_batch(bundle, RNG, batch=2, seq=16)
    assert batch["tokens"].shape == (2, 16, 4)
    loss, _ = jax.jit(bundle.loss)(params, batch)
    assert np.isfinite(float(loss))


def test_mla_cache_is_compressed():
    """MiniCPM3 decode cache stores latents (kv_rank + d_rope), not full KV."""
    bundle = _bundle("minicpm3-4b")
    cfg = bundle.cfg
    cache = jax.eval_shape(lambda: bundle.init_cache(1, 64))
    leaves = {str(p): l for p, l in
              [(jax.tree_util.keystr(p), l) for p, l
               in jax.tree_util.tree_flatten_with_path(cache)[0]]}
    per_tok = sum(l.shape[-1] for l in leaves.values())
    full_kv = 2 * cfg.n_heads * (cfg.mla.d_nope + cfg.mla.d_rope)
    assert per_tok == cfg.mla.kv_rank + cfg.mla.d_rope
    assert per_tok < full_kv / 4


def test_param_counts_full_configs():
    """Full (non-smoke) configs: abstract param counts near literature sizes."""
    import jax
    expect = {
        "gemma-2b": (2.0e9, 3.5e9),
        "minicpm3-4b": (3.0e9, 5.5e9),
        "deepseek-67b": (60e9, 72e9),
        "smollm-360m": (0.30e9, 0.45e9),
        "rwkv6-7b": (6.0e9, 9.0e9),
        "chameleon-34b": (30e9, 38e9),
        "mixtral-8x22b": (130e9, 150e9),
        # the dense first layer (10944) and 27 MoE layers: 16.4B published
        "deepseek-moe-16b": (16.0e9, 16.8e9),
        # 1 dense layer (11264) and 26 MoE layers: 16B published
        "moonlight-16b-a3b": (15.5e9, 16.5e9),
        "zamba2-2.7b": (2.2e9, 3.4e9),
        # musicgen-large is ~3.3B incl. the T5 text encoder + cross-attn;
        # the assigned backbone (decoder-only, frontend stubbed) is ~2.4B.
        "musicgen-large": (2.2e9, 3.2e9),
    }
    for name, (lo, hi) in expect.items():
        bundle = build(name)
        shapes = jax.eval_shape(lambda b=bundle: b.init(jax.random.PRNGKey(0)))
        n = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B params out of [{lo/1e9},{hi/1e9}]"
