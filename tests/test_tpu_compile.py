"""Ahead-of-time compiles of the main path's kernels for a described v5e.

Nothing here runs on a chip: the TPU compiler, which ships with jax,
compiles for a topology that is described, not attached, and refuses what
the chip would refuse (unaligned blocks, primitives Mosaic cannot lower)
where interpret mode accepts it.  Shapes are the real widths of the main
path.  The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops
from repro.kernels.byteshuffle import byteshuffle_pages
from repro.kernels.decode_pages import (
    decode_offset_pages, device_decode_offsets, unsplit_pages,
)
from repro.kernels.offsets_scan import offsets_scan

KERNEL = "tpu_custom_call"
PAGE = 64 * 1024  # the default page size in bytes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_offsets_scan_compiles(one_chip, no_cache):
    sizes = jax.ShapeDtypeStruct((200_000,), jnp.int32, sharding=one_chip)
    assert KERNEL in _hlo(offsets_scan, sizes)


def test_byteshuffle_pages_compiles(one_chip, no_cache):
    per = PAGE // 4  # 16 384 int32 elements per page
    pages = jax.ShapeDtypeStruct((8, per, 4), jnp.uint8, sharding=one_chip)
    assert KERNEL in _hlo(byteshuffle_pages, pages)


def test_unsplit_pages_compiles(one_chip, no_cache):
    per = PAGE // 4
    planes = jax.ShapeDtypeStruct((8, 4, per), jnp.uint8, sharding=one_chip)
    assert KERNEL in _hlo(unsplit_pages, planes)


@pytest.mark.parametrize("per", [PAGE // 8, 2048, 1000])
def test_decode_offset_pages_compiles(one_chip, no_cache, per):
    planes = jax.ShapeDtypeStruct((16, 8, per), jnp.uint8, sharding=one_chip)
    assert KERNEL in _hlo(decode_offset_pages, planes)


def test_device_decode_offsets_compiles(one_chip, no_cache):
    per = PAGE // 8
    n = 5 * per + 77  # full pages through the kernel plus a partial tail
    raw = jax.ShapeDtypeStruct((n * 8,), jnp.uint8, sharding=one_chip)
    fn = functools.partial(device_decode_offsets, n=n, per=per,
                           use_pallas=True, interpret=False)
    assert KERNEL in _hlo(fn, raw)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_train_step_attention_compiles(one_chip, no_cache, monkeypatch, grad):
    """smollm-360m attention as the train step calls it, at batch 4 x 2048,
    forward and gradient: on a TPU ``ops.flash_attention`` takes the train
    kernel (Pallas forward and backward, interpret off), so the program
    holds Mosaic kernels and no [4, 15, 2048, 2048] score tensor.  The
    backend here is the CPU, so the test says it is a TPU."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops.ATTENTION, "calls",
                        dict.fromkeys(ops.ATTENTION.calls, 0))
    cfg = get_arch("smollm-360m")
    b, s, d = 4, 2048, cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((b, cfg.n_heads, s, d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, cfg.n_kv_heads, s, d), jnp.bfloat16,
                              sharding=one_chip)

    def attn(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, window=cfg.window,
                                   impl=cfg.attn_impl)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else attn
    hlo = _hlo(fn, q, kv, kv)
    calls = ops.ATTENTION.calls
    assert calls["xla"] == 0 and calls["kernel"] > 0
    # the forward kernel, and in the gradient the fused backward kernel too
    assert hlo.count(KERNEL) >= (2 if grad else 1)
    scores = f"{b},{cfg.n_heads},{s},{s}"
    assert scores not in hlo


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_mla_attention_compiles(one_chip, no_cache, monkeypatch, grad):
    """Moonlight-16B-A3B's MLA attention as its train step calls it, at 2 x
    8192 with q/k head dim 192 (nope 128 + rope 64) and v head dim 128:
    on a TPU ``ops.flash_attention`` takes the train kernel, forward and
    gradient, and no [2, 16, 8192, 8192] score tensor exists."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops.ATTENTION, "calls",
                        dict.fromkeys(ops.ATTENTION.calls, 0))
    m = get_arch("moonlight-16b-a3b").mla
    b, h, s = 2, 16, 8192
    qk = jax.ShapeDtypeStruct((b, h, s, m.d_nope + m.d_rope), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, h, s, m.d_v), jnp.bfloat16, sharding=one_chip)
    scale = 1.0 / (m.d_nope + m.d_rope) ** 0.5

    def attn(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, scale=scale)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else attn
    hlo = _hlo(fn, qk, qk, v)
    calls = ops.ATTENTION.calls
    assert calls["xla"] == 0 and calls["kernel"] > 0
    assert hlo.count(KERNEL) >= (2 if grad else 1)
    assert f"{b},{h},{s},{s}" not in hlo


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_moe_grouped_matmul_compiles(one_chip, no_cache, monkeypatch, grad):
    """The dropless MoE layer's grouped matmul (megablox gmm, and tgmm in
    the weight gradient) at Moonlight's expert widths: 98304 rows (2 x 8192
    tokens x top-6) over the 8 held experts of 2048 x 1408.  The backend
    here is the CPU, so the test says it is a TPU (no interpret mode)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((98304, 2048), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, 2048, 1408), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)

    def mm(x, w, sizes):
        return ops.grouped_matmul(x, w, sizes)

    def loss(x, w, sizes):
        return jnp.sum(mm(x, w, sizes)[:1536].astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1)) if grad else mm
    hlo = _hlo(fn, x, w, sizes)
    # the gradient: gmm for the rows, tgmm for the weights
    assert hlo.count(KERNEL) >= (2 if grad else 1)


#: the compiler's HBM on a v5e, which it refuses to exceed
V5E_HBM = int(15.75 * 2 ** 30)


def _train_step_lowered(topo, cfg, b, s):
    """The train step (``make_train_step``) lowered for one described chip
    at batch ``b`` x ``s``."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.models.registry import build
    from repro.train.optimizer import AdamWState
    from repro.train.step import make_train_step

    bundle = build(cfg)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    jitted, _ = make_train_step(bundle, mesh)
    params = bundle.param_shapes()
    f32 = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params)
    opt = AdamWState(jax.ShapeDtypeStruct((), jnp.int32), f32, f32)
    batch = {k: jax.ShapeDtypeStruct((b, s), jnp.int32)
             for k in ("tokens", "labels")}
    return jitted(batch).lower(params, opt,
                               jax.ShapeDtypeStruct((), jnp.float32), batch)


@pytest.fixture
def v5e_memory(monkeypatch):
    """The backend here is the CPU: say it is a TPU with a v5e's memory
    limit, and count the remat policies from zero."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "device_memory_limit", lambda: V5E_HBM)
    monkeypatch.setattr(ops.REMAT, "calls", dict.fromkeys(ops.REMAT.calls, 0))


def test_smollm_train_step_saves_dots_and_fits(topo, no_cache, v5e_memory):
    """smollm-360m's train step at 4 x 2048, as the train cell runs it
    (head tied): the rule keeps the layers' matmul outputs, and the
    compiled step, with one more copy of the parameters beside it (the
    headroom's), fits the chip's memory."""
    from repro.models.backbone import _nbytes
    from repro.models.registry import build

    cfg = get_arch("smollm-360m").with_(tie_embeddings=True)
    compiled = _train_step_lowered(topo, cfg, 4, 2048).compile()
    assert ops.REMAT.calls == {"dots": 1, "full": 0}
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert peak + _nbytes(build(cfg).param_shapes()) <= V5E_HBM


def test_moonlight_train_step_keeps_full_remat(topo, no_cache, v5e_memory):
    """Moonlight-16B-A3B's train cell (one chip's share, 2 x 8192): the
    matmul outputs of its dense and MoE stacks do not fit beside the train
    state, so both stacks keep full remat."""
    from repro.configs.moonlight_16b_a3b import EIGHT_CHIP_SHARE

    _train_step_lowered(topo, EIGHT_CHIP_SHARE, 2, 8192)
    assert ops.REMAT.calls == {"dots": 0, "full": 2}
