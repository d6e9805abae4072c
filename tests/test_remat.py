"""The layer stacks' activation-checkpoint policy, chosen by memory (CPU).

Where the device's memory holds them, every remat'd stack keeps its
layers' matmul outputs for the backward (``backbone.SAVE_DOTS``);
otherwise, or where no memory limit can be read, it keeps full remat.
The CPU reports no limit, so the tests patch one in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import smoke_config
from repro.core import stats
from repro.kernels import ops
from repro.models import backbone, build

B, S = 2, 32
HUGE = 1 << 50


def _cfg(name: str):
    return smoke_config(name).with_(remat=True, compute_dtype="float32")


def _batch(cfg, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0,
                             cfg.vocab_size)
    return {"tokens": tok[:, :-1], "labels": tok[:, 1:]}


@pytest.fixture
def remat_records(monkeypatch):
    """Records kept as under a profiler session, counters from zero."""
    assert stats._bind_jax()
    monkeypatch.setattr(stats, "_is_enabled", lambda: True)
    for counter in (ops.REMAT, ops.ATTENTION, ops.MOE):
        monkeypatch.setattr(counter, "calls", dict.fromkeys(counter.calls, 0))
    stats.clear()
    yield
    stats.clear()


def _records():
    return [(r.name, r.key) for r in stats.records()
            if r.name.startswith("remat.")]


def _limit(monkeypatch, limit):
    monkeypatch.setattr(ops, "device_memory_limit", lambda: limit)


def _loss_and_grads(cfg, params, batch):
    bundle = build(cfg)
    return jax.jit(jax.value_and_grad(bundle.loss, has_aux=True))(params, batch)


@pytest.mark.parametrize("name", ["smollm-360m", "moonlight-16b-a3b"],
                         ids=["dense", "moe"])
def test_saving_policy_matches_full_remat(name, monkeypatch, remat_records):
    """Loss, metrics and gradients under the saving policy equal full
    remat's (f32): the policy only changes what the backward recomputes.
    Each stack is counted once per trace, and the probe that measures the
    saved bytes counts no attention or MoE layer."""
    cfg = _cfg(name)
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    n_stacks = len(backbone._stacks(cfg))

    _limit(monkeypatch, None)
    (loss_f, m_f), g_f = _loss_and_grads(cfg, params, batch)
    assert ops.REMAT.calls == {"dots": 0, "full": n_stacks}
    assert sum(ops.ATTENTION.calls.values()) == n_stacks

    _limit(monkeypatch, HUGE)
    (loss_d, m_d), g_d = _loss_and_grads(cfg, params, batch)
    assert ops.REMAT.calls == {"dots": n_stacks, "full": n_stacks}
    assert sum(ops.ATTENTION.calls.values()) == 2 * n_stacks
    assert sum(ops.MOE.calls.values()) == (2 if cfg.moe else 0)

    np.testing.assert_allclose(float(loss_d), float(loss_f), rtol=1e-6)
    for k in m_f:
        np.testing.assert_allclose(np.asarray(m_d[k]), np.asarray(m_f[k]),
                                   rtol=1e-6, atol=1e-6)
    leaves_f = jax.tree_util.tree_leaves(g_f)
    leaves_d = jax.tree_util.tree_leaves(g_d)
    assert len(leaves_f) == len(leaves_d)
    for a, b in zip(leaves_d, leaves_f):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=1e-6,
                                   atol=1e-6 * max(1.0, np.abs(b).max()))


def _trace(cfg):
    """Trace (not run) the loss's gradient at the test's batch shape."""
    bundle = build(cfg)
    params = bundle.param_shapes()
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32)
             for k in ("tokens", "labels")}
    jax.eval_shape(jax.grad(lambda p, b: bundle.loss(p, b)[0]), params, batch)


@pytest.mark.parametrize("name", ["smollm-360m", "moonlight-16b-a3b"],
                         ids=["dense", "moe"])
@pytest.mark.parametrize("where", ["above", "at", "below", "unreadable"])
def test_rule_picks_by_the_memory_limit(name, where, monkeypatch,
                                        remat_records):
    """``dots`` where the counted bytes fit the limit less the state and
    headroom (up to the byte), ``full`` a byte below it and where no limit
    can be read.  Every stack of a step takes the same policy, and each
    record holds the stack's shapes, the saved bytes and the budget."""
    cfg = _cfg(name)
    stacks = backbone._stacks(cfg)
    _limit(monkeypatch, HUGE)
    _trace(cfg)
    (_, key), *_ = _records()
    saved, budget = key[-2:]
    assert 0 < saved < budget
    # the limit at which the budget equals the saved bytes
    fit = HUGE - budget + saved
    limit = {"above": fit + (1 << 20), "at": fit, "below": fit - 1,
             "unreadable": None}[where]
    stats.clear()
    monkeypatch.setattr(ops.REMAT, "calls", dict.fromkeys(ops.REMAT.calls, 0))
    _limit(monkeypatch, limit)
    _trace(cfg)
    path = "dots" if where in ("above", "at") else "full"
    assert ops.REMAT.calls[path] == len(stacks) == sum(ops.REMAT.calls.values())
    recs = _records()
    assert [n for n, _ in recs] == [f"remat.{path}"] * len(stacks)
    for (_, k), (key_, layer_cfg) in zip(recs, stacks):
        shape, n_layers, stack_bytes, saved_k, budget_k = k
        assert shape == (B, S, cfg.d_model)
        stacked = build(cfg).param_shapes()[key_]
        assert n_layers == jax.tree_util.tree_leaves(stacked)[0].shape[0]
        assert stack_bytes == backbone._nbytes(stacked)
        if limit is None:
            assert saved_k is None and budget_k is None
        else:
            assert saved_k == saved and budget_k == limit - HUGE + budget


def test_budget_counts_state_and_headroom():
    """The budget is the limit less parameters and gradients, AdamW's two
    f32 moments, one more copy of the parameters and the f32 logits with
    their gradient."""
    cfg = _cfg("smollm-360m")
    params = {"a": jax.ShapeDtypeStruct((10, 4), jnp.float32),
              "b": jax.ShapeDtypeStruct((6,), jnp.bfloat16)}
    x = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.float32)
    pbytes = 10 * 4 * 4 + 6 * 2
    want = 10 ** 9 - 3 * pbytes - 2 * 4 * 46 - 2 * 4 * B * S * cfg.vocab_size
    assert backbone.remat_budget(params, x, cfg, 10 ** 9) == want


def test_no_remat_config_checkpoints_nothing(monkeypatch, remat_records):
    """``remat=False`` keeps meaning no checkpoint: nothing is counted and
    no ``checkpoint`` appears in the gradient's program."""
    cfg = _cfg("smollm-360m").with_(remat=False)
    _limit(monkeypatch, HUGE)
    bundle = build(cfg)
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32)
             for k in ("tokens", "labels")}
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: bundle.loss(p, b)[0]))(
        bundle.param_shapes(), batch)
    assert "checkpoint" not in str(jaxpr) and "remat" not in str(jaxpr)
    assert ops.REMAT.calls == {"dots": 0, "full": 0} and _records() == []
