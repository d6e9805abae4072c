"""The dropless expert-share MoE layer and DeepSeek-V3 routing (CPU).

The program against the plain reference the benchmark's check uses
(``benchmarks/chip/configs/deepseek_v3.py``) at a tiny Moonlight shape:
MLA without query compression, 16 routed experts of which a share is
held, top-3, 2 shared experts, 1 dense + 2 MoE layers.  The program runs
in float32 here, so the two agree to rounding; the chip's check compares
the bfloat16 program.
"""

from __future__ import annotations

import json
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

from drivers.train import load_reference  # noqa: E402
from drivers.train_moe import arch_from, bias_gap, load_gap, moe_checks  # noqa: E402

import counts_moe  # noqa: E402

from repro.models import build  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.layers import mlp_apply  # noqa: E402
from repro.train.optimizer import AdamW, cosine_schedule  # noqa: E402

REF = load_reference("deepseek_v3")
TINY = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 96, "vocab_size": 256,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "moe_intermediate_size": 32, "router_experts": 16,
        "n_routed_experts": 4, "first_expert_held": 0,
        "num_experts_per_tok": 3, "aux_weight": 0.01,
        "compute_dtype": "float32"}


def tiny(**over) -> dict:
    cfg = json.loads((CHIP / "configs" / "moonlight-16b-a3b.json").read_text())
    return {**cfg, **TINY, **over}


def _steps(cfg, params, batches):
    """The program's train steps (``make_train_step``) from ``params``:
    losses, first-step gradient norms, update norms, first-step picks,
    the final parameters and optimizer state."""
    from repro.launch.mesh import make_local_mesh
    from repro.train import make_optimizer, make_train_step

    o = cfg["optimizer"]
    opt = make_optimizer(peak_lr=o["peak_lr"], warmup=o["warmup_steps"],
                         total=o["total_steps"], b1=o["b1"], b2=o["b2"],
                         eps=o["eps"], weight_decay=o["weight_decay"],
                         clip_norm=o["clip_norm"])
    bundle = build(arch_from(cfg))
    jitted, _ = make_train_step(bundle, make_local_mesh(devices=jax.devices()[:1]),
                                optimizer=opt)
    p0 = jax.device_get(params)
    params = jax.tree_util.tree_map(jnp.array, params)
    state, err = opt.init(params), jnp.zeros(())
    losses, g1, picks1 = [], None, None
    for tok, lab in batches:
        batch = {"tokens": jnp.asarray(tok), "labels": jnp.asarray(lab)}
        step = jitted(jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
        params, state, err, metrics = step(params, state, err, batch)
        losses.append(float(metrics["loss"]))
        if g1 is None:
            g1 = REF.leaf_norms(state.m, 1.0 / (1.0 - o["b1"]))
            picks1 = np.asarray(metrics["moe.picks"])
    return losses, g1, REF.host_delta_norms(params, p0), picks1, params, state


def _batches(cfg, n, b=2, s=32, seed=0):
    rng = np.random.default_rng(seed)
    grids = [rng.integers(0, cfg["vocab_size"], (b, s + 1)).astype(np.int32)
             for _ in range(n)]
    return [(g[:, :-1], g[:, 1:]) for g in grids]


LIMITS = {"batch_tokens_mismatched": 0, "loss_gap": 1e-5,
          "update_norm_gap": 1e-4, "expert_load_gap": 0.0, "bias_gap": 0.0}


def _program(cfg, params, batches):
    """The program's readings as the cell's check takes them."""
    losses, g1, delta, picks, after, _ = _steps(cfg, params, batches)
    return losses, g1, delta, picks, np.asarray(after["layers"]["moe"][MOE.BIAS])


@pytest.mark.parametrize("first", [0, 12], ids=["experts-0-3", "experts-12-15"])
def test_program_matches_reference(first):
    """Loss of each of 3 steps, the first gradient and the update over the
    steps (the router bias moving after each) against the reference; the
    first step's picks per layer and expert, and the bias after the
    steps, exactly."""
    cfg = tiny(first_expert_held=first)
    params = REF.init_params(cfg, jax.random.PRNGKey(3))
    batches = _batches(cfg, 3)
    prog = _program(cfg, params, batches)
    ref = REF.run_steps(params, batches, cfg, cfg["optimizer"])
    checks, g1_gap = moe_checks(prog, ref, 0, LIMITS, cfg["bias_update_speed"])
    assert all(c.ok for c in checks), [(c.name, c.value) for c in checks]
    assert g1_gap < 1e-4
    # the bias moved: most entries are a bias step or more from 0
    assert np.mean(np.abs(ref[4]) > 0.5 * cfg["bias_update_speed"]) > 0.8


@pytest.mark.parametrize("sign", [0.0, -1.0], ids=["update-skipped", "wrong-sign"])
def test_bias_gap_catches_a_wrong_bias_step(sign, monkeypatch):
    """A program whose bias update is skipped, or moves the bias the wrong
    way, fails ``bias_gap`` on nearly every entry, though its loss stays
    close to the reference's."""
    cfg = tiny()
    params = REF.init_params(cfg, jax.random.PRNGKey(3))
    batches = _batches(cfg, 3)
    right = MOE.update_bias
    monkeypatch.setattr(MOE, "update_bias", lambda bias, picks, c: bias + sign * (
        right(bias, picks, c) - bias))
    prog = _program(cfg, params, batches)
    ref = REF.run_steps(params, batches, cfg, cfg["optimizer"])
    checks, _ = moe_checks(prog, ref, 0, {**LIMITS, "bias_gap": 0.1},
                           cfg["bias_update_speed"])
    got = {c.name: c.value for c in checks}
    assert got["bias_gap"] > 0.8
    assert got["loss_gap"] < 1e-3


def _layer(params, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], params["layers"]["moe"])


def _ref_layer(p, x, cfg):
    """The reference MoE layer over each row of x (B, T, D)."""
    n = REF.dims(cfg)
    return jnp.stack([REF._moe(p, row, cfg, n, REF.dot_f32)[0] for row in x])


def _share(p, first, held):
    ex = jax.tree_util.tree_map(lambda a: a[first:first + held], p["experts"])
    return {**p, "experts": ex}


def test_expert_shares_sum_to_uncut_layer():
    """Four disjoint shares of 4 experts: their outputs, the shared experts
    counted once, add up to the reference's layer with all 16 held."""
    full = tiny(n_routed_experts=16)
    params = REF.init_params(full, jax.random.PRNGKey(5))
    p = _layer(params)
    p = {**p, MOE.BIAS: jax.random.uniform(jax.random.PRNGKey(6), (16,)) * 0.1}
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 64))
    want = _ref_layer(p, x, full)
    total = 0.0
    for first in (0, 4, 8, 12):
        cfg = arch_from(tiny(first_expert_held=first))
        y, _, stats = MOE.moe_apply(_share(p, first, 4), x, cfg)
        total = total + y
    cfg = arch_from(tiny())
    total = total - 3 * mlp_apply(p["shared"], x, cfg)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_dropless_under_forced_imbalance():
    """A bias sends every token to held expert 5: all of them are computed
    (no capacity), and the layer equals the reference's share."""
    cfg_d = tiny(first_expert_held=4)
    params = REF.init_params(cfg_d, jax.random.PRNGKey(8))
    p = _layer(params)
    p = {**p, MOE.BIAS: p[MOE.BIAS].at[5].set(10.0)}
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 64))
    y, _, stats = MOE.moe_apply(p, x, arch_from(cfg_d))
    held = np.asarray(stats["held"])
    assert held[1] == 2 * 64 and held.sum() <= 2 * 64 * 3
    assert int(np.asarray(stats["picks"])[5]) == 2 * 64
    np.testing.assert_allclose(np.asarray(y), np.asarray(_ref_layer(p, x, cfg_d)),
                               rtol=1e-4, atol=1e-5)


def test_bias_update_sign_rule():
    """After a step the bias moves by gamma * sign(mean_j c_j - c_i) over
    the step's picks, and only by that: no gradient, moments of 0, no
    weight decay."""
    cfg = tiny()
    params = REF.init_params(cfg, jax.random.PRNGKey(10))
    bias0 = np.asarray(params["layers"]["moe"][MOE.BIAS])
    _, _, _, picks, after, state = _steps(cfg, params, _batches(cfg, 1))
    c = picks.astype(np.float64)
    want = bias0 + cfg["bias_update_speed"] * np.sign(c.mean(-1, keepdims=True) - c)
    np.testing.assert_allclose(np.asarray(after["layers"]["moe"][MOE.BIAS]),
                               want, rtol=0, atol=1e-7)
    assert not np.any(np.asarray(state.m["layers"]["moe"][MOE.BIAS]))
    assert not np.any(np.asarray(state.v["layers"]["moe"][MOE.BIAS]))


def test_adamw_leaves_router_bias_alone():
    """The model marks ``router_bias`` (rank 2 as stacked layers) as state,
    and AdamW, handed that mask, leaves it as it is whatever gradient it
    gets, while it updates and decays a weight of rank 2."""
    opt = AdamW(schedule=cosine_schedule(1e-2, 0, 10), weight_decay=0.1)
    params = {"w": jnp.ones((2, 3)),
              "layers": {"moe": {MOE.BIAS: jnp.ones((2, 3))}}}
    frozen = build(arch_from(tiny())).state_mask(params)
    assert frozen == {"w": False, "layers": {"moe": {MOE.BIAS: True}}}
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    new, _ = opt.update(grads, opt.init(params), params, frozen)
    assert not np.allclose(np.asarray(new["w"]), 1.0)
    np.testing.assert_array_equal(np.asarray(new["layers"]["moe"][MOE.BIAS]), 1.0)
    new, _ = opt.update(grads, opt.init(params), params)
    assert not np.allclose(np.asarray(new["layers"]["moe"][MOE.BIAS]), 1.0)


# ---------------------------------------------------------------------------
# counts_moe.py against hand-worked shapes

HAND = {"hidden_size": 4, "num_attention_heads": 2, "qk_nope_head_dim": 2,
        "qk_rope_head_dim": 2, "v_head_dim": 2, "kv_lora_rank": 2,
        "intermediate_size": 8, "moe_intermediate_size": 2,
        "router_experts": 4, "n_routed_experts": 2, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "first_k_dense_replace": 1,
        "num_hidden_layers": 2, "vocab_size": 10}


@pytest.mark.parametrize("what,got,want", [
    # MLA 32+8+8+8+8+16 = 80 a layer; dense SwiGLU 96; MoE: router 16,
    # routed 2 x 2/4 x 24 = 24, shared 24; head 40 -> 6 x 360; attention
    # 2 layers x 2 x 2 heads x (4 + 2) x 4 / 2 = 96 -> 3 x 96
    ("flops_per_token", lambda: counts_moe.moe_train_flops_per_token(HAND, 4),
     6 * 360 + 3 * 96),
    # 2 x 10 rows x 4 x 2; bytes 2 x (10 x 6 + 2 experts x 4 x 2)
    ("gmm", lambda: counts_moe.gmm_cost(HAND, 10), {"flops": 160.0, "bytes": 152.0}),
    # 2 heads x 8 scores x 2 x (4 + 2); bytes 2 x (4 x 12 x 2 + 4 x 4)
    ("attention_fwd", lambda: counts_moe.mla_attention_cost(HAND, 1, 4, False),
     {"flops": 192.0, "bytes": 224.0}),
    # 2 x 8 x 2 x (3 x 4 + 2 x 2); bytes + 2 x (4 x 10 x 2 + 4 x 2 x 2)
    ("attention_bwd", lambda: counts_moe.mla_attention_cost(HAND, 1, 4, True),
     {"flops": 512.0, "bytes": 416.0}),
])
def test_counts_moe_hand_worked(what, got, want):
    assert got() == want


def test_counts_moe_at_the_cell():
    """The cell's shapes give the 2.64 GFLOP a token the cut model needs."""
    cfg = json.loads((CHIP / "configs" / "moonlight-16b-a3b.json").read_text())
    assert 2.62e9 < counts_moe.moe_train_flops_per_token(cfg, 8192) < 2.65e9


def test_load_gap():
    a = np.array([[3, 1, 2, 0]])
    assert load_gap(a, a) == 0.0
    assert load_gap(np.array([[2, 2, 2, 0]]), a) == pytest.approx(1 / 6)


REF_BIAS = 1e-3 * np.array([[3.0, 1.0, -1.0, -3.0], [1.0, 1.0, -1.0, -1.0]])


@pytest.mark.parametrize("prog,want", [
    (REF_BIAS, 0.0),
    (REF_BIAS + 4e-4, 0.0),                         # under gamma / 2
    (REF_BIAS * np.array([[1, 1, 1, 1], [1, 1, 1, -1]]), 1 / 8),
    (REF_BIAS * np.array([[1, 1, 1, 1], [1, 1, 3, 1]]), 1 / 8),
    (np.zeros_like(REF_BIAS), 1.0),                 # the update skipped
    (-REF_BIAS, 1.0),                               # the wrong sign
    (REF_BIAS[:1], float("inf")),
], ids=["same", "rounding", "one-sign", "one-step", "frozen", "flipped", "shape"])
def test_bias_gap(prog, want):
    assert bias_gap(prog, REF_BIAS, 1e-3) == want
