"""Training loop: loss goes down, exact restart, stragglers, compression."""

import time
from dataclasses import replace

import jax
import numpy as np
import pytest

from repro.configs import get_arch, smoke_config
from repro.launch.mesh import make_local_mesh
from repro.models import build
from repro.pipeline import PackedLoader, ingest_corpus, synth_corpus
from repro.train import LoopConfig, TrainLoop, make_optimizer


def tiny_cfg():
    return get_arch("smollm-360m").with_(
        name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=256, remat=False,
    )


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    p = str(tmp_path_factory.mktemp("data") / "c.rntj")
    ingest_corpus(synth_corpus(300, seed=0, mean_len=80, vocab=256), p,
                  n_workers=2)
    return p


def make_loop(corpus, ckpt_dir, steps=20, arch=None, **cfg_kw):
    bundle = build(arch or tiny_cfg())
    loader = PackedLoader(corpus, batch=4, seq_len=32)
    return TrainLoop(
        bundle, make_local_mesh(), loader, ckpt_dir,
        config=LoopConfig(steps=steps, ckpt_every=10, log_every=1000,
                          ckpt_async=False, **cfg_kw),
        optimizer=make_optimizer(peak_lr=5e-3, warmup=5, total=200),
    )


def test_loss_decreases(corpus, tmp_path):
    loop = make_loop(corpus, str(tmp_path / "ck"), steps=60)
    hist = loop.run()
    first = np.mean([h.loss for h in hist[:5]])
    last = np.mean([h.loss for h in hist[-5:]])
    assert last < first - 0.05, (first, last)


def test_restart_is_exact(corpus, tmp_path):
    """20 straight steps == 10 steps + crash + 10 resumed steps."""
    a = make_loop(corpus, str(tmp_path / "a"), steps=20)
    a.run()
    ref = jax.tree_util.tree_leaves(a.params)

    b1 = make_loop(corpus, str(tmp_path / "b"), steps=10)
    b1.run()
    del b1  # "crash" after the step-10 checkpoint
    b2 = make_loop(corpus, str(tmp_path / "b"), steps=10)
    assert b2.step == 10  # restored
    b2.run()
    got = jax.tree_util.tree_leaves(b2.params)
    for x, y in zip(ref, got):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   atol=1e-6, rtol=1e-6)


def test_straggler_detection(corpus, tmp_path):
    events = []
    loop = make_loop(corpus, str(tmp_path / "s"), steps=10)
    loop.on_straggler = events.append
    loop.run()                       # warm: builds timing baseline
    orig = loop._step_fn

    def slow(*a):
        time.sleep(max(0.2, 10 * np.median(loop._step_times)))
        return orig(*a)

    loop._step_fn = slow
    loop.run(steps=1)
    assert events and events[-1].straggler


def test_grad_compression_runs(corpus, tmp_path):
    loop = make_loop(corpus, str(tmp_path / "g"), steps=10,
                     grad_compression=True)
    hist = loop.run()
    assert all(np.isfinite(h.loss) for h in hist)


def test_microbatched_matches_plain(corpus, tmp_path):
    """Gradient accumulation matches the single-batch step (absolute tol:
    bf16 reduction-order differences pass through Adam's 1/sqrt(v) early)."""
    a = make_loop(corpus, str(tmp_path / "m1"), steps=3)
    a.run()
    b = make_loop(corpus, str(tmp_path / "m2"), steps=3, microbatches=2)
    b.run()
    for x, y in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=2e-2)
    assert abs(a.history[-1].loss - b.history[-1].loss) < 0.05


def test_moe_step_counters_reach_the_history(corpus, tmp_path):
    """A step's metrics beyond the loss reach its StepEvent: a MoE model's
    counters, and a dense model's ``ce`` and ``aux``.  The MoE model is a
    small Moonlight shape (1 dense + 3 MoE layers, 8 experts of which 4
    held, top-2) over 4 x 32 tokens."""
    cfg = smoke_config("moonlight-16b-a3b")
    cfg = cfg.with_(vocab_size=256,
                    moe=replace(cfg.moe, n_held=4, first_held=2))
    hist = make_loop(corpus, str(tmp_path / "moe"), steps=3, arch=cfg).run()
    layers, k, gamma = cfg.n_layers - cfg.first_k_dense, 2, cfg.moe.bias_update
    for ev in hist:
        picks = np.asarray(ev.counters["moe.picks"])
        assert picks.shape == (layers, 8)
        assert (picks.sum(-1) == 4 * 32 * k).all()
        assert ev.counters["moe.rows_local"] == picks[:, 2:6].sum()
        assert ev.counters["moe.load_max_over_mean"] >= 1.0
        assert 0.0 < ev.counters["moe.bias_abs_mean"] <= ev.step * gamma + 1e-9
    dense = make_loop(corpus, str(tmp_path / "dense"), steps=1).run()
    assert set(dense[0].counters) == {"ce", "aux"}
    assert dense[0].counters["ce"] == dense[0].loss
    assert dense[0].counters["aux"] == 0.0
