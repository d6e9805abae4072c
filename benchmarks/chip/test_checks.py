"""The checks that decide ``correct`` fail when they should.

Each test drives a whole run of a cell at a small size on whatever
backend JAX has (the chip check is skipped; the CPU does), with the timed
path broken underneath, and sees ``correct`` come out false; the sound
run beside them comes out true.  The controls are the plain reference one
precision below what the configuration states, put in the program's
place.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench  # noqa: E402

TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
              "max_position_embeddings": 64}
TINY = {
    "smollm360m.train_packed": {
        "config": TINY_MODEL,
        "traffic": {"batch": 4, "seq_len": 32, "corpus_tokens": 20000}},
    "smollm360m.loader_stream": {
        "config": TINY_MODEL,
        "traffic": {"batch": 4, "seq_len": 32, "corpus_tokens": 20000}},
    "synthetic.write_zstd8": {
        "config": {"cluster_bytes": 1 << 20, "level": 1},
        "traffic": {"producers": 2, "batch_entries": 4096}},
}
SEED = 2 ** 31 + 11


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def correct(cell: str) -> dict:
    result, _ = bench.run_cell(cell, SEED, 0.5, False, need_chip=False,
                               overrides=TINY[cell], t_start=time.perf_counter())
    return result


# -- train ----------------------------------------------------------------


def _reference():
    from drivers.train import load_reference

    return load_reference("llama")


def test_train_sound_run_is_correct():
    assert correct("smollm360m.train_packed")["correct"]


def test_train_state_unchanged_fails(monkeypatch):
    import repro.train.loop as loop_mod

    real = loop_mod.make_train_step

    def frozen_step(*a, **kw):
        jitted_for, sh = real(*a, **kw)

        def for_shapes(shapes):
            step = jitted_for(shapes)

            def run(params, opt, err, batch):
                import jax
                import jax.numpy as jnp

                copies = jax.tree_util.tree_map(jnp.copy, (params, opt, err))
                *_, metrics = step(*copies, batch)
                return params, opt, err, metrics
            return run
        return for_shapes, sh

    monkeypatch.setattr(loop_mod, "make_train_step", frozen_step)
    res = correct("smollm360m.train_packed")
    assert not res["correct"]
    assert res["checks"]["update_norm_gap"]["value"] > 0.9


def test_train_half_batch_fails(monkeypatch):
    from repro.train import TrainLoop

    real = TrainLoop.place

    def half(self, batch):
        b = batch["tokens"].shape[0] // 2
        return real(self, {k: v[:b] for k, v in batch.items()})

    monkeypatch.setattr(TrainLoop, "place", half)
    assert not correct("smollm360m.train_packed")["correct"]


def test_train_token_altered_fails(monkeypatch):
    _alter_loader_token(monkeypatch)
    res = correct("smollm360m.train_packed")
    assert not res["correct"]
    assert res["checks"]["batch_tokens_mismatched"]["value"] >= 1


def test_train_control_fp8_fails(monkeypatch):
    """The float32 reference computed with float8 matmuls, in the step's
    place."""
    import jax

    import repro.train.loop as loop_mod
    from repro.train.optimizer import AdamWState

    ref = _reference()
    real = loop_mod.make_train_step

    def control_step(bundle, mesh, optimizer=None, **kw):
        _, sh = real(bundle, mesh, optimizer=optimizer, **kw)
        cfg = {**bench.load_cell("smollm360m.train_packed")["config"],
               **TINY_MODEL}
        opt = cfg["optimizer"]

        @jax.jit
        def step(params, state, err, batch):
            loss, grads = ref.loss_and_grad(params, batch["tokens"],
                                            batch["labels"], cfg, ref.dot_fp8)
            p, m, v, n = ref.adamw(params, grads, state.m, state.v,
                                   state.step, opt)
            return p, AdamWState(n, m, v), err, {"loss": loss}
        return (lambda shapes: step), sh

    monkeypatch.setattr(loop_mod, "make_train_step", control_step)
    assert not correct("smollm360m.train_packed")["correct"]


# -- loader ---------------------------------------------------------------


def _alter_loader_token(monkeypatch):
    from repro.pipeline import PackedLoader

    real = PackedLoader.batches

    def altered(self):
        for i, b in enumerate(real(self)):
            if i == 1:
                b = {**b, "tokens": b["tokens"].at[0, 3].add(1)}
            yield b

    monkeypatch.setattr(PackedLoader, "batches", altered)


def _map_loader_batches(monkeypatch, fn):
    from repro.pipeline import PackedLoader

    real = PackedLoader.batches

    def mapped(self):
        for b in real(self):
            yield {k: fn(v) for k, v in b.items()}

    monkeypatch.setattr(PackedLoader, "batches", mapped)


def test_loader_sound_run_is_correct():
    assert correct("smollm360m.loader_stream")["correct"]


def test_loader_token_altered_fails(monkeypatch):
    _alter_loader_token(monkeypatch)
    assert not correct("smollm360m.loader_stream")["correct"]


def test_loader_half_batch_fails(monkeypatch):
    _map_loader_batches(monkeypatch, lambda v: v.at[v.shape[0] // 2:].set(0))
    assert not correct("smollm360m.loader_stream")["correct"]


def test_loader_control_int16_fails(monkeypatch):
    import jax.numpy as jnp

    _map_loader_batches(monkeypatch,
                        lambda v: v.astype(jnp.int16).astype(jnp.int32))
    # the tiny vocabulary fits 16 bits; shift ids past 2**15 so that the
    # narrower type has something to lose, as the full vocabulary does
    monkeypatch.setitem(TINY["smollm360m.loader_stream"]["config"],
                        "vocab_size", 49152)
    assert not correct("smollm360m.loader_stream")["correct"]


# -- write ----------------------------------------------------------------


def _map_fill(monkeypatch, fn):
    from repro.core import ColumnBatch, FillContext

    real = FillContext.fill_batch

    def mapped(self, batch):
        by_path = {c.path: batch.data[c.index].copy()
                   for c in batch.schema.columns}
        n, by_path = fn(batch.n_entries, by_path)
        real(self, ColumnBatch.from_arrays(batch.schema, n, by_path))

    monkeypatch.setattr(FillContext, "fill_batch", mapped)


def test_write_sound_run_is_correct():
    assert correct("synthetic.write_zstd8")["correct"]


def test_write_value_altered_fails(monkeypatch):
    def alter(n, d):
        d["vals._0"][0] += 1.0
        return n, d

    _map_fill(monkeypatch, alter)
    assert not correct("synthetic.write_zstd8")["correct"]


def test_write_half_batch_fails(monkeypatch):
    def half(n, d):
        h = n // 2
        kept = int(d["vals"][:h].sum())
        return h, {"id": d["id"][:h], "vals": d["vals"][:h],
                   "vals._0": d["vals._0"][:kept]}

    _map_fill(monkeypatch, half)
    assert not correct("synthetic.write_zstd8")["correct"]


def test_write_control_bf16_fails(monkeypatch):
    def bf16(n, d):
        d["vals._0"].view(np.uint32)[:] &= np.uint32(0xFFFF0000)
        return n, d

    _map_fill(monkeypatch, bf16)
    assert not correct("synthetic.write_zstd8")["correct"]
