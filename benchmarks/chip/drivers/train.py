"""Train cells: ``TrainLoop.run`` over ``PackedLoader``'s device engine.

Set-up generates the corpus from the seed and ingests it with the
program's parallel writer, draws one whole epoch (and the first batch of
the next) through a first ``PackedLoader`` so that every cluster's
decode and packing programs exist, builds the loop on a second loader
over the same file, puts the benchmark's own seeded weights in it, and
drives the loop's first ``checked_steps`` steps through its own ``run``
and feed (which compiles the step).  The window continues the same loop
until the deadline: the feed raises ``WindowClosed`` at the first
``next()`` after it, right after the last step's loss has reached the
host.  At today's step the window reads about 0.4 M tokens, inside the
first of the corpus's four 1 M-token clusters; a faster step crosses
into the next clusters (or wraps the epoch) with nothing left to
compile.

The check: every batch the loop consumed equals the reference packing
of the documents in file order; the first steps' losses, the first
gradient (from AdamW's first moment) and the parameters' change over
those steps agree with the plain reference (``configs/<reference>.py``)
run in float32 on the same weights and batches.
"""

from __future__ import annotations

import importlib.util
import time
from pathlib import Path

import numpy as np

import common
from drivers import corpus as corpus_mod

_ns = time.perf_counter_ns
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
#: a leaf whose reference gradient norm is under this share of the median
#: leaf's is left out of the gradient and update comparisons
LEAF_FLOOR = 1e-3


def load_reference(name: str):
    spec = importlib.util.spec_from_file_location(
        f"reference_{name}", CONFIGS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_from(config: dict):
    """The program's ArchConfig with every size the configuration file
    states (the file, not the program's preset, is the source of truth)."""
    from repro.configs import get_arch

    return get_arch(config["program_arch"]).with_(
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        param_dtype=config["param_dtype"],
        compute_dtype=config["compute_dtype"])


def seed_key(seed: int):
    """A JAX key from a seed of any size (seeds may exceed 32 bits)."""
    import jax

    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


class TimedFeed:
    """The loader as ``TrainLoop`` sees it: times each ``next()`` as a
    ``loader_next`` span, keeps every batch it hands out for the check,
    starts the tracer for the window's last seconds, and closes the window
    at its deadline."""

    def __init__(self, inner, spans: common.Spans) -> None:
        self.inner = inner
        self.spans = spans
        self.batches_out = []
        self.deadline = None
        self.trace_at = None
        self.tracer = None

    def batches(self):
        gen = self.inner.batches()
        while True:
            now = time.perf_counter()
            if self.deadline is not None and now >= self.deadline:
                raise common.WindowClosed
            if self.tracer is not None and now >= self.trace_at \
                    and self.tracer.t0 is None:
                self.tracer.start()
            with self.spans.span("loader_next"):
                b = next(gen)
            self.batches_out.append((b["tokens"], b["labels"]))
            yield b

    def state(self):
        return self.inner.state()

    def load_state(self, state):
        self.inner.load_state(state)


class Run:
    def __init__(self, config, traffic, seed, devices, scratch: Path):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.scratch = devices, scratch
        self.ref = load_reference(config["reference"])
        self.spans = common.Spans()
        self.loader = None

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        import jax

        from repro.launch.mesh import make_local_mesh
        from repro.models.registry import build
        from repro.pipeline import PackedLoader
        from repro.train import LoopConfig, TrainLoop, make_optimizer

        cfg, tr = self.config, self.traffic
        b, s = tr["batch"], tr["seq_len"]
        if s > cfg["max_position_embeddings"]:
            raise ValueError(f"seq_len {s} beyond the model's context")
        path = self.scratch / "corpus.rntj"
        self.stream = corpus_mod.make(cfg, tr, self.seed, path)["stream"]
        self._warm_epoch(path)

        bundle = build(arch_from(cfg))
        mesh = make_local_mesh(devices=self.devices)
        opt = cfg["optimizer"]
        self.loader = PackedLoader(str(path), batch=b, seq_len=s,
                                   eos_id=cfg["data"]["eos_id"], device="device")
        self.feed = TimedFeed(self.loader, self.spans)
        self.loop = TrainLoop(
            bundle, mesh, self.feed, str(self.scratch / "ckpt"),
            config=LoopConfig(steps=1, ckpt_every=2 ** 62, log_every=2 ** 62),
            optimizer=make_optimizer(
                peak_lr=opt["peak_lr"], warmup=opt["warmup_steps"],
                total=opt["total_steps"], b1=opt["b1"], b2=opt["b2"],
                eps=opt["eps"], weight_decay=opt["weight_decay"],
                clip_norm=opt["clip_norm"]))
        self.loop.params = self._weights(bundle)
        self._first_steps()

    def _warm_epoch(self, path: Path) -> None:
        """One epoch and a batch through a loader of its own: every cluster
        shape's programs, as the window may reach any of them."""
        from repro.pipeline import PackedLoader

        tr = self.traffic
        b, s = tr["batch"], tr["seq_len"]
        t0 = time.perf_counter()
        warm = PackedLoader(str(path), batch=b, seq_len=s,
                            eos_id=self.config["data"]["eos_id"], device="device")
        try:
            gen = warm.batches()
            for _ in range(len(self.stream) // (b * (s + 1)) + 2):
                last = next(gen)
            last["tokens"].block_until_ready()
        finally:
            warm.close()
        print(f"[setup] warm epoch in {time.perf_counter() - t0:.3f} s",
              flush=True)

    def _weights(self, bundle):
        """The benchmark's seeded weights, made on the device in one jitted
        call and laid out on the step's parameter shardings."""
        import jax

        from repro.distributed.sharding import auto_param_sharding

        want = bundle.param_shapes()
        made = jax.eval_shape(lambda k: self.ref.init_params(self.config, k),
                              seed_key(self.seed))
        if (jax.tree_util.tree_structure(made) != jax.tree_util.tree_structure(want)
                or any(a.shape != w.shape or a.dtype != w.dtype for a, w in zip(
                    jax.tree_util.tree_leaves(made), jax.tree_util.tree_leaves(want)))):
            raise RuntimeError("the program's parameter layout differs from "
                               "the reference's")
        sh = auto_param_sharding(want, self.loop.mesh)
        return jax.jit(lambda k: self.ref.init_params(self.config, k),
                       out_shardings=sh)(seed_key(self.seed))

    def _first_steps(self) -> None:
        import jax
        import jax.numpy as jnp

        k = self.traffic["checked_steps"]
        b1 = self.config["optimizer"]["b1"]
        p0 = jax.tree_util.tree_map(jnp.copy, self.loop.params)
        t0 = time.perf_counter()
        self.loop.run(1)
        self.prog_g1 = self.ref.leaf_norms(self.loop.opt_state.m, 1.0 / (1.0 - b1))
        if k > 1:
            self.loop.run(k - 1)
        self.prog_delta = self.ref.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, self.loop.params, p0))
        del p0
        self.prog_losses = [h.loss for h in self.loop.history[:k]]
        print(f"[setup] first {k} steps in {time.perf_counter() - t0:.3f} s "
              f"(compiles the step), losses {self.prog_losses}", flush=True)

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float, tracer) -> dict:
        tr = self.traffic
        step0 = self.loop.step
        t0 = time.perf_counter()
        self.feed.deadline = t0 + seconds
        if tracer is not None:
            self.feed.tracer = tracer
            self.feed.trace_at = t0 + max(0.0, seconds - tr["trace_seconds"])
        try:
            self.loop.run(2 ** 62)
        except common.WindowClosed:
            pass
        t1 = time.perf_counter()
        if tracer is not None:
            tracer.stop()
        self.feed.deadline = None
        steps = self.loop.step - step0
        window_s = t1 - t0
        losses = [h.loss for h in self.loop.history[-steps:]] if steps else []
        nonfinite = int(sum(not np.isfinite(x) for x in losses))
        waited = self.spans.total_ns("loader_next", int(t0 * 1e9), int(t1 * 1e9))
        slowest = self.spans.longest_ns("loader_next", int(t0 * 1e9), int(t1 * 1e9))
        tokens = steps * tr["batch"] * tr["seq_len"]
        return {
            "train_tokens_per_s": tokens / window_s,
            "attempted": steps, "failed": nonfinite,
            "window_s": window_s, "steps": steps, "tokens": tokens,
            "loader_wait_s": waited / 1e9,
            "slowest_loader_next_s": slowest / 1e9,
            "last_loss": losses[-1] if losses else float("nan"),
        }

    # -- check ----------------------------------------------------------------

    def release(self) -> None:
        import jax

        self.batches = [(np.asarray(t), np.asarray(l))
                        for t, l in self.feed.batches_out]
        self.feed.batches_out = []
        self.loop = None
        self.feed = None
        for x in jax.live_arrays():
            x.delete()

    def check(self) -> list:
        import jax

        tr, cfg = self.traffic, self.config
        b, s = tr["batch"], tr["seq_len"]
        mismatched = 0
        for k, (tok, lab) in enumerate(self.batches):
            grid = corpus_mod.batch_grid(self.stream, k, b, s)
            mismatched += int(np.sum(tok != grid[:, :-1]))
            mismatched += int(np.sum(lab != grid[:, 1:]))
        kept = self.traffic["checked_steps"]
        batches = [(corpus_mod.batch_grid(self.stream, k, b, s)[:, :-1],
                    corpus_mod.batch_grid(self.stream, k, b, s)[:, 1:])
                   for k in range(kept)]
        t0 = time.perf_counter()
        params = jax.jit(lambda k: self.ref.init_params(cfg, k))(seed_key(self.seed))
        ref_losses, ref_g1, ref_delta = self.ref.run_steps(
            params, batches, cfg, cfg["optimizer"])
        del params
        print(f"[check] reference {kept} steps in {time.perf_counter() - t0:.3f} s,"
              f" losses {ref_losses}", flush=True)
        return compare(self.prog_losses, self.prog_g1, self.prog_delta,
                       ref_losses, ref_g1, ref_delta, mismatched,
                       tr["limits"])

    def close(self) -> None:
        if self.loader is not None:
            self.loader.close()


def norm_gap(prog: dict, ref: dict, keep) -> float:
    """The worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's."""
    med = float(np.median(list(ref.values())))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep]
    return max(gaps) if gaps else float("nan")


def compare(prog_losses, prog_g1, prog_delta, ref_losses, ref_g1, ref_delta,
            mismatched, limits: dict) -> list:
    """The numbers that decide ``correct`` for a train cell."""
    med = float(np.median(list(ref_g1.values())))
    # leaves whose reference gradient is nought to rounding (under
    # LEAF_FLOOR of the median leaf's) move under Adam by round-off alone
    keep = [k for k, v in ref_g1.items() if v >= LEAF_FLOOR * med]
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog_losses, ref_losses))
    if len(prog_losses) != len(ref_losses) or not np.all(np.isfinite(prog_losses)):
        loss_gap = float("inf")
    return [
        common.Check("batch_tokens_mismatched", mismatched,
                     limits["batch_tokens_mismatched"]),
        common.Check("loss_gap", loss_gap, limits["loss_gap"]),
        common.Check("grad1_norm_gap", norm_gap(prog_g1, ref_g1, keep),
                     limits["grad1_norm_gap"]),
        common.Check("update_norm_gap", norm_gap(prog_delta, ref_delta, keep),
                     limits["update_norm_gap"]),
    ]
