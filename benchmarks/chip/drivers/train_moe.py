"""MoE train cells: ``TrainLoop.run`` over ``PackedLoader``'s device engine,
for a DeepSeek-V3-style configuration (MLA, routed and shared experts,
one chip's share of the experts and the vocabulary).

Set-up, window and check are the train driver's (``drivers/train.py``),
with three differences:

* the program's configuration is built from the file's DeepSeek-V3 keys
  (:func:`arch_from`), before anything else, so that a program that
  cannot run it fails at once;
* the weights before the checked steps are kept on the host, not the
  device, so that the parameters' change is taken beside nothing else on
  the chip;
* the check (:func:`moe_checks`) leaves ``grad1_norm_gap`` without a
  limit (printed, not checked: bfloat16 routing moves the first gradient
  almost as far as float8 matmuls do, PERF.md) and adds two numbers:

  - ``expert_load_gap``: half the L1 distance between the program's and
    the reference's picks per MoE layer and expert in the first step,
    over all the step's picks.  It is the least share of the step's
    (token, k) selections that the program sent to another expert than
    the reference did (the program's picks are the ones the timed step
    returned, ``StepEvent.counters["moe.picks"]``);
  - ``bias_gap``: the share of (MoE layer, expert) entries of the
    routers' correction bias after the checked steps where the program's
    and the reference's differ by more than half a bias step (gamma / 2).
    Each step moves every entry by +-gamma (or 0), so an entry differs
    by gamma or more wherever one step's sign differed; a program that
    skips the update, or moves the bias the wrong way, differs nearly
    everywhere.

The window line also carries the step's MoE counters, averaged over the
window's steps: ``moe.rows_local`` and ``moe.load_max_over_mean``.
"""

from __future__ import annotations

import time

import numpy as np

import common
from drivers import train


def arch_from(config: dict):
    """The program's ArchConfig with every size the configuration file
    states: DeepSeek-V3 keys, the chip's share of the experts
    (``n_routed_experts`` held from ``first_expert_held`` on, of
    ``router_experts``) and of the vocabulary."""
    from dataclasses import replace

    from repro.configs import get_arch
    from repro.configs.base import MLAConfig

    c = config
    base = get_arch(c["program_arch"])
    return base.with_(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        rope_theta=float(c["rope_theta"]), param_dtype=c["param_dtype"],
        compute_dtype=c["compute_dtype"],
        first_k_dense=c["first_k_dense_replace"],
        mla=MLAConfig(q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
                      d_nope=c["qk_nope_head_dim"], d_rope=c["qk_rope_head_dim"],
                      d_v=c["v_head_dim"]),
        moe=replace(
            base.moe, n_experts=c["router_experts"],
            top_k=c["num_experts_per_tok"], n_shared=c["n_shared_experts"],
            d_ff=c["moe_intermediate_size"], scoring=c["scoring_func"],
            routed_scale=c["routed_scaling_factor"],
            bias_update=c["bias_update_speed"],
            aux="seq" if c["seq_aux"] else "switch",
            aux_weight=c["aux_weight"], n_held=c["n_routed_experts"],
            first_held=c["first_expert_held"]))


class Run(train.Run):
    def __init__(self, config, traffic, seed, devices, scratch):
        super().__init__(config, traffic, seed, devices, scratch)
        self.arch = arch_from(config)

    def setup(self) -> None:
        from repro.launch.mesh import make_local_mesh
        from repro.models.registry import build
        from repro.pipeline import PackedLoader
        from repro.train import LoopConfig, TrainLoop, make_optimizer

        cfg, tr = self.config, self.traffic
        b, s = tr["batch"], tr["seq_len"]
        if s > cfg["max_position_embeddings"]:
            raise ValueError(f"seq_len {s} beyond the model's context")
        path = self.scratch / "corpus.rntj"
        self.stream = train.corpus_mod.make(cfg, tr, self.seed, path)["stream"]
        self._warm_epoch(path)

        bundle = build(self.arch)
        mesh = make_local_mesh(devices=self.devices)
        opt = cfg["optimizer"]
        self.loader = PackedLoader(str(path), batch=b, seq_len=s,
                                   eos_id=cfg["data"]["eos_id"], device="device")
        self.feed = train.TimedFeed(self.loader, self.spans)
        self.loop = TrainLoop(
            bundle, mesh, self.feed, str(self.scratch / "ckpt"),
            config=LoopConfig(steps=1, ckpt_every=2 ** 62, log_every=2 ** 62),
            optimizer=make_optimizer(
                peak_lr=opt["peak_lr"], warmup=opt["warmup_steps"],
                total=opt["total_steps"], b1=opt["b1"], b2=opt["b2"],
                eps=opt["eps"], weight_decay=opt["weight_decay"],
                clip_norm=opt["clip_norm"]))
        self.loop.params = None
        self.loop.params = self._weights(bundle)
        self._first_steps()

    def _first_steps(self) -> None:
        import jax

        k = self.traffic["checked_steps"]
        b1 = self.config["optimizer"]["b1"]
        p0 = jax.device_get(self.loop.params)
        t0 = time.perf_counter()
        self.loop.run(1)
        self.prog_g1 = self.ref.leaf_norms(self.loop.opt_state.m, 1.0 / (1.0 - b1))
        if k > 1:
            self.loop.run(k - 1)
        self.prog_delta = self.ref.host_delta_norms(self.loop.params, p0)
        del p0
        self.prog_bias = np.asarray(
            self.loop.params["layers"]["moe"][self.ref.BIAS])
        self.prog_losses = [h.loss for h in self.loop.history[:k]]
        self.prog_picks = np.asarray(self.loop.history[0].counters["moe.picks"])
        print(f"[setup] first {k} steps in {time.perf_counter() - t0:.3f} s "
              f"(compiles the step), losses {self.prog_losses}", flush=True)

    def window(self, seconds: float, tracer) -> dict:
        step0 = self.loop.step
        out = super().window(seconds, tracer)
        events = self.loop.history[step0:]
        for name in ("moe.rows_local", "moe.load_max_over_mean"):
            vals = [e.counters[name] for e in events if name in e.counters]
            out[name] = float(np.mean(vals)) if vals else float("nan")
        return out

    def check(self) -> list:
        import jax

        tr, cfg = self.traffic, self.config
        b, s = tr["batch"], tr["seq_len"]
        mismatched = 0
        for k, (tok, lab) in enumerate(self.batches):
            grid = train.corpus_mod.batch_grid(self.stream, k, b, s)
            mismatched += int(np.sum(tok != grid[:, :-1]))
            mismatched += int(np.sum(lab != grid[:, 1:]))
        kept = tr["checked_steps"]
        grids = [train.corpus_mod.batch_grid(self.stream, k, b, s)
                 for k in range(kept)]
        batches = [(g[:, :-1], g[:, 1:]) for g in grids]
        t0 = time.perf_counter()
        params = jax.jit(lambda k: self.ref.init_params(cfg, k))(
            train.seed_key(self.seed))
        ref = self.ref.run_steps(params, batches, cfg, cfg["optimizer"])
        del params
        print(f"[check] reference {kept} steps in {time.perf_counter() - t0:.3f} s,"
              f" losses {ref[0]}", flush=True)
        prog = (self.prog_losses, self.prog_g1, self.prog_delta,
                self.prog_picks, self.prog_bias)
        checks, g1_gap = moe_checks(prog, ref, mismatched, tr["limits"],
                                    cfg["bias_update_speed"])
        print(f"[check] grad1_norm_gap={g1_gap} (no limit)", flush=True)
        return checks


def moe_checks(prog, ref, mismatched, limits: dict, gamma: float):
    """(the checks that decide ``correct``, the ``grad1_norm_gap``
    reading) for a program's and a reference's (losses, first-gradient
    norms, update norms, step 1's picks, bias after the steps)."""
    # train.compare reads a limit for each of its numbers; grad1's is
    # taken out of the list below
    base = train.compare(*prog[:3], *ref[:3], mismatched,
                         {**limits, "grad1_norm_gap": float("nan")})
    g1 = next(c for c in base if c.name == "grad1_norm_gap")
    return [c for c in base if c is not g1] + [
        common.Check("expert_load_gap", load_gap(prog[3], ref[3]),
                     limits["expert_load_gap"]),
        common.Check("bias_gap", bias_gap(prog[4], ref[4], gamma),
                     limits["bias_gap"]),
    ], g1.value


def load_gap(prog_picks, ref_picks) -> float:
    """Half the L1 distance between two (L, E) tables of picks over the
    picks in all: the least share of selections that went elsewhere."""
    prog = np.asarray(prog_picks, np.float64)
    ref = np.asarray(ref_picks, np.float64)
    if prog.shape != ref.shape:
        return float("inf")
    return float(np.abs(prog - ref).sum() / (2.0 * ref.sum()))


def bias_gap(prog_bias, ref_bias, gamma: float) -> float:
    """The share of (layer, expert) bias entries where two tables differ
    by more than ``gamma / 2``."""
    prog = np.asarray(prog_bias, np.float64)
    ref = np.asarray(ref_bias, np.float64)
    if prog.shape != ref.shape:
        return float("inf")
    return float(np.mean(np.abs(prog - ref) > gamma / 2))
