"""The controls that the limits of a MoE train cell's ``correct`` must
catch (not part of a benchmark run; the program's own readings are the
checks of the cell's runs).  For each seed, in one process on the chip,
the plain reference's first steps in float32 on the seed's weights and
batches, and against them:

* ``control_fp8``: the reference with every matmul in float8 e4m3 (one
  precision below the bfloat16 that the configuration states) put in
  the program's place;
* ``fault_half_batch``: the reference with half of each batch left out;
* ``fault_bias_frozen``: the reference with the routers' bias update
  skipped (the bias stays 0);
* ``fault_bias_sign``: the reference moving the bias the wrong way
  (``-gamma``);

each compared as the cell's check compares the program.

    python3 benchmarks/chip/calibrate_moe.py --workload <cell> --seeds 1,2,3 \
        [--readings control_fp8,fault_bias_frozen]

Prints one JSON line per seed and reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench  # noqa: E402
import common  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


READINGS = ("control_fp8", "fault_half_batch", "fault_bias_frozen",
            "fault_bias_sign")


def controls(spec, seed, scratch, readings) -> None:
    import jax

    from drivers import corpus as corpus_mod
    from drivers.train import load_reference, seed_key
    from drivers.train_moe import moe_checks

    cfg, tr = spec["config"], spec["traffic"]
    gamma = cfg["bias_update_speed"]
    ref_mod = load_reference(cfg["reference"])
    stream = corpus_mod.make(cfg, tr, seed, scratch / "corpus.rntj")["stream"]
    b, s, k = tr["batch"], tr["seq_len"], tr["checked_steps"]
    grids = [corpus_mod.batch_grid(stream, i, b, s) for i in range(k)]
    full = [(g[:, :-1], g[:, 1:]) for g in grids]
    half = [(g[: b // 2, :-1], g[: b // 2, 1:]) for g in grids]
    runs = {"control_fp8": (full, "fp8", gamma),
            "fault_half_batch": (half, "f32", gamma),
            "fault_bias_frozen": (full, "f32", 0.0),
            "fault_bias_sign": (full, "f32", -gamma)}

    def steps(batches, precision, speed):
        params = jax.jit(lambda key: ref_mod.init_params(cfg, key))(
            seed_key(seed))
        return ref_mod.run_steps(params, batches,
                                 {**cfg, "bias_update_speed": speed},
                                 cfg["optimizer"], precision)

    ref = steps(full, "f32", gamma)
    for name in readings:
        checks, g1_gap = moe_checks(steps(*runs[name]), ref, 0, tr["limits"],
                                    gamma)
        emit(seed=seed, reading=name, grad1_norm_gap=g1_gap,
             **{c.name: c.value for c in checks})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--readings", default=",".join(READINGS))
    args = ap.parse_args(argv)
    readings = args.readings.split(",")
    if not set(readings) <= set(READINGS):
        ap.error(f"--readings: choose from {READINGS}")
    spec = bench.load_cell(args.workload)
    bench.require_chips(spec["cell"]["chips"])
    bench.prepare_jax()
    for seed in [int(x) for x in args.seeds.split(",")]:
        scratch = common.scratch_dir(bench.ROOT, f"calibrate.{seed}")
        t0 = time.perf_counter()
        try:
            controls(spec, seed, scratch, readings)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
            gc.collect()
        emit(seed=seed, reading="seconds", value=time.perf_counter() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
