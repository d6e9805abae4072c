"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run).  For each seed, in one process on the chip:

* the program's sound readings: a cell's set-up (for a train cell, its
  checked first steps) and, for loader and write cells, a short window,
  then the cell's own check;
* with ``--control``: the control, the plain reference computed one
  precision below what the configuration states and put in the
  program's place, compared in the same way.  Train: matmuls in float8
  e4m3 against the float32 reference.  Loader: the reference packing
  with token ids held in 16 bits.  Write: values rounded to bfloat16
  before they are filled;
* for a train cell with ``--control``, also the fault "half of the batch
  left out, the mean taken over the rest", planted in the reference put
  in the program's place.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control] [--seconds 5]

Prints one JSON line per seed and reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench  # noqa: E402
import common  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def train_seed(Run, spec, seed, devices, scratch, control: bool) -> None:
    import jax

    from drivers import corpus as corpus_mod
    from drivers.train import compare, seed_key

    r = Run(spec["config"], spec["traffic"], seed, devices, scratch)
    try:
        r.setup()
        r.release()
        checks = r.check()
        emit(seed=seed, reading="program",
             **{c.name: c.value for c in checks})
        if not control:
            return
        cfg, tr = spec["config"], spec["traffic"]
        b, s, k = tr["batch"], tr["seq_len"], tr["checked_steps"]
        grids = [corpus_mod.batch_grid(r.stream, i, b, s) for i in range(k)]
        full = [(g[:, :-1], g[:, 1:]) for g in grids]
        half = [(g[: b // 2, :-1], g[: b // 2, 1:]) for g in grids]

        def steps(batches, precision):
            params = jax.jit(lambda key: r.ref.init_params(cfg, key))(
                seed_key(seed))
            out = r.ref.run_steps(params, batches, cfg, cfg["optimizer"],
                                  precision)
            del params
            return out

        ref = steps(full, "f32")
        for name, batches, prec in (("control_fp8", full, "fp8"),
                                    ("fault_half_batch", half, "f32")):
            got = steps(batches, prec)
            checks = compare(*got, *ref, 0, tr["limits"])
            emit(seed=seed, reading=name, **{c.name: c.value for c in checks})
    finally:
        r.close()


def loader_seed(Run, spec, seed, devices, scratch, seconds, control) -> None:
    import jax

    from drivers import corpus as corpus_mod
    from drivers.loader import host_checksum

    r = Run(spec["config"], spec["traffic"], seed, devices, scratch)
    try:
        r.setup()
        w = r.window(seconds, None)
        r.release()
        checks = r.check()
        emit(seed=seed, reading="program", batches=w["batches"],
             **{c.name: c.value for c in checks})
        if control:
            tr = spec["traffic"]
            low = r.stream.astype(np.int16).astype(np.int32)
            acc = jax.device_put(np.uint32(0), devices[0])
            for k in range(r.drawn):
                g = corpus_mod.batch_grid(low, k, tr["batch"], tr["seq_len"])
                acc = r.fold(acc, g[:, :-1], g[:, 1:], r.w)
            want = host_checksum(r.stream, r.drawn, tr["batch"], tr["seq_len"],
                                 r.w_host)
            emit(seed=seed, reading="control_int16_tokens",
                 checksum_mismatch=int(int(np.asarray(acc)) != want),
                 batches=r.drawn)
    finally:
        r.close()


def write_seed(Run, spec, seed, devices, scratch, seconds, control) -> None:
    r = Run(spec["config"], spec["traffic"], seed, devices, scratch)
    try:
        r.setup()
        if control:
            for pool, batches in zip(r.pools, r.batches):
                c_val = r.schema.column_of_path["vals._0"]
                for b in batches:
                    # the generator's values stay as they were for the check
                    b.data[c_val] = b.data[c_val].copy()
                    bits = b.data[c_val].view(np.uint32)
                    bits &= np.uint32(0xFFFF0000)   # truncated to bfloat16
        w = r.window(seconds, None)
        r.release()
        checks = r.check()
        emit(seed=seed, reading="control_bf16_values" if control else "program",
             mb_per_s=w["write_mb_per_s"], **{c.name: c.value for c in checks})
    finally:
        r.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    import importlib
    import shutil

    spec = bench.load_cell(args.workload)
    devices = bench.require_chips(spec["cell"]["chips"])[: spec["cell"]["chips"]]
    bench.prepare_jax()
    kind = spec["traffic"]["driver"]
    Run = importlib.import_module(f"drivers.{kind}").Run
    for seed in [int(x) for x in args.seeds.split(",")]:
        scratch = common.scratch_dir(bench.ROOT, f"calibrate.{seed}")
        t0 = time.perf_counter()
        try:
            if kind == "train":
                train_seed(Run, spec, seed, devices, scratch, args.control)
            elif kind == "loader":
                loader_seed(Run, spec, seed, devices, scratch, args.seconds,
                            args.control)
            else:
                write_seed(Run, spec, seed, devices, scratch, args.seconds,
                           args.control)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
            gc.collect()
        emit(seed=seed, reading="seconds", value=time.perf_counter() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
