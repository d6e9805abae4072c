"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` at the root of the checkout names the cell, the cell
names a configuration (``file``) and a traffic mix
(``benchmarks/chip/traffic/<traffic>.json``), and the traffic mix names
the driver (``benchmarks/chip/drivers/<driver>.py``) that runs it.  Each
per-layer metric is read by ``benchmarks/chip/metrics/<metric>.py``.  A
new cell or metric therefore needs new files and entries only.

A run: set-up (data, weights, warm-up of every shape the window uses;
timed as ``setup_s``), a window of ``--seconds`` in which nothing
compiles, the device's peak memory, then the check that decides
``correct``: the driver compares what the window produced with a plain
reference.  With ``--trace 1`` the last seconds of the window are
traced and the per-layer metrics are printed instead of the end-to-end
ones.  The last line of standard output is the result as one JSON
object; the numbers compared, each beside its limit, are also the last
lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits 4 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import common  # noqa: E402

EXIT_NO_CHIP = 4


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entries and files, as ``BENCHMARK.json`` names them."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "benchmarks/chip/traffic" / f"{cell['traffic']}.json").read_text())

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(n: int):
    """The first ``n`` TPU devices, or exit without a result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found {devs[0].platform!r} devices", file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    if len(devs) < n:
        print(f"{len(devs)} TPU chip(s), the cell needs {n}", file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    return devs


def prepare_jax() -> str:
    """Persistent compilation cache (the program's own placement) with
    every program kept, so that only a checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, need_chip: bool = True,
             overrides: dict | None = None, t_start: float | None = None,
             keep_trace: str | None = None):
    """One run of a cell -> the result dict (the line to print) and the
    list of checks.  ``need_chip=False`` and ``overrides`` (replacing
    configuration or traffic keys) serve the tests, which drive a run on
    the CPU at a small size."""
    t_start = T_START if t_start is None else t_start
    spec = load_cell(name, root)
    for part, values in (overrides or {}).items():
        spec[part] = {**spec[part], **values}
    import jax

    devices = (require_chips(spec["cell"]["chips"]) if need_chip
               else jax.devices())[: spec["cell"]["chips"]]
    cache_dir = prepare_jax()
    print(f"[setup] compile cache {cache_dir}; host cores {os.cpu_count()}; "
          f"device {devices[0].device_kind} x{len(devices)}", flush=True)

    driver = importlib.import_module(f"drivers.{spec['traffic']['driver']}")
    scratch = common.scratch_dir(root, name)
    run = None
    try:
        run = driver.Run(spec["config"], spec["traffic"], seed, devices,
                         scratch)
        with common.CompileCounter() as made:
            run.setup()
        setup_s = time.perf_counter() - t_start
        print(f"[setup] setup_s={setup_s!r}; programs lowered {made.count}, "
              f"backend compile {made.backend_s:.3f} s", flush=True)

        counter = common.CompileCounter()
        tracer = common.Tracer(scratch / "trace") if trace else None
        with counter:
            window = run.window(seconds, tracer)
        window["compiles_in_window"] = counter.count
        window["memory_peak_bytes"] = common.peak_bytes(devices)
        window["setup_s"] = setup_s
        print(f"[window] {common.brief(window)}", flush=True)
        summary = tracer.summary() if tracer else None
        if tracer and keep_trace:
            shutil.copytree(tracer.log_dir, Path(keep_trace) / f"{name}.{seed}",
                            dirs_exist_ok=True)
        run.release()
        gc.collect()
        checks = run.check()
    finally:
        if run is not None:
            run.close()
        shutil.rmtree(scratch, ignore_errors=True)

    ctx = common.Context(spec=spec, window=window, devices=devices,
                         trace=summary)
    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": window[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    if window["compiles_in_window"]:
        print(f"warning: {window['compiles_in_window']} program(s) lowered "
              f"inside the window", file=sys.stderr)
    correct = all(c.ok for c in checks)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": window["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the raw trace of a traced run under DIR")
    args = ap.parse_args(argv)
    result, checks = run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), keep_trace=args.keep_trace)
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
