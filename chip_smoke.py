"""On-chip smoke check of the main path, through the entry points a user calls.

Phases, in order, each printing one line (seconds, compile seconds where it
compiles, and what it checked):

1. device   — the backend must be a TPU; nothing runs on any other platform.
2. ingest   — a seeded synthetic corpus at real width (vocab 49 152,
   log-normal document lengths, >= 20 M tokens) goes through
   ``ingest_corpus`` with 4 producer threads into one file; the write-side
   Pallas kernels (offsets scan, byteshuffle) run on the chip.  Every
   document read back must equal the generator's.
3. decode   — every cluster decodes through ``iter_clusters_device`` on the
   Pallas route; offset and value columns must be bit-identical to the host
   decode (``read_cluster``).
4. train    — ``TrainLoop`` on full-width smollm-360m over ``make_local_mesh()``,
   fed by ``PackedLoader``'s device engine (batch 4 x 2048), runs 10 steps and
   saves a checkpoint at step 10 through the loop's parallel-writer save.
5. restore  — a fresh ``TrainLoop`` on the same directory must restore params
   and optimizer state bit for bit, and its next step must give the same loss
   as the uninterrupted run.

``--chips 4`` runs only the sharded-checkpoint check instead: the train phase
on a ``data=4`` mesh (FSDP-sharded params and optimizer state), a save, and a
restore on a one-chip mesh in the same process; params must match the 4-chip
state gathered to the host bit for bit, and the next-step losses must agree
within ``LOSS_RTOL``.

Usage, from the root of a checkout on a TPU host::

    python chip_smoke.py [--seed 0] [--chips 4]

The last line of standard output is ``{"ok": true, "device": {...}}``; it is
printed only when every phase passed, and the exit code is 0 only then.
Everything runs in this one process: the chip belongs to one process at a
time.  Scratch files (corpus, checkpoints) live in a temporary directory
under ``TMPDIR`` and are removed at exit.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-360m"
VOCAB = 49_152
MEAN_LEN = 512          # synth_corpus's log-normal scale (median length)
MIN_TOKENS = 20_000_000
BATCH, SEQ = 4, 2048
STEPS = 10              # the checkpoint is saved at the last of these
#: next-step loss agreement across layouts: two bf16 ulps, relative
LOSS_RTOL = 2.0 ** -7
#: offsets-scan size floor during ingest: the ingest fills 256-document
#: batches, so the default floor (65 536 sizes) would keep numpy there
INGEST_OFFSETS_MIN = 256


class PhaseError(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseError(what)


def report(name: str, seconds: float, text: str,
           compile_s: float | None = None) -> None:
    comp = f" compile_s={compile_s:.3f}" if compile_s is not None else ""
    print(f"[{name}] seconds={seconds:.3f}{comp} | {text}", flush=True)


# ---------------------------------------------------------------------------
# phases


def phase_device(want_count: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"platform is {d.platform!r}, not 'tpu'")
    check(len(devs) >= want_count,
          f"{len(devs)} device(s), {want_count} needed")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def make_corpus(seed: int, min_tokens: int, vocab: int, mean_len: int):
    """The seeded generator's documents, up to ``min_tokens`` tokens."""
    from repro.pipeline import synth_corpus

    docs, total = [], 0
    for i, toks in synth_corpus(10**9, seed=seed, mean_len=mean_len,
                                vocab=vocab):
        docs.append((i, toks))
        total += len(toks)
        if total >= min_tokens:
            break
    return docs, total


def phase_ingest(path: str, docs, n_workers: int = 4):
    """Parallel ingest with the write-side kernels, then a full read-back."""
    import numpy as np

    from repro.core import RNTJReader
    from repro.core import encoding as E
    from repro.pipeline import ingest_corpus

    scan0, shuf0 = E.OFFSETS_SCAN.calls, E.BYTESHUFFLE.calls
    floor = E.OFFSETS_SCAN.min
    E.OFFSETS_SCAN.min = INGEST_OFFSETS_MIN
    try:
        t0 = time.perf_counter()
        ingest_corpus(iter(docs), path, n_workers=n_workers)
        wall = time.perf_counter() - t0
    finally:
        E.OFFSETS_SCAN.min = floor
    scan_calls = E.OFFSETS_SCAN.calls - scan0
    shuf_calls = E.BYTESHUFFLE.calls - shuf0
    check(scan_calls > 0, "no offsets-scan call went to the kernel")
    check(shuf_calls > 0, "no byteshuffle call went to the kernel")

    lens = np.array([len(t) for _, t in docs], np.int64)
    ref = np.concatenate([t for _, t in docs])
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    seen = np.zeros(len(docs), bool)
    r = RNTJReader(path)
    c_id, c_off, c_val = (r.schema.column_of_path[p]
                          for p in ("doc_id", "tokens", "tokens._0"))
    for ci in range(r.n_clusters):
        cols = r.read_cluster(ci, [c_id, c_off, c_val])
        ids, offs, vals = cols[c_id], cols[c_off], cols[c_val]
        sizes = np.diff(offs, prepend=0)
        check(not seen[ids].any(), f"cluster {ci}: a document appears twice")
        seen[ids] = True
        check(np.array_equal(sizes, lens[ids]),
              f"cluster {ci}: document lengths differ from the generator")
        begin = np.concatenate([[0], offs[:-1]])
        idx = np.repeat(starts[ids] - begin, sizes) + np.arange(len(vals))
        check(np.array_equal(vals, ref[idx]),
              f"cluster {ci}: tokens differ from the generator")
    n_clusters = r.n_clusters
    r.close()
    check(bool(seen.all()), f"{int((~seen).sum())} documents missing")
    size = os.path.getsize(path)
    text = (f"{len(docs)} docs, {int(lens.sum())} tokens (mean length "
            f"{lens.mean():.1f}), {n_workers} producer threads -> 1 file, "
            f"{n_clusters} clusters, {size} bytes; every document read back "
            f"equals the seeded generator's; kernel calls during ingest: "
            f"offsets_scan={scan_calls} byteshuffle={shuf_calls} (offsets "
            f"floor {INGEST_OFFSETS_MIN} sizes during ingest)")
    return wall, size, text


def phase_decode(path: str):
    """Device decode on the Pallas route vs the host decode, every cluster."""
    import jax
    import numpy as np

    from repro.core import RNTJReader, ReadOptions

    dev = RNTJReader(path, options=ReadOptions(device_decode="pallas"))
    host = RNTJReader(path)
    c_off = host.schema.column_of_path["tokens"]
    c_val = host.schema.column_of_path["tokens._0"]
    first_s, n, elems = None, 0, 0
    t0 = time.perf_counter()
    for ci, cols in dev.iter_clusters_device([c_off, c_val]):
        o, v = cols[c_off], cols[c_val]
        check(isinstance(o, jax.Array) and isinstance(v, jax.Array),
              f"cluster {ci}: a column took the host fallback")
        want = host.read_cluster(ci, [c_off, c_val])
        check(np.array_equal(np.asarray(o).astype(np.int64), want[c_off]),
              f"cluster {ci}: device offsets differ from the host decode")
        check(np.array_equal(np.asarray(v), want[c_val]),
              f"cluster {ci}: device tokens differ from the host decode")
        if first_s is None:
            first_s = time.perf_counter() - t0
        n += 1
        elems += int(o.shape[0]) + int(v.shape[0])
    check(n == host.n_clusters, f"decoded {n} of {host.n_clusters} clusters")
    check(decode_kernels_lowered() > 0,
          "the Pallas offsets decode lowered without a Mosaic kernel")
    dev.close()
    host.close()
    text = (f"{n} clusters, {elems} offset+token elements via "
            f"iter_clusters_device(device_decode='pallas', compiled); "
            f"offsets and tokens bit-identical to read_cluster")
    return first_s, text


def decode_kernels_lowered() -> int:
    """Mosaic kernels in the offsets decode function as the reader's Pallas
    route lowers it (interpret mode would lower to plain HLO instead)."""
    import jax
    import numpy as np

    from repro.kernels import decode_pages as dk

    per = 64 * 1024 // 8
    raw = jax.ShapeDtypeStruct((4 * per * 8,), np.uint8)
    return dk.device_decode_offsets.lower(
        raw, n=4 * per, per=per, use_pallas=True).as_text().count(
            "tpu_custom_call")


def _host_state(loop):
    """Params and optimizer state gathered to host numpy, by leaf name."""
    import jax
    import numpy as np

    tree = {"params": loop.params,
            "opt": {"step": loop.opt_state.step, "m": loop.opt_state.m,
                    "v": loop.opt_state.v}}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.array(np.asarray(x), copy=True)
            for p, x in flat}


def _same_bits(a, b) -> bool:
    import numpy as np

    a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8)))


def compare_state(saved, loop) -> int:
    restored = _host_state(loop)
    check(saved.keys() == restored.keys(), "restored tree has other leaves")
    bad = [k for k in saved if not _same_bits(saved[k], restored[k])]
    check(not bad, f"restored leaves differ: {bad[:5]}")
    return sum(a.nbytes for a in saved.values())


def attention_in_step(bundle, mesh) -> str:
    """Which attention the train step compiles: counts Pallas kernels in
    the lowered step (the same step function ``TrainLoop`` jits)."""
    import jax
    import jax.numpy as jnp

    from repro.train.step import make_train_step
    from repro.train.optimizer import make_optimizer

    opt = make_optimizer()
    jitted_for, _ = make_train_step(bundle, mesh, optimizer=opt)
    params = bundle.param_shapes()
    opt_state = jax.eval_shape(opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
             for k in ("tokens", "labels")}
    err = jax.ShapeDtypeStruct((), jnp.float32)
    hlo = jitted_for(batch).lower(params, opt_state, err, batch).as_text()
    n = hlo.count("tpu_custom_call")
    kind = "Pallas flash kernel" if n else "XLA attention (ops.flash_attention auto rule)"
    return f"{kind}; {n} Pallas kernel(s) in the lowered train step"


def make_loop(path: str, ckpt_dir: str, mesh, bundle):
    from repro.pipeline import PackedLoader
    from repro.train import LoopConfig, TrainLoop, make_optimizer

    loader = PackedLoader(path, batch=BATCH, seq_len=SEQ, device="device")
    return TrainLoop(
        bundle, mesh, loader, ckpt_dir,
        config=LoopConfig(steps=STEPS, ckpt_every=STEPS, log_every=10**9),
        optimizer=make_optimizer(peak_lr=3e-4, warmup=5, total=100),
    )


def phase_train(path: str, ckpt_dir: str, mesh, bundle):
    import numpy as np

    loop = make_loop(path, ckpt_dir, mesh, bundle)
    check(loop.step == 0, "the checkpoint directory was not empty")
    t0 = time.perf_counter()
    hist = loop.run()
    wall = time.perf_counter() - t0
    losses = [h.loss for h in hist]
    check(len(losses) == STEPS, f"{len(losses)} steps ran, {STEPS} asked")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(loop.mgr.steps() == [STEPS], f"checkpoints: {loop.mgr.steps()}")
    saved = _host_state(loop)
    nxt = loop.run(1)[-1].loss
    check(np.isfinite(nxt), "non-finite loss after the checkpoint")
    steady = float(np.median([h.wall_s for h in hist[1:]]))
    return loop, saved, nxt, wall, hist[0].wall_s, steady, losses


def phase_restore(path: str, ckpt_dir: str, mesh, bundle, saved):
    t0 = time.perf_counter()
    loop = make_loop(path, ckpt_dir, mesh, bundle)
    restore_s = time.perf_counter() - t0
    check(loop.step == STEPS, f"restored step {loop.step}, not {STEPS}")
    nbytes = compare_state(saved, loop)
    loss = loop.run(1)[-1].loss
    return loop, loss, restore_s, nbytes


# ---------------------------------------------------------------------------
# runs


def run_one_chip(args, work: Path, device) -> None:
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import describe, make_local_mesh
    from repro.models.registry import build

    path = str(work / "corpus.rntj")
    t0 = time.perf_counter()
    docs, total = make_corpus(args.seed, MIN_TOKENS, VOCAB, MEAN_LEN)
    gen_s = time.perf_counter() - t0
    wall, size, text = phase_ingest(path, docs)
    report("ingest", wall, text + f"; corpus generated in {gen_s:.3f} s")
    print(f"[ingest] smoke number, not a benchmark: "
          f"{size / wall / 1e6:.3f} MB/s written (file bytes / ingest wall s)",
          flush=True)
    del docs
    gc.collect()

    t0 = time.perf_counter()
    first_s, text = phase_decode(path)
    report("decode", time.perf_counter() - t0, text, compile_s=first_s)

    bundle = build(get_arch(ARCH))
    mesh = make_local_mesh()
    ckpt = str(work / "ckpt")
    attn = attention_in_step(bundle, mesh)
    loop, saved, nxt, wall, first, steady, losses = phase_train(
        path, ckpt, mesh, bundle)
    report("train", wall,
           f"{ARCH} full width ({bundle.cfg.n_layers} layers, d_model "
           f"{bundle.cfg.d_model}), mesh {describe(mesh)}, batch {BATCH}x{SEQ} "
           f"from PackedLoader(device), {STEPS} steps, losses "
           f"{[round(x, 4) for x in losses]} all finite, steady step "
           f"{steady:.3f} s; attention: {attn}; checkpoint saved at step "
           f"{STEPS} by the loop's parallel-writer save",
           compile_s=first - steady)
    del loop
    gc.collect()

    loop, loss, restore_s, nbytes = phase_restore(path, ckpt, mesh, bundle,
                                                  saved)
    check(loss == nxt, f"next-step loss {loss!r} after restore, "
                       f"{nxt!r} uninterrupted")
    report("restore", restore_s,
           f"fresh TrainLoop restored step {STEPS}: params + optimizer "
           f"state bit-identical ({nbytes} bytes); next-step loss {loss!r} "
           f"== uninterrupted {nxt!r}")
    del loop


def run_four_chips(args, work: Path, device) -> None:
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import describe, make_local_mesh
    from repro.models.registry import build
    from repro.pipeline import PackedLoader, ingest_corpus

    path = str(work / "corpus.rntj")
    docs, _ = make_corpus(args.seed, 2_000_000, VOCAB, MEAN_LEN)
    ingest_corpus(iter(docs), path, n_workers=4)   # set-up, not a phase
    del docs

    bundle = build(get_arch(ARCH))
    mesh4 = make_local_mesh(devices=jax.devices()[:4])
    ckpt = str(work / "ckpt")
    loop4, saved, loss4, wall, first, steady, losses = phase_train(
        path, ckpt, mesh4, bundle)
    probe = PackedLoader(path, batch=BATCH, seq_len=SEQ, device="device")
    placed = loop4.place(next(probe.batches()))
    shards = placed["tokens"].addressable_shards
    check(len({s.device for s in shards}) == 4
          and all(s.data.shape == (BATCH // 4, SEQ) for s in shards),
          "loader batches are not split over the 4-device batch sharding")
    sharded = sum(x.sharding.shard_shape(x.shape) != x.shape
                  for x in jax.tree_util.tree_leaves(
                      (loop4.params, loop4.opt_state)))
    check(sharded > 0, "no param or optimizer leaf is sharded")
    report("train4", wall,
           f"{ARCH} full width, mesh {describe(mesh4)}, {sharded} of "
           f"{len(saved)} param/optimizer leaves FSDP-sharded, batch "
           f"{BATCH}x{SEQ} placed as {len(shards)} shards of "
           f"{shards[0].data.shape} on 4 devices, {STEPS} steps, losses "
           f"{[round(x, 4) for x in losses]} all finite, steady step "
           f"{steady:.3f} s; checkpoint saved at step {STEPS}",
           compile_s=first - steady)
    del loop4, placed, shards
    probe.close()
    gc.collect()

    mesh1 = make_local_mesh(devices=jax.devices()[:1])
    loop1, loss1, restore_s, nbytes = phase_restore(path, ckpt, mesh1,
                                                    bundle, saved)
    tol = LOSS_RTOL * abs(loss4)
    check(abs(loss1 - loss4) <= tol,
          f"next-step loss {loss1!r} on 1 chip vs {loss4!r} on 4")
    report("restore1", restore_s,
           f"fresh TrainLoop on mesh {describe(mesh1)} restored step {STEPS} "
           f"of the data=4 save: params + optimizer state bit-identical to "
           f"the 4-chip state gathered to host ({nbytes} bytes); next-step "
           f"loss {loss1!r} (1 chip) vs {loss4!r} (4 chips), |diff| "
           f"{abs(loss1 - loss4):.3g} <= {tol:.3g} (rtol 2**-7)")
    del loop1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    t0 = time.perf_counter()
    try:
        device = phase_device(args.chips)
    except PhaseError as e:
        print(f"[device] FAILED: {e}", file=sys.stderr, flush=True)
        return 2
    report("device", time.perf_counter() - t0,
           f"platform {device['platform']}, kind {device['kind']}, "
           f"count {device['count']}; compile cache {cache}")
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
            if args.chips == 4:
                run_four_chips(args, Path(work), device)
            else:
                run_one_chip(args, Path(work), device)
    except PhaseError as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
