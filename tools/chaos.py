"""Chaos driver: run the write path under injected storage faults.

Each scenario builds a file through a :class:`FaultInjectingSink` and
asserts the robustness contract that DESIGN.md §8 promises for it:

* ``transient``     — scripted EIO/EAGAIN bursts + a torn (short) write:
                      the run completes, retry counters are nonzero, and
                      the file reads back with zero loss.
* ``seeded``        — seeded random transient errors at an error rate:
                      same seed → same fault schedule; zero loss.
* ``enospc``        — persistent ENOSPC on an offset window: retries
                      exhaust, the writer poisons, close() raises, and a
                      second close() is a safe no-op.
* ``fsync``         — transient then permanent fsync failure: the former
                      is retried, the latter poisons (never swallowed).
* ``stripe``        — a non-retryable stripe error: the engine rewrites
                      the extent monolithically and disables striping.
* ``writebehind``   — write-behind (the submission ring) under transient
                      faults: completes with zero loss.
* ``latency``       — injected latency spikes: slow but lossless.
* ``kill``          — a matrix of process-kill points across the file:
                      each torn file is salvaged by ``recover_container``
                      and every salvaged entry reads back byte-identical.
* ``skim``          — selective (zone-map pruned) filtered reads under
                      transient pread faults (retried, results equal the
                      clean run) and after a kill+recover (zone maps
                      dropped with a reason, filtered results exact).
* ``remote``        — the object-store sink cell matrix (DESIGN.md §10):
                      clean multipart byte-identity, transient transport
                      faults retried, seeded random faults, torn ranged
                      GETs, hedged slow tails, multipart→serial-put
                      degradation, and a writer killed mid-multipart
                      whose interrupted upload ``recover_container``
                      salvages back into a readable object.

Run:
    python tools/chaos.py                      # all scenarios
    python tools/chaos.py --scenario kill      # one scenario
    python tools/chaos.py --seed 3 --entries 2000

Exit status: 0 when every scenario holds its invariant, 1 otherwise.
"""

from __future__ import annotations

import argparse
import errno
import multiprocessing
import os
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT / "src") not in sys.path:
    sys.path.insert(0, str(REPO_ROOT / "src"))

import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    Collection,
    F,
    FaultInjectingSink,
    FaultSpec,
    FencedError,
    Leaf,
    MemorySink,
    MultiWriterCoordinator,
    ParallelWriter,
    ProcessKilled,
    RNTJReader,
    RetryPolicy,
    Schema,
    SequentialWriter,
    WriteOptions,
    join_container,
    open_sink,
    recover_container,
    RecoveryError,
)
from repro.core import FaultSchedule, ReadOptions  # noqa: E402
from repro.core.faults import crashed_file_bytes, memory_sink_from_bytes  # noqa: E402
from repro.core.remote import (  # noqa: E402
    FakeTransport,
    ObjectBucket,
    ObjectStoreSink,
    RemoteOptions,
    salvage_remote,
)

SCHEMA = Schema([
    Leaf("id", "int64"),
    Collection("vals", Leaf("_0", "float32")),
])

# fast deterministic backoff so chaos runs stay quick
POLICY = RetryPolicy(max_attempts=8, backoff_base=0.0002, backoff_cap=0.002)


def make_entries(n: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 6, size=n)
    return [
        {"id": int(i), "vals": [float(v) for v in rng.random(lens[i],
                                                             dtype=np.float32)]}
        for i in range(n)
    ]


def write_through(sink, entries, **opt_kw):
    opts = WriteOptions(cluster_bytes=opt_kw.pop("cluster_bytes", 8192),
                        retry_policy=POLICY, **opt_kw)
    w = SequentialWriter(SCHEMA, sink, opts)
    for e in entries:
        w.fill(e)
    w.close()
    return w


def verify_lossless(inner_sink, entries, label):
    r = RNTJReader(inner_sink)
    got = list(r.iter_entries())
    r.close()
    assert len(got) == len(entries), (
        f"{label}: {len(got)} of {len(entries)} entries read back")
    assert got == entries, f"{label}: entries differ after faults"


# -- scenarios ---------------------------------------------------------------


def scenario_transient(entries, seed):
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec.transient_error(count=3),
        FaultSpec.transient_error(err=errno.EAGAIN, count=2, at_call=11),
        FaultSpec.short_write(at_call=6),
    ])
    w = write_through(fs, entries)
    d = w.stats.as_dict()
    assert d["io_retries"] >= 5, f"retries not counted: {d['io_retries']}"
    assert d["io_giveups"] == 0
    verify_lossless(fs.inner, entries, "transient")
    return {"retries": d["io_retries"], "injected": fs.faults.injected}


def scenario_seeded(entries, seed):
    # a 10% per-call rate needs enough write calls to fire with near
    # certainty — pad tiny --entries workloads deterministically
    if len(entries) < 2000:
        entries = entries + make_entries(2000 - len(entries), seed + 1)
    fs = FaultInjectingSink(MemorySink(), seed=seed, error_rate=0.1)
    w = write_through(fs, entries, cluster_bytes=2048)
    d = w.stats.as_dict()
    assert fs.faults.random_errors >= 1, "seeded schedule injected nothing"
    assert d["io_retries"] >= fs.faults.random_errors
    verify_lossless(fs.inner, entries, "seeded")
    return {"retries": d["io_retries"],
            "injected": fs.faults.random_errors}


def scenario_enospc(entries, seed):
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec(op="write", kind="error", err=errno.ENOSPC, count=-1,
                  at_offset=(4096, 1 << 62)),
    ])
    w = SequentialWriter(SCHEMA, fs, WriteOptions(cluster_bytes=2048,
                                                  retry_policy=POLICY))
    poisoned = False
    try:
        for e in entries:
            w.fill(e)
        w.close()
    except (OSError, RuntimeError):
        poisoned = True
    assert poisoned, "persistent ENOSPC did not fail the writer"
    try:
        w.close()  # the first close after a poisoned commit surfaces it
    except (OSError, RuntimeError):
        pass
    w.close()      # ... and any further close is a safe no-op (§8.2)
    d = w.stats.as_dict()
    assert d["io_giveups"] >= 1, "exhausted retries not counted as giveup"
    return {"giveups": d["io_giveups"], "retries": d["io_retries"]}


def scenario_fsync(entries, seed):
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.fsync_error(count=2)])
    w = write_through(fs, entries, fsync_policy="every_cluster")
    assert w.stats.as_dict()["io_retries"] >= 2
    verify_lossless(fs.inner, entries, "fsync-transient")

    fs = FaultInjectingSink(MemorySink(), [FaultSpec.fsync_error(count=-1)])
    w = SequentialWriter(SCHEMA, fs, WriteOptions(
        cluster_bytes=8192, retry_policy=POLICY,
        fsync_policy="every_cluster"))
    poisoned = False
    try:
        for e in entries:
            w.fill(e)
        w.close()
    except (OSError, RuntimeError):
        poisoned = True
    try:
        w.close()
    except (OSError, RuntimeError):
        pass
    assert poisoned, "permanent fsync failure was swallowed"
    assert w.stats.as_dict()["io_fsync_failures"] >= 1
    return {"fsync_failures": w.stats.as_dict()["io_fsync_failures"]}


def scenario_stripe(entries, seed):
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec.transient_error(err=errno.EBADF, at_call=4, count=1),
    ])
    w = write_through(fs, entries, cluster_bytes=16384,
                      io_stripe_bytes=2048, io_workers=2)
    d = w.stats.as_dict()
    assert d["io_stripe_fallbacks"] >= 1, "stripe failure did not degrade"
    verify_lossless(fs.inner, entries, "stripe")
    return {"stripe_fallbacks": d["io_stripe_fallbacks"]}


def scenario_writebehind(entries, seed):
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec.transient_error(count=4),
    ])
    opts = WriteOptions(cluster_bytes=4096, retry_policy=POLICY,
                        io_inflight_bytes=1 << 20)
    w = ParallelWriter(SCHEMA, fs, opts)
    ctx = w.create_fill_context()
    for e in entries:
        ctx.fill(e)
    ctx.close()
    w.close()
    d = w.stats.as_dict()
    assert d["io_retries"] >= 1
    verify_lossless(fs.inner, entries, "writebehind")
    return {"retries": d["io_retries"]}


def scenario_latency(entries, seed):
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec.latency(0.002, count=5),
    ])
    write_through(fs, entries)
    assert fs.faults.latencies == 5
    verify_lossless(fs.inner, entries, "latency")
    return {"latencies": fs.faults.latencies}


def scenario_kill(entries, seed):
    # reference file: the same workload written cleanly
    ref = MemorySink()
    write_through(ref, entries, cluster_bytes=2048)
    size = ref.size
    kills = [int(k) for k in np.linspace(200, size + 64, 12)]
    salvaged_total = 0
    results = []
    for K in kills:
        fs = FaultInjectingSink(MemorySink(), [FaultSpec.kill_at(K)])
        try:
            write_through(fs, entries, cluster_bytes=2048)
            crashed = False
        except (ProcessKilled, OSError, RuntimeError):
            crashed = True
        ms = memory_sink_from_bytes(crashed_file_bytes(fs))
        try:
            rep = recover_container(ms)
        except RecoveryError:
            assert K < 1024, f"header-only loss expected near 0, not K={K}"
            results.append((K, "unrecoverable"))
            continue
        r = RNTJReader(ms)
        got = list(r.iter_entries())
        r.close()
        assert got == entries[: len(got)], (
            f"K={K}: salvaged entries not byte-identical")
        if not crashed:
            assert len(got) == len(entries)
        salvaged_total += len(got)
        results.append((K, len(got)))
    return {"kill_points": len(kills), "salvage": results}


def scenario_skim(entries, seed):
    """Selective (zone-map pruned) reads under the §8.2 fault schedule.

    Three invariants: (a) a pruned filtered read through a faulty sink
    equals the clean pruned read (transient pread errors retried, not
    surfaced); (b) pruned ≡ unpruned on the same faulty sink; (c) a file
    torn by a mid-write kill recovers with its zone maps DROPPED (the
    journal cannot attest them) and the filtered read over the salvaged
    file is exact — stale bounds are never served.
    """
    pred = (F("vals._0") > 0.8) | (F("id") < 10)

    def filtered(sink, prune, retry=None):
        r = RNTJReader(sink, options=ReadOptions(filter=pred, prune=prune,
                                                 retry_policy=retry))
        try:
            return list(r.iter_filtered_entries()), r.stats
        finally:
            r.close()

    clean = MemorySink()
    write_through(clean, entries, cluster_bytes=2048,
                  page_size=512, codec="none")
    ref, ref_stats = filtered(clean, prune=True)
    full, _ = filtered(clean, prune=False)
    assert ref == full, "pruned filtered read differs from full scan"

    # (a)+(b) transient pread faults under the reader's retry policy
    fs = FaultInjectingSink(memory_sink_from_bytes(bytes(clean.buf[:clean.size])), [
        FaultSpec.transient_error(count=3, op="read"),
        FaultSpec.transient_error(err=errno.EAGAIN, count=2, op="read",
                                  at_call=5),
    ])
    got, stats = filtered(fs, prune=True, retry=POLICY)
    assert got == ref, "faulty-sink pruned read differs from clean read"
    assert stats.retries >= 1, "transient pread faults were not retried"

    # (c) kill mid-write, recover, re-filter: zone maps dropped, results exact
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.kill_at(clean.size // 2)])
    try:
        write_through(fs, entries, cluster_bytes=2048,
                      page_size=512, codec="none")
    except (ProcessKilled, OSError, RuntimeError):
        pass
    ms = memory_sink_from_bytes(crashed_file_bytes(fs))
    rep = recover_container(ms)
    assert rep.rebuilt, "kill point did not tear the footer"
    assert rep.zonemaps is not None and not rep.zonemaps["preserved"], (
        "recovery must drop unattested zone maps with a reason")
    pruned, _ = filtered(ms, prune=True)
    unpruned, _ = filtered(ms, prune=False)
    assert pruned == unpruned, "salvaged file: pruned differs from full scan"
    n_match = len(pruned)
    return {"matched": len(ref), "retries": stats.retries,
            "salvaged_matches": n_match}


# -- multi-process crash matrix (DESIGN.md §8.6) -----------------------------

# WriteOptions for every mp cell: tiny clusters, fast leases, no side-car
# fsync (the matrix kills processes, not the kernel)
def _mp_options():
    return WriteOptions(cluster_bytes=2048, retry_policy=POLICY,
                        lease_interval=0.3, rendezvous_timeout=5.0,
                        mpw_log_fsync=False)


def _mp_fault_specs(fault: str, point: int):
    if fault == "eio":
        return [FaultSpec.transient_error(count=3)]
    if fault == "torn":
        return [FaultSpec.short_write(at_call=3)]
    if fault == "enospc":  # a persistent wall at this writer's Nth byte
        return [FaultSpec(op="write", kind="error", err=errno.ENOSPC,
                          count=-1, at_byte=point)]
    if fault == "fsync":
        return [FaultSpec.fsync_error(count=-1)]
    if fault == "kill":
        return [FaultSpec.kill_at(point)]
    return []


def _mp_chaos_worker(path, entries, fault, point):
    """Forked child: join the shared container with an injected fault.

    Exit codes: 0 clean DONE; 2 poisoned (fault surfaced, no DONE);
    3 process-killed mid-write; 4 fenced straggler correctly refused;
    5 fencing VIOLATED (a fenced writer's commit went through).
    """
    fs = FaultInjectingSink(open_sink(path, create=False),
                            _mp_fault_specs(fault, point))
    try:
        w = join_container(path, schema=SCHEMA, options=_mp_options(), sink=fs)
        ctx = w.create_fill_context()
        if fault == "straggler":
            half = len(entries) // 2
            for e in entries[:half]:
                ctx.fill(e)
            ctx.flush_cluster()
            time.sleep(point)  # sleep past the rendezvous deadline
            try:
                for e in entries[half:]:
                    ctx.fill(e)
                ctx.flush_cluster()
                os._exit(5)  # must be unreachable: we were fenced
            except (FencedError, RuntimeError, OSError):
                os._exit(4)
        for e in entries:
            ctx.fill(e)
        ctx.close()
        w.close()
    except ProcessKilled:
        os._exit(3)
    except (OSError, RuntimeError):
        os._exit(2)
    os._exit(0)


def _mp_run_cell(entries, n_writers, fault, point, rendezvous_timeout=None):
    """One matrix cell: N forked writers over one container; returns
    (salvaged entries in file order, per-writer slices, exitcodes, report,
    container path, tmpdir handle).  ``fault`` is one kind for every
    writer, or a per-writer list."""
    tmp = tempfile.TemporaryDirectory(prefix="rntj-chaos-")
    path = os.path.join(tmp.name, "mp.rntj")
    opts = _mp_options()
    chunk = (len(entries) + n_writers - 1) // n_writers
    slices = [entries[w * chunk: (w + 1) * chunk] for w in range(n_writers)]
    faults = fault if isinstance(fault, list) else [fault] * n_writers
    ctx = multiprocessing.get_context("fork")
    coord = MultiWriterCoordinator(SCHEMA, path, opts)
    procs = [ctx.Process(target=_mp_chaos_worker,
                         args=(path, slices[w], faults[w], point))
             for w in range(n_writers)]
    for p in procs:
        p.start()
    report = coord.seal(expect_writers=n_writers,
                        timeout=rendezvous_timeout)
    coord.close()
    for p in procs:
        p.join()
    exitcodes = [p.exitcode for p in procs]
    r = RNTJReader(path)
    got = list(r.iter_entries())
    r.close()
    return got, slices, exitcodes, report, path, tmp


def _mp_check_cell(got, slices, exitcodes, label):
    """The salvage contract for one cell: every clean writer's entries are
    all present; a crashed writer's surviving entries are a prefix of what
    it wrote; every salvaged entry is byte-identical to its source."""
    by_id = {e["id"]: e for s in slices for e in s}
    for e in got:
        assert e == by_id[e["id"]], f"{label}: salvaged entry differs"
    ids = [e["id"] for e in got]
    assert len(ids) == len(set(ids)), f"{label}: duplicate salvaged entries"
    for w, s in enumerate(slices):
        mine = [e for e in got if e["id"] in {x["id"] for x in s}]
        if exitcodes[w] == 0:
            assert mine == s, (
                f"{label}: clean writer {w} lost "
                f"{len(s) - len(mine)} of {len(s)} entries")
        else:
            assert mine == s[: len(mine)], (
                f"{label}: writer {w} salvage is not a prefix of its commits")
    # byte-level check: the salvaged set re-written single-writer must
    # decode identically (same codec path, same framing semantics)
    ref = MemorySink()
    write_through(ref, got, cluster_bytes=2048)
    rr = RNTJReader(ref)
    assert list(rr.iter_entries()) == got, (
        f"{label}: salvaged decode differs from single-writer reference")
    rr.close()


def scenario_mpkill(entries, seed):
    """N-process × kill-point × fault-type crash matrix through real
    multiprocessing workers sharing one container file."""
    cells = []
    for n in (2, 4):
        for fault in ("eio", "torn", "fsync"):
            cells.append((n, fault, 0))
        # points straddle the commit stream: before the first cluster
        # lands (total loss), mid-stream (partial salvage), past the end
        # (no fault fires — clean)
        for fault in ("enospc", "kill"):
            for point in (900, 1400, 3000):
                cells.append((n, fault, point))
    results = []
    for n, fault, point in cells:
        label = f"mpkill[N={n},{fault},@{point}]"
        got, slices, codes, report, path, tmp = _mp_run_cell(
            entries[: 160 * n], n, fault, point)
        with tmp:
            _mp_check_cell(got, slices, codes, label)
            if fault in ("eio", "torn"):  # retried to success: zero loss
                assert codes == [0] * n, f"{label}: {codes}"
                assert not report["fenced"], f"{label}: {report}"
            if fault == "fsync":  # fsync poison: DONE withheld, fenced
                assert all(c != 0 for c in codes), f"{label}: {codes}"
                assert len(report["fenced"]) == n, f"{label}: {report}"
            # a degraded seal keeps the side-car; cross-check recovery's
            # view of the sealed file (footer must already be valid)
            rep = recover_container(path, dry_run=True)
            assert rep.footer_valid, f"{label}: sealed footer invalid"
        results.append((f"N={n}", fault, point, len(got),
                        {"codes": codes, "fenced": report["fenced"]}))

    # fencing invariant: a straggler fenced mid-rendezvous can never
    # corrupt what the seal committed
    n = 2
    got, slices, codes, report, path, tmp = _mp_run_cell(
        entries[:320], n, ["none", "straggler"], 3, rendezvous_timeout=1.0)
    with tmp:
        sealed = got
        assert codes[0] == 0 and codes[1] == 4, (
            f"straggler: exit codes {codes} (4 = fenced write refused)")
        assert len(report["fenced"]) == 1, f"straggler: {report}"
        r = RNTJReader(path)   # re-read AFTER the straggler's late attempt
        assert list(r.iter_entries()) == sealed, (
            "straggler: sealed entries changed after a fenced write")
        r.close()
        rep = recover_container(path, dry_run=True)
        assert rep.footer_valid, "straggler: footer damaged by fenced writer"
    results.append(("N=2", "straggler", 3, len(sealed),
                    {"codes": codes, "fenced": report["fenced"]}))
    return {"cells": len(results), "matrix": results}


def scenario_mprecover(entries, seed):
    """Coordinator dies mid-rendezvous (no footer): recover_container
    rebuilds the file from the journal + side-car log alone."""
    tmp = tempfile.TemporaryDirectory(prefix="rntj-chaos-")
    with tmp:
        path = os.path.join(tmp.name, "mp.rntj")
        opts = _mp_options()
        n = 2
        chunk = (len(entries) + n - 1) // n
        slices = [entries[w * chunk: (w + 1) * chunk] for w in range(n)]
        ctx = multiprocessing.get_context("fork")
        coord = MultiWriterCoordinator(SCHEMA, path, opts)
        procs = [ctx.Process(target=_mp_chaos_worker,
                             args=(path, slices[w], "none", 0))
                 for w in range(n)]
        for p in procs:
            p.start()
        for p in procs:
            p.join()
        assert [p.exitcode for p in procs] == [0, 0]
        # coordinator "crashes" here: no seal, no footer — just drop it
        coord.sink.close()
        coord.log.close()
        rep = recover_container(path)
        assert not rep.footer_valid, "unsealed file cannot have a footer"
        assert rep.multiwriter is not None, "side-car state not consulted"
        r = RNTJReader(path)
        got = list(r.iter_entries())
        r.close()
        assert sorted(e["id"] for e in got) == sorted(
            e["id"] for s in slices for e in s), "recovery lost entries"
        by_id = {e["id"]: e for s in slices for e in s}
        assert all(e == by_id[e["id"]] for e in got), "recovered entry differs"
        return {"writers": n, "recovered": len(got),
                "clusters": rep.clusters_salvaged}


def scenario_remote(entries, seed):
    """The object-store cell matrix: every remote failure mode in one run."""
    ROPTS = RemoteOptions(part_bytes=1024, retry_policy=POLICY)

    def remote_write(transport, entries, **kw):
        s = ObjectStoreSink(transport, "chaos.rntj", ROPTS)
        return s, write_through(s, entries, **kw)

    def remote_verify(bucket, entries, label):
        verify_lossless(
            ObjectStoreSink(FakeTransport(bucket), "chaos.rntj",
                            create=False),
            entries, label)

    info = {}

    # cell: clean multipart is byte-identical to the local reference
    ms = MemorySink()
    write_through(ms, entries)
    ref = bytes(ms.buf[: ms.size])
    ms.close()
    t = FakeTransport(ObjectBucket())
    s, w = remote_write(t, entries)
    s.close()
    assert t.bucket.objects["chaos.rntj"] == ref, "remote bytes differ"
    assert w.stats.as_dict()["io_retries"] == 0
    info["object_bytes"] = len(ref)

    # cell: scripted transient part/put faults are retried, zero loss
    sched = FaultSchedule([
        FaultSpec.transient_error(op="part", count=3),
        FaultSpec(op="part", kind="short", count=1, fraction=0.5),
    ])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    s, w = remote_write(t, entries)
    s.close()
    d = w.stats.as_dict()
    assert d["io_retries"] >= 4, f"transport retries: {d['io_retries']}"
    assert d["io_degradations"] == 0
    assert t.bucket.objects["chaos.rntj"] == ref
    info["transient_retries"] = d["io_retries"]

    # cell: seeded random transport faults — same seed, same schedule.
    # Transport ops are per-part (far fewer than per-pwrite), so a tiny
    # --entries workload is padded and the rate is high enough that the
    # schedule fires for any seed with near certainty.
    seeded_entries = entries
    if len(seeded_entries) < 2000:
        seeded_entries = entries + make_entries(2000 - len(entries),
                                                seed + 1)
    sched = FaultSchedule(seed=seed, error_rate=0.35,
                          errnos=(errno.EIO, errno.ETIMEDOUT),
                          random_ops=("put", "part", "get"))
    t = FakeTransport(ObjectBucket(), schedule=sched)
    s, w = remote_write(t, seeded_entries)
    s.close()
    d = w.stats.as_dict()
    assert sched.stats.random_errors >= 1, "seeded schedule injected nothing"
    assert d["io_retries"] + d["io_degradations"] >= 1
    remote_verify(t.bucket, seeded_entries, "remote-seeded")
    info["seeded_injected"] = sched.stats.random_errors

    # cell: torn ranged GETs + reader-level retry policy
    sched = FaultSchedule([
        FaultSpec.short_read(op="get", count=2, fraction=0.5),
        FaultSpec.transient_error(op="get", count=2),
    ])
    bkt = ObjectBucket()
    bkt.objects["chaos.rntj"] = ref
    rs = ObjectStoreSink(FakeTransport(bkt, schedule=sched), "chaos.rntj",
                         RemoteOptions(retry_policy=POLICY), create=False)
    r = RNTJReader(rs, options=ReadOptions(retry_policy=POLICY))
    got = list(r.iter_entries())
    r.close()
    assert got == entries, "torn/faulty GETs lost entries"
    d = r.stats.as_dict()
    assert d["io_retries"] >= 2, "transport-level read retries not counted"
    info["read_retries"] = d["io_retries"]

    # cell: hedged slow tail — scripted latency on the first GET only
    sched = FaultSchedule([FaultSpec.latency(0.2, op="get", count=1)])
    bkt = ObjectBucket()
    bkt.objects["chaos.rntj"] = ref
    rs = ObjectStoreSink(FakeTransport(bkt, schedule=sched), "chaos.rntj",
                         RemoteOptions(retry_policy=POLICY, hedge_ms=10),
                         create=False)
    r = RNTJReader(rs)
    got = list(r.iter_entries())
    r.close()
    assert got == entries
    d = r.stats.as_dict()
    assert d["io_hedges"] >= 1 and d["io_hedge_wins"] >= 1, (
        f"hedge did not win the race: {d['io_hedges']}/{d['io_hedge_wins']}")
    info["hedge_wins"] = d["io_hedge_wins"]

    # cell: permanent part failure degrades multipart -> serial put
    sched = FaultSchedule([FaultSpec.permanent_error(op="part")])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    s, w = remote_write(t, entries)
    s.close()
    d = w.stats.as_dict()
    assert d["io_degradations"] >= 1, "degradation not counted"
    assert t.bucket.objects["chaos.rntj"] == ref, "degraded put lost bytes"
    info["degradations"] = d["io_degradations"]

    # cell: writer killed mid-multipart -> salvage the interrupted upload
    sched = FaultSchedule([FaultSpec(op="part", kind="kill", at_call=4)])
    bkt = ObjectBucket()
    s = ObjectStoreSink(FakeTransport(bkt, schedule=sched), "chaos.rntj",
                        ROPTS)
    killed = False
    try:
        write_through(s, entries, cluster_bytes=2048)
    except (ProcessKilled, RuntimeError):
        killed = True
    s.close()
    assert killed, "kill point never fired"
    assert "chaos.rntj" not in bkt.objects
    rep = salvage_remote(FakeTransport(bkt), "chaos.rntj")
    assert rep.remote["mode"] == "multipart"
    assert rep.rebuilt and rep.entries_salvaged > 0
    r = RNTJReader(ObjectStoreSink(FakeTransport(bkt), "chaos.rntj",
                                   create=False))
    got = list(r.iter_entries())
    r.close()
    assert got == entries[: len(got)], "salvaged entries differ"
    assert len(got) == rep.entries_salvaged
    info["salvaged_entries"] = rep.entries_salvaged
    return info


SCENARIOS = {
    "transient": scenario_transient,
    "seeded": scenario_seeded,
    "enospc": scenario_enospc,
    "fsync": scenario_fsync,
    "stripe": scenario_stripe,
    "writebehind": scenario_writebehind,
    "latency": scenario_latency,
    "kill": scenario_kill,
    "skim": scenario_skim,
    "mpkill": scenario_mpkill,
    "mprecover": scenario_mprecover,
    "remote": scenario_remote,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="RNT-J chaos scenarios")
    ap.add_argument("--scenario", default="all",
                    choices=["all"] + sorted(SCENARIOS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--entries", type=int, default=800)
    args = ap.parse_args(argv)

    names = sorted(SCENARIOS) if args.scenario == "all" else [args.scenario]
    entries = make_entries(args.entries, args.seed)
    failed = []
    for name in names:
        try:
            info = SCENARIOS[name](list(entries), args.seed)
        except AssertionError as e:
            print(f"FAIL {name}: {e}")
            failed.append(name)
            continue
        print(f"ok   {name}: {info}")
    if failed:
        print(f"{len(failed)} scenario(s) failed: {', '.join(failed)}")
        return 1
    print(f"all {len(names)} scenario(s) held their invariants")
    return 0


if __name__ == "__main__":
    sys.exit(main())
