"""Object-store sink failure semantics (ISSUE 9, DESIGN.md §10).

The tentpole matrix: byte-identity under zero faults, retry-until-success
vs retry-exhaustion, per-attempt deadline enforcement, hedge-wins-race
determinism, interrupted-multipart salvage round-trips, degraded-mode
fallback — all over the hermetic :class:`FakeTransport`.  Plus the
satellite regressions: :class:`FaultInjectingSink` injecting on the
zero-copy ``pread_into`` path, and the reader-level retry chokepoint
(``ReadOptions.retry_policy`` → ``ReaderStats.retries``).
"""

import errno
import threading

import numpy as np
import pytest

from _legacy_seed_reader import SeedRNTJReader

from repro.core import (
    Collection,
    FaultInjectingSink,
    FaultSchedule,
    FaultSpec,
    Leaf,
    MemorySink,
    ParallelWriter,
    ProcessKilled,
    ReadOptions,
    RecoveryError,
    RNTJReader,
    RetryPolicy,
    Schema,
    SequentialWriter,
    WriteOptions,
    open_sink,
    recover_container,
)
from repro.core.faults import memory_sink_from_bytes
from repro.core.remote import (
    FakeTransport,
    ObjectBucket,
    ObjectStoreSink,
    RemoteOptions,
    _add_interval,
    mem_bucket,
    parse_remote_url,
    reset_mem_buckets,
    salvage_remote,
)


SCHEMA = Schema([
    Leaf("id", "int64"),
    Collection("vals", Leaf("_0", "float32")),
])

# fast deterministic backoff: tests must not sleep for real
FAST = RetryPolicy(max_attempts=6, backoff_base=0.0001, backoff_cap=0.0005)
FAST_OPTS = RemoteOptions(part_bytes=256, retry_policy=FAST)


def make_entries(n, seed=0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 6, size=n)
    return [
        {"id": int(i),
         "vals": [float(v) for v in rng.random(lens[i], dtype=np.float32)]}
        for i in range(n)
    ]


def write_seq(sink, entries, **kw):
    opts = WriteOptions(cluster_bytes=kw.pop("cluster_bytes", 2048),
                        retry_policy=kw.pop("retry_policy", FAST), **kw)
    w = SequentialWriter(SCHEMA, sink, opts)
    for e in entries:
        w.fill(e)
    w.close()
    return w


def reference_bytes(entries, **kw):
    ms = MemorySink()
    write_seq(ms, entries, **kw)
    data = bytes(ms.buf[: ms.size])
    ms.close()
    return data


def read_all(sink_or_path, options=None):
    r = RNTJReader(sink_or_path, options=options)
    try:
        return list(r.iter_entries())
    finally:
        r.close()


# ---------------------------------------------------------------------------
# Part-interval bookkeeping unit
# ---------------------------------------------------------------------------


def test_add_interval_merges():
    iv = []
    _add_interval(iv, 10, 20)
    _add_interval(iv, 30, 40)
    assert iv == [(10, 20), (30, 40)]
    _add_interval(iv, 20, 30)          # bridges the gap
    assert iv == [(10, 40)]
    _add_interval(iv, 0, 5)
    _add_interval(iv, 5, 10)           # touching merges
    assert iv == [(0, 40)]
    _add_interval(iv, 50, 60)
    _add_interval(iv, 45, 55)
    assert iv == [(0, 40), (45, 60)]


def test_parse_remote_url():
    scheme, bucket, key, opts, params = parse_remote_url(
        "mem-s3://bkt/dir/file.rntj?part_bytes=4096&remote_hedge_ms=5&rtt_ms=10")
    assert (scheme, bucket, key) == ("mem-s3", "bkt", "dir/file.rntj")
    assert opts.part_bytes == 4096 and opts.hedge_ms == 5.0
    assert params == {"rtt_ms": "10"}
    with pytest.raises(ValueError):
        parse_remote_url("mem-s3://bucketonly")
    with pytest.raises(ValueError):
        open_sink("no-such-scheme://b/k")


# ---------------------------------------------------------------------------
# Byte identity under zero faults
# ---------------------------------------------------------------------------


def test_byte_identity_zero_faults(tmp_path):
    entries = make_entries(400)
    ref = reference_bytes(entries)

    # vs FileSink
    from repro.core import FileSink
    fsink = FileSink(str(tmp_path / "ref.rntj"))
    write_seq(fsink, entries)
    fsink.close()
    assert (tmp_path / "ref.rntj").read_bytes() == ref

    # remote multipart
    t = FakeTransport(ObjectBucket())
    s = ObjectStoreSink(t, "k", FAST_OPTS)
    write_seq(s, entries)
    s.close()
    assert t.bucket.objects["k"] == ref
    # zero faults -> zero retries, hedges, degradations
    assert s.io.retries == 0 and s.io.giveups == 0
    assert s.io.hedges == 0 and s.io.degradations == 0
    # multipart actually ran: nothing left dangling
    assert t.bucket.uploads.get("k", {}) == {}

    # remote serial-put mode is identical too
    t2 = FakeTransport(ObjectBucket())
    s2 = ObjectStoreSink(t2, "k", RemoteOptions(part_bytes=256,
                                                retry_policy=FAST,
                                                multipart=False))
    write_seq(s2, entries)
    s2.close()
    assert t2.bucket.objects["k"] == ref
    assert s2.io.degradations == 0


def test_url_roundtrip_and_reader_routing():
    reset_mem_buckets()
    entries = make_entries(300, seed=3)
    sink = open_sink("mem-s3://rt/test.rntj?part_bytes=512")
    assert isinstance(sink, ObjectStoreSink)
    write_seq(sink, entries)
    sink.close()
    assert mem_bucket("rt").objects["test.rntj"] == reference_bytes(entries)
    # RNTJReader routes URLs through open_sink(create=False)
    got = read_all("mem-s3://rt/test.rntj")
    assert [dict(e) for e in got] == entries


def test_write_mode_local_reads_and_flush():
    t = FakeTransport(ObjectBucket())
    s = ObjectStoreSink(t, "k", FAST_OPTS)
    off = s.reserve(600)
    s.pwrite(off, b"x" * 600)
    # write-mode preads serve from retained buffers, holes read as zeros
    assert s.pread(0, 600) == b"x" * 600
    assert s.pread(600, 10) == b"\x00" * 10
    # parts 0 and 1 are fully covered by the 600-byte write: flush (and the
    # pwrite itself) ships them
    s.flush()
    parts = next(iter(t.bucket.uploads["k"].values()))
    assert sorted(parts) == [1, 2]
    s.close()
    assert t.bucket.objects["k"] == b"x" * 600


# ---------------------------------------------------------------------------
# Retry semantics
# ---------------------------------------------------------------------------


def test_retry_until_success():
    entries = make_entries(400)
    ref = reference_bytes(entries)
    sched = FaultSchedule([FaultSpec.transient_error(op="part", count=3)])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    s = ObjectStoreSink(t, "k", FAST_OPTS)
    write_seq(s, entries)
    s.close()
    assert t.bucket.objects["k"] == ref
    assert s.io.retries >= 3
    assert s.io.giveups == 0 and s.io.degradations == 0


def test_torn_part_retried_idempotently():
    entries = make_entries(400)
    ref = reference_bytes(entries)
    # two torn part uploads: a prefix lands in the store, the call fails,
    # the retry re-uploads the full part over the same part number
    sched = FaultSchedule([FaultSpec(op="part", kind="short", count=2,
                                     fraction=0.5)])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    s = ObjectStoreSink(t, "k", FAST_OPTS)
    write_seq(s, entries)
    s.close()
    assert t.bucket.objects["k"] == ref
    assert sched.stats.short_writes == 2
    assert s.io.retries >= 2


def test_read_retry_exhaustion_counts_giveup():
    ref = reference_bytes(make_entries(200))
    sched = FaultSchedule([FaultSpec.permanent_error(op="get")])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    t.bucket.objects["k"] = ref
    s = ObjectStoreSink(t, "k", RemoteOptions(retry_policy=FAST),
                        create=False)
    with pytest.raises(OSError):
        s.pread(0, 100)
    assert s.io.retries == FAST.max_attempts - 1
    assert s.io.giveups == 1
    s.close()


def test_torn_get_retried():
    ref = reference_bytes(make_entries(200))
    sched = FaultSchedule([FaultSpec.short_read(op="get", count=2,
                                                fraction=0.25)])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    t.bucket.objects["k"] = ref
    s = ObjectStoreSink(t, "k", RemoteOptions(retry_policy=FAST),
                        create=False)
    assert s.pread(0, 200) == ref[:200]
    assert sched.stats.short_reads == 2
    assert s.io.retries >= 2
    s.close()


def test_deadline_enforcement():
    ref = reference_bytes(make_entries(200))
    # one slow GET (80 ms service) against a 20 ms per-attempt deadline:
    # the attempt burns its deadline, fails with ETIMEDOUT (retryable),
    # and the retry — no longer hit by the latency rule — succeeds
    sched = FaultSchedule([FaultSpec.latency(0.08, op="get", count=1)])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    t.bucket.objects["k"] = ref
    s = ObjectStoreSink(t, "k", RemoteOptions(deadline_ms=20,
                                              retry_policy=FAST),
                        create=False)
    assert s.pread(0, 128) == ref[:128]
    assert s.io.retries >= 1
    s.close()

    # permanent slowness exhausts the retry budget with ETIMEDOUT
    sched = FaultSchedule([FaultSpec.latency(0.05, op="get", count=-1)])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    t.bucket.objects["k"] = ref
    s = ObjectStoreSink(t, "k",
                        RemoteOptions(deadline_ms=10,
                                      retry_policy=RetryPolicy(
                                          max_attempts=3,
                                          backoff_base=0.0001,
                                          backoff_cap=0.0005)),
                        create=False)
    with pytest.raises(OSError) as ei:
        s.pread(0, 64)
    assert ei.value.errno == errno.ETIMEDOUT
    assert s.io.giveups == 1
    s.close()


def test_hedge_wins_race():
    entries = make_entries(300)
    ref = reference_bytes(entries)
    # scripted slow tail on the FIRST ranged GET only: the primary stalls
    # 200 ms, the hedge (the second "get" call) is instant and wins —
    # deterministic because the schedule is scripted, not sampled
    sched = FaultSchedule([FaultSpec.latency(0.2, op="get", count=1)])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    t.bucket.objects["k"] = ref
    s = ObjectStoreSink(t, "k", RemoteOptions(hedge_ms=10,
                                              retry_policy=FAST),
                        create=False)
    r = RNTJReader(s)
    got = list(r.iter_entries())
    r.close()
    assert [dict(e) for e in got] == entries
    d = r.stats.as_dict()
    assert d["io_hedges"] >= 1
    assert d["io_hedge_wins"] >= 1
    assert d["retries"] == 0  # the hedge raced, nothing had to fail


def test_hedge_survives_failing_primary():
    # the hedged pair tolerates one of the two attempts erroring outright
    ref = reference_bytes(make_entries(100))
    sched = FaultSchedule([
        FaultSpec.latency(0.2, op="get", count=1),
        FaultSpec.transient_error(op="get", count=1, at_call=1),
    ])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    t.bucket.objects["k"] = ref
    s = ObjectStoreSink(t, "k", RemoteOptions(hedge_ms=10,
                                              retry_policy=FAST),
                        create=False)
    # hedge (call 1) errors; the slow primary (call 0) still answers
    assert s.pread(0, 100) == ref[:100]
    assert s.io.hedges >= 1
    s.close()


# ---------------------------------------------------------------------------
# Degraded mode
# ---------------------------------------------------------------------------


def test_degraded_fallback_is_lossless():
    entries = make_entries(400)
    ref = reference_bytes(entries)
    sched = FaultSchedule([FaultSpec.permanent_error(op="part")])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    s = ObjectStoreSink(t, "k", FAST_OPTS)
    write_seq(s, entries)
    s.close()
    # multipart never succeeded; the serial put carried the bytes
    assert t.bucket.objects["k"] == ref
    assert s.io.degradations == 1
    assert s.io.retries > 0
    # the dangling upload was aborted during close
    assert t.bucket.uploads.get("k", {}) == {}
    assert [dict(e) for e in
            read_all(ObjectStoreSink(FakeTransport(t.bucket), "k",
                                     create=False))] == entries


def test_degraded_create_multipart():
    entries = make_entries(200)
    ref = reference_bytes(entries)
    sched = FaultSchedule([FaultSpec.permanent_error(op="create")])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    s = ObjectStoreSink(t, "k", FAST_OPTS)
    assert s.io.degradations == 1  # degraded at open, before any write
    write_seq(s, entries)
    s.close()
    assert t.bucket.objects["k"] == ref


def test_degraded_complete_multipart():
    entries = make_entries(300)
    ref = reference_bytes(entries)
    sched = FaultSchedule([FaultSpec.permanent_error(op="complete")])
    t = FakeTransport(ObjectBucket(), schedule=sched)
    s = ObjectStoreSink(t, "k", FAST_OPTS)
    write_seq(s, entries)
    s.close()
    assert t.bucket.objects["k"] == ref
    assert s.io.degradations == 1


# ---------------------------------------------------------------------------
# Interrupted multipart -> salvage
# ---------------------------------------------------------------------------


def _kill_mid_multipart(entries, at_call=10, part_bytes=256):
    sched = FaultSchedule([FaultSpec(op="part", kind="kill",
                                     at_call=at_call)])
    bkt = ObjectBucket()
    t = FakeTransport(bkt, schedule=sched)
    s = ObjectStoreSink(t, "k", RemoteOptions(part_bytes=part_bytes,
                                              retry_policy=FAST))
    with pytest.raises((ProcessKilled, RuntimeError)):
        write_seq(s, entries)
    s.close()  # poisoned teardown must not raise
    assert "k" not in bkt.objects
    assert bkt.uploads["k"], "interrupted upload must survive the crash"
    return bkt


def test_interrupted_multipart_salvage_roundtrip():
    entries = make_entries(2000, seed=11)
    bkt = _kill_mid_multipart(entries)
    # a fresh transport over the same bucket is the recovery process
    report = salvage_remote(FakeTransport(bkt), "k")
    assert report.remote["mode"] == "multipart"
    assert report.remote["parts_salvaged"] >= 10
    assert report.rebuilt
    assert report.entries_salvaged > 0
    # the rebuilt object is a readable container with a salvaged prefix
    assert "k" in bkt.objects
    assert bkt.uploads.get("k", {}) == {}, "dangling upload aborted"
    got = read_all(ObjectStoreSink(FakeTransport(bkt), "k", create=False))
    assert [dict(e) for e in got] == entries[: len(got)]
    assert len(got) == report.entries_salvaged


def test_salvage_dry_run_leaves_store_untouched():
    entries = make_entries(2000, seed=11)
    bkt = _kill_mid_multipart(entries)
    report = salvage_remote(FakeTransport(bkt), "k", dry_run=True)
    assert report.entries_salvaged > 0 and not report.rebuilt
    assert "k" not in bkt.objects
    assert bkt.uploads["k"]


def test_recover_container_routes_remote_urls():
    reset_mem_buckets()
    entries = make_entries(2000, seed=5)
    sched = FaultSchedule([FaultSpec(op="part", kind="kill", at_call=10)])
    bkt = mem_bucket("rec")
    t = FakeTransport(bkt, schedule=sched)
    s = ObjectStoreSink(t, "file.rntj", RemoteOptions(part_bytes=256,
                                                      retry_policy=FAST))
    with pytest.raises((ProcessKilled, RuntimeError)):
        write_seq(s, entries)
    s.close()
    with pytest.raises(ValueError):
        recover_container("mem-s3://rec/file.rntj", output="/tmp/x")
    report = recover_container("mem-s3://rec/file.rntj")
    assert report.remote["mode"] == "multipart"
    assert report.rebuilt and report.entries_salvaged > 0
    got = read_all("mem-s3://rec/file.rntj")
    assert [dict(e) for e in got] == entries[: len(got)]


def test_salvage_existing_object_with_valid_footer_is_noop():
    entries = make_entries(300)
    bkt = ObjectBucket()
    s = ObjectStoreSink(FakeTransport(bkt), "k", FAST_OPTS)
    write_seq(s, entries)
    s.close()
    before = bkt.objects["k"]
    report = salvage_remote(FakeTransport(bkt), "k")
    assert report.remote["mode"] == "object"
    assert report.footer_valid and not report.rebuilt
    assert bkt.objects["k"] == before


def test_salvage_nothing_there():
    with pytest.raises(RecoveryError):
        salvage_remote(FakeTransport(ObjectBucket()), "missing")


# ---------------------------------------------------------------------------
# Satellite: FaultInjectingSink covers pread_into (zero-copy read path)
# ---------------------------------------------------------------------------


def test_fault_sink_pread_into_injects():
    ref = b"0123456789" * 20
    fs = FaultInjectingSink(memory_sink_from_bytes(ref),
                            faults=[FaultSpec.transient_error(op="read")])
    buf = bytearray(50)
    with pytest.raises(OSError):
        fs.pread_into(0, buf)
    assert fs.faults.errors == 1
    # next call goes through (count=1 consumed) and lands real bytes
    assert fs.pread_into(0, buf) == 50
    assert bytes(buf) == ref[:50]


def test_fault_sink_pread_into_torn_fills_prefix():
    ref = bytes(range(200))
    fs = FaultInjectingSink(memory_sink_from_bytes(ref),
                            faults=[FaultSpec.short_read(fraction=0.5)])
    buf = bytearray(b"\xff" * 100)
    with pytest.raises(OSError):
        fs.pread_into(0, buf)
    assert fs.faults.short_reads == 1
    # the torn response delivered exactly the prefix; the tail is the
    # caller's stale buffer — the contract recycled-pool readers must
    # survive
    assert bytes(buf[:50]) == ref[:50]
    assert bytes(buf[50:]) == b"\xff" * 50


def test_fault_sink_pread_torn_raises_without_prefix():
    ref = bytes(range(100))
    fs = FaultInjectingSink(memory_sink_from_bytes(ref),
                            faults=[FaultSpec.short_read(fraction=0.5)])
    with pytest.raises(OSError):
        fs.pread(0, 64)
    assert fs.faults.short_reads == 1
    assert fs.pread(0, 64) == ref[:64]


def test_fault_sink_pwritev_decomposition_sees_every_part():
    # base-class pwritev decomposes into pwrites, so per-part faults fire
    fs = FaultInjectingSink(MemorySink(),
                            faults=[FaultSpec.transient_error(at_call=1)])
    fs.reserve(8)
    with pytest.raises(OSError):
        fs.pwritev(0, [b"aaaa", b"bbbb"])
    assert fs.faults.errors == 1
    assert fs.persisted_bytes == 4  # first part landed before the fault


# ---------------------------------------------------------------------------
# Satellite: reader-level retry policy
# ---------------------------------------------------------------------------


def test_reader_retries_transient_pread_faults():
    entries = make_entries(400)
    ref = reference_bytes(entries)
    fs = FaultInjectingSink(memory_sink_from_bytes(ref),
                            faults=[FaultSpec.transient_error(op="read",
                                                              count=3)])
    got = read_all(fs, options=ReadOptions(retry_policy=FAST))
    assert [dict(e) for e in got] == entries
    r = RNTJReader(memory_sink_from_bytes(ref))
    r.close()


def test_reader_retry_stats_and_default_fail_fast():
    entries = make_entries(400)
    ref = reference_bytes(entries)

    fs = FaultInjectingSink(memory_sink_from_bytes(ref),
                            faults=[FaultSpec.transient_error(op="read",
                                                              count=2)])
    r = RNTJReader(fs, options=ReadOptions(retry_policy=FAST))
    list(r.iter_entries())
    r.close()
    d = r.stats.as_dict()
    assert d["retries"] >= 2 and d["giveups"] == 0

    # default ReadOptions: first transient error raises (fail fast)
    fs2 = FaultInjectingSink(memory_sink_from_bytes(ref),
                             faults=[FaultSpec.transient_error(op="read")])
    with pytest.raises((IOError, OSError)):
        read_all(fs2)


def test_reader_gives_up_on_permanent_faults():
    entries = make_entries(200)
    ref = reference_bytes(entries)
    fs = FaultInjectingSink(memory_sink_from_bytes(ref),
                            faults=[FaultSpec.permanent_error(op="read")])
    r = None
    with pytest.raises((IOError, OSError)):
        r = RNTJReader(fs, options=ReadOptions(retry_policy=FAST))
        list(r.iter_entries())
    if r is not None:
        r.close()


# ---------------------------------------------------------------------------
# Acceptance: 100 ms RTT, seeded transient faults, parallel write
# ---------------------------------------------------------------------------


def test_acceptance_high_rtt_faulty_parallel_write():
    entries = make_entries(600, seed=42)

    sched = FaultSchedule(seed=1234, error_rate=0.05,
                          errnos=(errno.EIO, errno.ETIMEDOUT),
                          random_ops=("put", "part", "get", "create",
                                      "complete"))
    t = FakeTransport(ObjectBucket(), schedule=sched, rtt_s=0.1)
    s = ObjectStoreSink(t, "k", RemoteOptions(part_bytes=256,
                                              retry_policy=FAST))
    w = ParallelWriter(SCHEMA, s, WriteOptions(cluster_bytes=4096,
                                               retry_policy=FAST))

    def fill(tid):
        ctx = w.create_fill_context()
        for e in entries[tid::4]:
            ctx.fill(e)
        ctx.close()

    threads = [threading.Thread(target=fill, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    w.close()

    stats = w.stats.as_dict()
    # faults were actually sampled, and every one shows up as a retry:
    # zero retries ≠ zero faults, in both directions
    assert sched.stats.random_errors > 0, "fault schedule never fired"
    assert stats["io_retries"] > 0
    assert stats["io_retries"] >= sched.stats.random_errors - \
        stats["io_degradations"] * FAST.max_attempts
    assert stats["io_giveups"] == 0 or stats["io_degradations"] > 0

    # parallel commit order (and hence cluster packing) is nondeterministic,
    # so verify losslessness through the readers rather than byte equality
    # with the sequential reference
    assert t.bucket.objects["k"]

    seed_r = SeedRNTJReader(
        ObjectStoreSink(FakeTransport(t.bucket), "k", create=False))
    assert seed_r.n_entries == len(entries)
    ids = np.concatenate(
        [seed_r.read_cluster(i)[0] for i in range(seed_r.n_clusters)]
    )
    seed_r.close()
    assert ids.dtype == np.int64
    assert sorted(ids.tolist()) == [e["id"] for e in entries]

    got = read_all(ObjectStoreSink(FakeTransport(t.bucket), "k",
                                   create=False))
    assert sorted(e["id"] for e in got) == [e["id"] for e in entries]

    # and the inverse direction: a clean transport reports zero retries
    t2 = FakeTransport(ObjectBucket(), rtt_s=0.0)
    s2 = ObjectStoreSink(t2, "k", FAST_OPTS)
    write_seq(s2, entries, cluster_bytes=4096)
    s2.close()
    assert s2.io.retries == 0


def test_idempotent_reupload_skips_unchanged_parts():
    t = FakeTransport(ObjectBucket())
    s = ObjectStoreSink(t, "k", RemoteOptions(part_bytes=128,
                                              retry_policy=FAST))
    s.reserve(256)
    s.pwrite(0, b"a" * 256)   # ships parts 1 and 2
    sched_calls_before = len(next(iter(t.bucket.uploads["k"].values())))
    assert sched_calls_before == 2
    s.flush()                  # nothing new: CRC-keyed skip
    s.close()                  # close re-walks all parts; unchanged -> skip
    assert t.bucket.objects["k"] == b"a" * 256


def test_rewritten_part_reuploads_under_same_number():
    t = FakeTransport(ObjectBucket())
    s = ObjectStoreSink(t, "k", RemoteOptions(part_bytes=128,
                                              retry_policy=FAST))
    s.reserve(300)
    s.pwrite(0, b"a" * 300)
    s.pwrite(0, b"b" * 64)     # dirties part 0 after it was shipped
    s.close()
    obj = t.bucket.objects["k"]
    assert obj == b"b" * 64 + b"a" * 236
