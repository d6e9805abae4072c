"""Fault injection, retrying I/O, and crash recovery (ISSUE 6).

Covers the three tentpole layers — the :mod:`repro.core.faults` sink, the
engine's :class:`RetryPolicy` chokepoint + degradation paths, and the
envelope/journal format with :mod:`repro.core.recover` — plus the
satellite regressions: idempotent close after a poisoned commit, fsync
errors never swallowed, and the crash matrix (salvage is byte-identical
and maximal at every kill point).
"""

import errno
import os
import random
import struct
import subprocess
import sys
import zlib
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    Collection,
    ColumnBatch,
    FaultInjectingSink,
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
    merge_files,
    recover_container,
    scan_container,
)
from repro.core.faults import crashed_file_bytes, memory_sink_from_bytes
from repro.core import metadata as md
from repro.core.pages import PageDesc

REPO_ROOT = Path(__file__).resolve().parent.parent

SCHEMA = Schema([
    Leaf("id", "int64"),
    Collection("vals", Leaf("_0", "float32")),
])

# fast deterministic backoff: tests must not sleep for real
FAST = RetryPolicy(max_attempts=6, backoff_base=0.0001, backoff_cap=0.0005)


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


def read_all(sink):
    r = RNTJReader(sink)
    try:
        return list(r.iter_entries())
    finally:
        r.close()


# ---------------------------------------------------------------------------
# FaultInjectingSink units


def test_fault_sink_transparent_without_rules():
    fs = FaultInjectingSink(MemorySink())
    off = fs.reserve(10)
    fs.pwrite(off, b"0123456789")
    assert fs.pread(off, 10) == b"0123456789"
    assert fs.persisted_bytes == 10
    assert fs.faults.injected == 0


def test_fault_sink_at_call_and_count():
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec.transient_error(at_call=1, count=1),
    ])
    fs.reserve(30)
    fs.pwrite(0, b"aaaaaaaaaa")                     # call 0: fine
    with pytest.raises(OSError):
        fs.pwrite(10, b"bbbbbbbbbb")                # call 1: EIO, no bytes
    assert fs.persisted_bytes == 10
    fs.pwrite(10, b"bbbbbbbbbb")                    # call 2: rule exhausted
    assert fs.pread(0, 20) == b"aaaaaaaaaabbbbbbbbbb"


def test_fault_sink_offset_window():
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec(op="write", kind="error", count=-1, at_offset=(100, 200)),
    ])
    fs.reserve(300)
    fs.pwrite(0, b"x" * 50)                          # below the window
    with pytest.raises(OSError):
        fs.pwrite(150, b"y")                         # inside
    with pytest.raises(OSError):
        fs.pwrite(90, b"z" * 20)                     # overlaps the boundary
    fs.pwrite(200, b"w")                             # past it


def test_fault_sink_short_write_persists_prefix():
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.short_write(fraction=0.3)])
    fs.reserve(100)
    with pytest.raises(OSError):
        fs.pwrite(0, b"A" * 100)
    assert fs.persisted_bytes == 30                  # the torn prefix landed
    assert fs.pread(0, 30) == b"A" * 30
    assert fs.faults.short_writes == 1


def test_fault_sink_kill_at_byte_freezes_file():
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.kill_at(25)])
    fs.reserve(100)
    fs.pwrite(0, b"a" * 20)
    with pytest.raises(ProcessKilled):
        fs.pwrite(20, b"b" * 20)                     # crosses byte 25
    assert fs.persisted_bytes == 25                  # exactly 5 of the 20
    assert fs.killed_at == 25 and fs.dead
    with pytest.raises(ProcessKilled):
        fs.pwrite(60, b"later")                      # dead sink stays dead
    with pytest.raises(ProcessKilled):
        fs.fsync()
    fs.close()                                       # teardown always works
    assert crashed_file_bytes(fs)[:25] == b"a" * 20 + b"b" * 5


def test_fault_sink_seeded_schedule_is_deterministic():
    def run(seed):
        fs = FaultInjectingSink(MemorySink(), seed=seed, error_rate=0.3)
        fs.reserve(1000)
        outcomes = []
        for i in range(50):
            try:
                fs.pwrite(i * 10, b"0123456789")
                outcomes.append(1)
            except OSError:
                outcomes.append(0)
        return outcomes
    assert run(7) == run(7)
    assert run(7) != run(8)


def test_fault_sink_latency_and_fsync_rules():
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec.latency(0.0, op="write", count=2),
        FaultSpec.fsync_error(count=1),
    ])
    fs.reserve(20)
    fs.pwrite(0, b"x" * 10)
    fs.pwrite(10, b"y" * 10)
    assert fs.faults.latencies == 2
    with pytest.raises(OSError):
        fs.fsync()
    fs.fsync()
    assert fs.faults.fsync_errors == 1


# ---------------------------------------------------------------------------
# RetryPolicy units


def test_retry_policy_retryable_classification():
    pol = RetryPolicy()
    assert pol.retryable(OSError(errno.EIO, "io"))
    assert pol.retryable(OSError(errno.ENOSPC, "nospc"))
    assert not pol.retryable(OSError(errno.EBADF, "badf"))
    assert not pol.retryable(ValueError("nope"))
    assert not pol.retryable(ProcessKilled("dead"))


def test_retry_policy_backoff_grows_and_caps():
    pol = RetryPolicy(backoff_base=0.01, backoff_cap=0.05, jitter=False)
    rng = random.Random(0)
    delays = [pol.backoff(a, rng) for a in range(1, 8)]
    assert delays[0] == pytest.approx(0.01)
    assert delays[1] == pytest.approx(0.02)
    assert all(d <= 0.05 + 1e-9 for d in delays)
    assert delays[-1] == pytest.approx(0.05)
    jit = RetryPolicy(backoff_base=0.01, backoff_cap=0.05, jitter=True)
    for a in range(1, 8):
        d = jit.backoff(a, random.Random(1))
        assert 0 < d <= 0.05 * 1.5


# ---------------------------------------------------------------------------
# engine retry paths


def test_transient_errors_retried_zero_loss():
    entries = make_entries(400)
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec.transient_error(count=3),
        FaultSpec.short_write(at_call=5),
    ])
    w = write_seq(fs, entries)
    d = w.stats.as_dict()
    assert d["io_retries"] >= 4
    assert d["io_giveups"] == 0
    assert read_all(fs.inner) == entries


def test_permanent_error_poisons_and_counts_giveup():
    entries = make_entries(400)
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec(op="write", kind="error", err=errno.EIO, count=-1,
                  at_offset=(2000, 1 << 62)),
    ])
    w = SequentialWriter(SCHEMA, fs,
                         WriteOptions(cluster_bytes=2048, retry_policy=FAST))
    with pytest.raises(OSError):
        for e in entries:
            w.fill(e)
        w.close()
    # satellite 1: first close surfaces the poison, any further close is
    # an exception-safe no-op
    with pytest.raises(RuntimeError, match="NOT finalized"):
        w.close()
    w.close()
    w.close()
    d = w.stats.as_dict()
    assert d["io_giveups"] >= 1
    assert d["io_retries"] >= FAST.max_attempts - 1
    # nothing was finalized: the torn file has no valid footer
    with pytest.raises(IOError):
        RNTJReader(memory_sink_from_bytes(crashed_file_bytes(fs)))


def test_non_retryable_errno_fails_fast():
    entries = make_entries(200)
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec(op="write", kind="error", err=errno.EBADF, count=-1,
                  at_offset=(2000, 1 << 62)),
    ])
    w = SequentialWriter(SCHEMA, fs,
                         WriteOptions(cluster_bytes=2048, retry_policy=FAST))
    with pytest.raises(OSError):
        for e in entries:
            w.fill(e)
        w.close()
    try:
        w.close()
    except RuntimeError:
        pass
    d = w.stats.as_dict()
    assert d["io_retries"] == 0          # EBADF is not in retryable_errnos


def test_retry_deadline_bounds_attempts():
    pol = RetryPolicy(max_attempts=1000, backoff_base=0.05, backoff_cap=0.05,
                      jitter=False, deadline=0.12)
    entries = make_entries(100)
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec(op="write", kind="error", err=errno.EIO, count=-1,
                  at_offset=(2000, 1 << 62)),
    ])
    w = SequentialWriter(SCHEMA, fs,
                         WriteOptions(cluster_bytes=2048, retry_policy=pol))
    with pytest.raises((OSError, RuntimeError)):
        for e in entries:
            w.fill(e)
        w.close()
    try:
        w.close()
    except RuntimeError:
        pass
    d = w.stats.as_dict()
    assert d["io_giveups"] >= 1
    assert d["io_retries"] <= 6          # the deadline cut the 1000 attempts


def test_write_behind_transient_errors_retried():
    entries = make_entries(400)
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.transient_error(count=4)])
    opts = WriteOptions(cluster_bytes=1024, retry_policy=FAST,
                        io_inflight_bytes=1 << 20)
    w = ParallelWriter(SCHEMA, fs, opts)
    ctx = w.create_fill_context()
    for e in entries:
        ctx.fill(e)
    ctx.close()
    w.close()
    d = w.stats.as_dict()
    assert d["io_retries"] >= 1
    assert read_all(fs.inner) == entries


def test_striped_failure_degrades_to_monolithic():
    entries = make_entries(600)
    fs = FaultInjectingSink(MemorySink(), [
        FaultSpec.transient_error(err=errno.EBADF, at_call=4, count=1),
    ])
    w = write_seq(fs, entries, cluster_bytes=16384,
                  io_stripe_bytes=2048, io_workers=2)
    d = w.stats.as_dict()
    assert d["io_stripe_fallbacks"] >= 1
    assert read_all(fs.inner) == entries


def test_fsync_transient_retried_permanent_poisons():
    entries = make_entries(300)
    # transient: retried, run completes, zero loss (satellite 2)
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.fsync_error(count=2)])
    w = write_seq(fs, entries, fsync_policy="every_cluster")
    assert w.stats.as_dict()["io_retries"] >= 2
    assert read_all(fs.inner) == entries

    # permanent: mid-run fsync failure must NOT be swallowed
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.fsync_error(count=-1)])
    w = SequentialWriter(SCHEMA, fs, WriteOptions(
        cluster_bytes=2048, retry_policy=FAST, fsync_policy="every_cluster"))
    with pytest.raises((OSError, RuntimeError)):
        for e in entries:
            w.fill(e)
        w.close()
    try:
        w.close()
    except RuntimeError:
        pass
    d = w.stats.as_dict()
    assert d["io_fsync_failures"] >= 1



def test_cluster_envelope_roundtrip_and_corruption():
    env = md.build_cluster_envelope(seq=7, payload_len=1234, desc_crc=0xABCD)
    assert len(env) == md.CLUSTER_ENV_SIZE
    d = md.parse_cluster_envelope(env)
    assert (d["seq"], d["payload_len"], d["desc_crc"]) == (7, 1234, 0xABCD)
    bad = bytearray(env)
    bad[9] ^= 0xFF
    with pytest.raises(IOError):
        md.parse_cluster_envelope(bytes(bad))
    with pytest.raises(IOError):
        md.parse_cluster_envelope(b"XXXX" + env[4:])


def _pages(offsets, base_col=0):
    return [PageDesc(column=base_col, n_elements=10, offset=o, size=40,
                     uncompressed_size=40, checksum=123, codec=0)
            for o in offsets]


def test_journal_record_roundtrip_buffered_offsets():
    pages = _pages([0, 40, 80])
    body = md.build_journal_body([10, 20], pages)
    rec, crc = md.finish_journal_record(
        seq=3, flags=md.JREC_BUFFERED, cluster_off=5000, cluster_size=120,
        first_entry=100, n_entries=10, n_columns=2, body=body)
    assert len(rec) == md.journal_record_size(2, 3)
    jr, end = md.parse_journal_record(rec)
    assert end == len(rec)
    assert jr.seq == 3 and jr.buffered and jr.crc == crc
    assert jr.n_elements == [10, 20]
    # cluster-relative offsets resolved to absolute
    assert [p.offset for p in jr.pages] == [5000, 5040, 5080]


def test_journal_record_unbuffered_keeps_absolute_offsets():
    pages = _pages([9000, 9040])
    body = md.build_journal_body([20], pages)
    rec, _ = md.finish_journal_record(0, 0, 0, 0, 0, 5, 1, body)
    jr, _ = md.parse_journal_record(rec)
    assert not jr.buffered
    assert [p.offset for p in jr.pages] == [9000, 9040]


def test_journal_record_corruption_detected():
    body = md.build_journal_body([10], _pages([0]))
    rec, _ = md.finish_journal_record(1, md.JREC_BUFFERED, 100, 40, 0, 5, 1,
                                      body)
    bad = bytearray(rec)
    bad[20] ^= 0x01
    with pytest.raises(IOError):
        md.parse_journal_record(bytes(bad))
    with pytest.raises(IOError):
        md.parse_journal_record(rec[: len(rec) - 3])   # truncated


def test_v1_anchor_still_parses():
    body = md._ANCHOR.pack(md.MAGIC, 1, 0, 64, 100, 32, 10, 2, 0)
    crc = zlib.crc32(body[:-8])
    anchor = md._ANCHOR.pack(md.MAGIC, 1, 0, 64, 100, 32, 10, 2, crc)
    d = md.parse_anchor(anchor)
    assert d["n_entries"] == 10
    bad = md._ANCHOR.pack(md.MAGIC, 9, 0, 64, 100, 32, 10, 2, crc)
    with pytest.raises(IOError):
        md.parse_anchor(bad)


def test_journal_framing_is_invisible_to_footer_readers():
    """byte_offset/byte_size point at the payload, so a journaled and an
    unjournaled file decode identically (framing = invisible padding)."""
    entries = make_entries(300)
    with_j, without_j = MemorySink(), MemorySink()
    write_seq(with_j, entries, retry_policy=None)
    write_seq(without_j, entries, retry_policy=None, journal=False)
    assert with_j.size > without_j.size         # framing occupies bytes
    assert read_all(with_j) == read_all(without_j) == entries


# ---------------------------------------------------------------------------
# recovery


def torn_copy(sink, cut):
    """The first ``cut`` bytes of a written file, as recovery sees them."""
    return memory_sink_from_bytes(bytes(sink.buf[:cut]))


def test_scan_complete_file_matches_footer():
    entries = make_entries(500)
    sink = MemorySink()
    write_seq(sink, entries)
    r = RNTJReader(sink)
    footer_clusters = [(cm.first_entry, cm.n_entries, cm.byte_offset,
                        cm.byte_size) for cm in r.clusters]
    r.close()
    _schema, _opts, clusters, rep = scan_container(sink)
    assert rep.entries_salvaged == len(entries)
    assert [(cm.first_entry, cm.n_entries, cm.byte_offset, cm.byte_size)
            for cm in clusters] == footer_clusters
    assert not rep.clusters_dropped


def test_recover_truncated_file_and_read_back():
    entries = make_entries(500)
    sink = MemorySink()
    write_seq(sink, entries)
    ms = torn_copy(sink, int(sink.size * 0.6))
    rep = recover_container(ms)
    assert rep.rebuilt and rep.clusters_salvaged > 0
    got = read_all(ms)
    assert got == entries[: len(got)]
    assert len(got) == rep.entries_salvaged > 0


def test_recover_valid_file_is_a_noop():
    entries = make_entries(200)
    sink = MemorySink()
    write_seq(sink, entries)
    size_before = sink.size
    rep = recover_container(sink)
    assert rep.footer_valid and not rep.rebuilt
    assert sink.size == size_before


def test_recover_force_rebuilds_valid_file():
    entries = make_entries(200)
    sink = MemorySink()
    write_seq(sink, entries)
    rep = recover_container(sink, force=True)
    assert rep.rebuilt
    assert read_all(sink) == entries


def test_recover_dry_run_writes_nothing():
    entries = make_entries(300)
    sink = MemorySink()
    write_seq(sink, entries)
    ms = torn_copy(sink, int(sink.size * 0.5))
    size_before = ms.size
    rep = recover_container(ms, dry_run=True)
    assert rep.clusters_salvaged > 0 and not rep.rebuilt
    assert ms.size == size_before
    with pytest.raises(IOError):
        RNTJReader(memory_sink_from_bytes(bytes(ms.buf[:ms.size])))


def test_recover_drops_cluster_with_corrupt_payload():
    entries = make_entries(500)
    sink = MemorySink()
    write_seq(sink, entries)
    _s, _o, clusters, _rep = scan_container(sink)
    assert len(clusters) >= 3
    victim = clusters[1]
    data = bytearray(bytes(sink.buf[: sink.size]))
    data[victim.byte_offset + 5] ^= 0xFF            # flip a payload byte
    ms = memory_sink_from_bytes(bytes(data))
    rep = recover_container(ms, force=True)
    assert any(d["seq"] == 1 for d in rep.clusters_dropped)
    assert rep.clusters_salvaged == len(clusters) - 1
    # surviving entries read back identical; the dropped cluster's range
    # is renumbered away (entry bytes never lie, ranges may shift)
    got = read_all(ms)
    survivors = []
    for i, cm in enumerate(clusters):
        if i != 1:
            survivors.extend(
                entries[cm.first_entry : cm.first_entry + cm.n_entries])
    assert got == survivors


def test_recover_unbuffered_file():
    entries = make_entries(400)
    sink = MemorySink()
    write_seq(sink, entries, buffered=False)
    ms = torn_copy(sink, int(sink.size * 0.7))
    rep = recover_container(ms)
    assert rep.clusters_salvaged > 0
    got = read_all(ms)
    assert got == entries[: len(got)]


def test_recover_merged_file():
    """Merge raw-copies clusters through _commit_raw_cluster: the merged
    output carries the same envelope/journal framing and salvages."""
    a, b = MemorySink(), MemorySink()
    ents_a, ents_b = make_entries(200, seed=1), make_entries(200, seed=2)
    write_seq(a, ents_a, retry_policy=None)
    write_seq(b, ents_b, retry_policy=None)
    out = MemorySink()
    merge_files([a, b], out, options=WriteOptions(cluster_bytes=2048))
    all_entries = ents_a + ents_b
    assert read_all(out) == all_entries
    ms = torn_copy(out, int(out.size * 0.55))
    rep = recover_container(ms)
    assert rep.clusters_salvaged > 0
    got = read_all(ms)
    assert got == all_entries[: len(got)]


def test_recover_header_torn_is_unrecoverable():
    entries = make_entries(100)
    sink = MemorySink()
    write_seq(sink, entries)
    with pytest.raises(RecoveryError):
        recover_container(torn_copy(sink, 40))
    with pytest.raises(RecoveryError):
        recover_container(memory_sink_from_bytes(b"not an rntj file at all"))


def test_recover_file_paths_and_output_copy(tmp_path):
    entries = make_entries(300)
    sink = MemorySink()
    write_seq(sink, entries)
    cut = int(sink.size * 0.6)
    torn = tmp_path / "torn.rntj"
    torn.write_bytes(bytes(sink.buf[:cut]))

    out = tmp_path / "recovered.rntj"
    rep = recover_container(str(torn), output=str(out))
    assert rep.rebuilt
    assert torn.stat().st_size == cut               # source untouched
    r = RNTJReader(str(out))
    got = list(r.iter_entries())
    r.close()
    assert got == entries[: len(got)] and got

    rep2 = recover_container(str(torn))             # now in place
    assert rep2.rebuilt
    r = RNTJReader(str(torn))
    assert list(r.iter_entries()) == got
    r.close()


def test_tolerant_reader_salvages_torn_file():
    entries = make_entries(400)
    sink = MemorySink()
    write_seq(sink, entries)
    ms = torn_copy(sink, int(sink.size * 0.6))
    with pytest.raises(IOError):
        RNTJReader(ms)
    r = RNTJReader(ms, options=ReadOptions(tolerant=True))
    assert r.salvage is not None and r.salvage.clusters_salvaged > 0
    got = list(r.iter_entries())
    r.close()
    assert got == entries[: len(got)] and got
    # a healthy file opened tolerant reports no salvage
    r = RNTJReader(sink, options=ReadOptions(tolerant=True))
    assert r.salvage is None
    r.close()


# ---------------------------------------------------------------------------
# the crash matrix (satellite 3)


def _journal_ends(sink):
    """Per-cluster journal-record end offsets of a cleanly written file,
    in commit order — cluster seq is fully durable iff the file reaches
    its record's end."""
    ends = {}
    _m, _t, plen = md._ENV_HDR.unpack(sink.pread(0, md._ENV_HDR.size))
    pos = md._ENV_HDR.size + plen + 4
    size = sink.size
    while pos + 4 <= size:
        magic = bytes(sink.pread(pos, 4))
        if magic == md.CLUSTER_ENV_MAGIC:
            env = md.parse_cluster_envelope(sink.pread(pos, md.CLUSTER_ENV_SIZE))
            pos += md.CLUSTER_ENV_SIZE + env["payload_len"]
        elif magic == md.JOURNAL_MAGIC:
            jr, end_rel = md.parse_journal_record(
                sink.pread(pos, size - pos), 0)
            ends[jr.seq] = pos + end_rel
            pos = ends[jr.seq]
        elif magic == md._ENV_MAGIC:
            _m2, _t2, plen2 = md._ENV_HDR.unpack(
                sink.pread(pos, md._ENV_HDR.size))
            pos += md._ENV_HDR.size + plen2 + 4
        elif magic == md.MAGIC:
            pos += md.ANCHOR_SIZE
        else:
            raise AssertionError(f"unexpected bytes at {pos} in clean file")
    return ends


def test_crash_matrix_salvage_is_byte_identical_and_maximal():
    entries = make_entries(700, seed=3)
    ref = MemorySink()
    write_seq(ref, entries, cluster_bytes=1024, retry_policy=None)
    size = ref.size
    ends = _journal_ends(ref)
    r = RNTJReader(ref)
    ranges = {i: (cm.first_entry, cm.n_entries)
              for i, cm in enumerate(r.clusters)}
    r.close()
    assert len(ranges) >= 8, "workload too small for a meaningful matrix"

    hdr_end = min(cm_end for cm_end in ends.values())
    kill_points = sorted(set(
        [int(k) for k in np.linspace(600, size + 128, 14)]
        + [hdr_end - 4, hdr_end, hdr_end + 1]        # around the 1st record
        + [size - 80, size - 8]                      # inside footer/anchor
    ))
    assert len(kill_points) >= 18

    for K in kill_points:
        fs = FaultInjectingSink(MemorySink(), [FaultSpec.kill_at(K)])
        crashed = False
        try:
            write_seq(fs, entries, cluster_bytes=1024, retry_policy=None)
        except (ProcessKilled, OSError, RuntimeError):
            crashed = True
        data = crashed_file_bytes(fs)
        # single producer, no write-behind: bytes persisted before the
        # kill are exactly the reference file's prefix; anything past the
        # kill byte is a reserved-but-unwritten (all-zero) sparse tail
        kbyte = fs.killed_at if crashed and fs.killed_at is not None else len(data)
        if crashed:
            assert data[:kbyte] == bytes(ref.buf[:kbyte]), f"K={K}: divergence"
        expected = sum(1 for e in ends.values() if e <= kbyte)
        ms = memory_sink_from_bytes(data)
        try:
            rep = recover_container(ms)
        except RecoveryError:
            assert expected == 0, (
                f"K={K}: unrecoverable but {expected} clusters were durable")
            continue
        if rep.footer_valid:                         # kill never fired
            assert not crashed and read_all(ms) == entries
            continue
        assert rep.clusters_salvaged == expected, (
            f"K={K}: salvaged {rep.clusters_salvaged}, journal says "
            f"{expected} were fully committed")
        assert not rep.clusters_dropped, f"K={K}: dropped {rep.clusters_dropped}"
        got = read_all(ms)
        assert got == entries[: len(got)], f"K={K}: salvage not identical"
        assert len(got) == sum(
            ranges[s][1] for s in range(rep.clusters_salvaged))


def test_crash_during_parallel_write_recovers_committed_prefix():
    """Write-behind + kill: every salvaged cluster must read back
    byte-identical (the salvage count is whatever was durable)."""
    entries = make_entries(600, seed=5)
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.kill_at(6000)])
    opts = WriteOptions(cluster_bytes=1024, io_inflight_bytes=1 << 20)
    w = ParallelWriter(SCHEMA, fs, opts)
    try:
        ctx = w.create_fill_context()
        for e in entries:
            ctx.fill(e)
        ctx.close()
        w.close()
    except (ProcessKilled, OSError, RuntimeError):
        pass
    try:
        w.close()
    except (ProcessKilled, OSError, RuntimeError):
        pass
    data = crashed_file_bytes(fs)
    ms = memory_sink_from_bytes(data)
    try:
        rep = recover_container(ms)
    except RecoveryError:
        return                                       # killed before header
    got = read_all(ms)
    assert len(got) == rep.entries_salvaged
    assert got == entries[: len(got)]                # sequential fill order


# ---------------------------------------------------------------------------
# CLI smoke


def test_recover_cli(tmp_path):
    entries = make_entries(300)
    sink = MemorySink()
    write_seq(sink, entries)
    torn = tmp_path / "torn.rntj"
    torn.write_bytes(bytes(sink.buf[: int(sink.size * 0.6)]))
    env = dict(os.environ, PYTHONPATH=str(REPO_ROOT / "src"))

    out = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "recover.py"),
         str(torn), "--dry-run", "--json"],
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    assert '"rebuilt": false' in out.stdout

    out = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "recover.py"), str(torn)],
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    assert "salvaged" in out.stdout
    r = RNTJReader(str(torn))
    got = list(r.iter_entries())
    r.close()
    assert got == entries[: len(got)] and got


def test_chaos_cli_single_scenario():
    out = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "chaos.py"),
         "--scenario", "transient", "--entries", "300"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ok   transient" in out.stdout


# ---------------------------------------------------------------------------
# RetryPolicy x fault interaction on the fsync path (ISSUE 8 satellite):
# a footer must never cover clusters whose fsync did not succeed


def test_fsync_transient_failure_retries_then_seals(tmp_path):
    """fsync fails transiently under a RetryPolicy: the engine retries to
    success and close() seals a VALID footer (the data really is synced)."""
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.fsync_error(count=2)])
    w = SequentialWriter(SCHEMA, fs, WriteOptions(
        cluster_bytes=2048, retry_policy=FAST, fsync_policy="on_close"))
    entries = make_entries(200, 0)
    for e in entries:
        w.fill(e)
    w.close()  # the close-time fsync absorbs both injected failures
    d = w.stats.as_dict()
    assert d["io_retries"] >= 2 and d["io_giveups"] == 0
    assert fs.faults.fsync_errors == 2
    rep = recover_container(fs.inner, dry_run=True)
    assert rep.footer_valid
    verify = RNTJReader(fs.inner)
    assert list(verify.iter_entries()) == entries
    verify.close()


def test_fsync_permanent_failure_never_seals_footer(tmp_path):
    """fsync fails permanently: retries exhaust, close() poisons — and the
    file must NOT end in a valid footer (its clusters were never synced).
    The journal still makes every committed cluster salvageable."""
    fs = FaultInjectingSink(MemorySink(), [FaultSpec.fsync_error(count=-1)])
    w = SequentialWriter(SCHEMA, fs, WriteOptions(
        cluster_bytes=2048, retry_policy=FAST, fsync_policy="every_cluster"))
    entries = make_entries(200, 0)
    with pytest.raises((OSError, RuntimeError)):
        for e in entries:
            w.fill(e)
        w.close()
    with pytest.raises((OSError, RuntimeError)):
        w.close()  # surfaces the latched poison (and merges engine stats)
    d = w.stats.as_dict()
    assert d["io_fsync_failures"] >= 1 and d["io_giveups"] >= 1

    rep = recover_container(fs.inner, dry_run=True)
    assert not rep.footer_valid, (
        "footer sealed over clusters whose fsync never succeeded")
    # the journaled prefix is still salvageable after the crash
    ms = memory_sink_from_bytes(crashed_file_bytes(fs))
    rep = recover_container(ms)
    assert rep.rebuilt
    r = RNTJReader(ms)
    got = list(r.iter_entries())
    r.close()
    assert got == entries[: len(got)]


def test_mp_participant_fsync_failure_withholds_done(tmp_path):
    """Multi-writer flavor: a participant whose finalize-fsync fails must
    not report DONE — the coordinator fences it and page-verifies its
    clusters instead of trusting the missing durability handshake."""
    from repro.core import (FencedError, MultiWriterCoordinator,
                            join_container, open_sink)
    from repro.core.extents import ExtentLog

    path = str(tmp_path / "mp.rntj")
    opts = WriteOptions(cluster_bytes=1024, retry_policy=FAST,
                        lease_interval=0.3, rendezvous_timeout=5.0,
                        mpw_log_fsync=False)
    coord = MultiWriterCoordinator(SCHEMA, path, opts)
    fs = FaultInjectingSink(open_sink(path, create=False),
                            [FaultSpec.fsync_error(count=-1)])
    w = join_container(path, schema=SCHEMA, options=opts, sink=fs)
    ctx = w.create_fill_context()
    entries = make_entries(60, 0)
    for e in entries:
        ctx.fill(e)
    with pytest.raises((OSError, RuntimeError)):
        ctx.close()
        w.close()

    log = ExtentLog(ExtentLog.sidecar_path(path), fsync=False)
    st = log.snapshot()
    log.close()
    assert not st.writers[w.writer_id].done, (
        "DONE reported despite a failed durability fsync")

    report = coord.seal(expect_writers=1)
    coord.close()
    assert report["fenced"] == [w.writer_id]
    r = RNTJReader(path)
    got = list(r.iter_entries())
    r.close()
    assert got == entries[: len(got)] and got, "committed clusters lost"
