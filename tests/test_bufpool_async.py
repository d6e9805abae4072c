"""Buffer pool + write-behind ring tests: size-class bounds,
completion-driven recycling (a buffer is never handed out while a queued
write still references it), ring submission byte-identity and ordering,
poisoning through the ring, and the pooled merge / unbuffered / reader
paths."""

import threading
import time

import numpy as np
import pytest

from repro.core import (
    BufferPool,
    Collection,
    ColumnBatch,
    ColumnBuffer,
    FileSink,
    Leaf,
    MemorySink,
    ReadOptions,
    RNTJReader,
    Schema,
    SequentialWriter,
    ThrottledSink,
    WriteOptions,
    merge_files,
)
from repro.core.bufpool import Recyclable, _class_bytes
from repro.core.ioengine import IOEngine


def vec_schema():
    return Schema([
        Leaf("id", "int64"),
        Collection("vals", Leaf("_0", "float32")),
    ])


def make_batch(schema, rng, n, id0=0):
    sizes = rng.poisson(5, n).astype(np.int64)
    vals = rng.uniform(0, 100, int(sizes.sum())).astype(np.float32)
    return ColumnBatch.from_arrays(
        schema, n,
        {"id": np.arange(id0, id0 + n), "vals": sizes, "vals._0": vals},
    )


def write_file(sink, opts, entries=4000, seed=0, batches=4):
    schema = vec_schema()
    rng = np.random.default_rng(seed)
    per = entries // batches
    with SequentialWriter(schema, sink, opts) as w:
        for i in range(batches):
            w.fill_batch(make_batch(schema, rng, per, id0=i * per))
        stats = w.stats
    return stats


BASE = dict(codec="none", cluster_bytes=1 << 16, page_size=8 * 1024)


# ---------------------------------------------------------------------------
# BufferPool unit behavior


def test_pool_power_of_two_classes():
    assert _class_bytes(1) == 4096          # minimum class
    assert _class_bytes(4096) == 4096
    assert _class_bytes(4097) == 8192
    assert _class_bytes(100_000) == 131072
    pool = BufferPool(limit_bytes=1 << 20)
    a = pool.take(5000)
    assert a.nbytes == 8192 and a.dtype == np.uint8


def test_pool_hit_miss_return_cycle():
    pool = BufferPool(limit_bytes=1 << 20)
    a = pool.take(10_000)
    assert pool.stats.pool_misses == 1
    pool.put(a)
    assert pool.stats.pool_returns == 1
    assert pool.resident_bytes == a.nbytes
    b = pool.take(10_000)
    assert b is a and pool.stats.pool_hits == 1
    assert pool.resident_bytes == 0
    # a different class does not hit
    c = pool.take(100_000)
    assert c is not a and pool.stats.pool_misses == 2


def test_pool_residency_bound_drops():
    pool = BufferPool(limit_bytes=8192)
    a, b = pool.take(8192), pool.take(8192)
    pool.put(a)
    pool.put(b)  # over the bound: dropped
    assert pool.stats.pool_drops == 1
    assert pool.resident_bytes == 8192
    assert pool.take(8192) is a


def test_pool_put_walks_views_to_base():
    pool = BufferPool(limit_bytes=1 << 20)
    a = pool.take(4096)
    view = memoryview(a.view(np.int64)[:100])
    pool.put(view)
    assert pool.take(4096) is a


def test_pool_rejects_foreign_and_odd_buffers():
    pool = BufferPool(limit_bytes=1 << 20)
    pool.put(np.empty(5000, np.uint8))   # non-power-of-two: never pooled
    assert pool.stats.pool_drops == 1
    pool.put(b"not an array")            # ignored entirely
    pool.put(None)
    assert pool.resident_bytes == 0


def test_pool_thread_safety_smoke():
    pool = BufferPool(limit_bytes=1 << 22)

    def worker():
        for _ in range(200):
            pool.put(pool.take(8192))

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    s = pool.stats
    assert s.pool_hits + s.pool_misses == 800
    assert s.pool_returns == 800


def test_column_buffer_draws_from_pool():
    pool = BufferPool(limit_bytes=1 << 20)
    buf = ColumnBuffer(np.int64, capacity=512, pool=pool)
    buf.extend(np.arange(512))
    first = buf.detach()
    np.testing.assert_array_equal(first, np.arange(512))
    pool.put(first)
    buf.extend(np.arange(10))
    # the replacement storage installed by detach() came from the pool,
    # and the recycled array backs the next detach
    assert pool.stats.pool_hits + pool.stats.pool_misses >= 2


# ---------------------------------------------------------------------------
# completion-driven recycling: the engine returns buffers only when the
# extent's last write has landed


class _GateSink(MemorySink):
    """Writes block until the test releases the gate."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()

    def pwrite(self, offset, data):
        assert self.gate.wait(10.0)
        super().pwrite(offset, data)


def test_buffer_not_recycled_while_queued_write_references_it():
    pool = BufferPool(limit_bytes=1 << 22)
    sink = _GateSink()
    engine = IOEngine(sink, workers=1, inflight_bytes=1 << 20,
                      buffer_pool=pool)
    arr = pool.take(8192)
    memoryview(arr)[:5] = b"hello"
    owner = Recyclable([arr])
    engine.admit(8192)
    engine.write_extent(0, [memoryview(arr)], 8192, owner=owner)
    time.sleep(0.05)  # the queued write is (blocked) in flight
    assert pool.take(8192) is not arr  # never handed out while referenced
    assert pool.resident_bytes == 0
    sink.gate.set()
    engine.drain()
    engine.close()
    # landed: the buffer is back in its class now
    assert pool.take(8192) is arr
    assert bytes(sink.buf[:5]) == b"hello"


def test_sync_write_recycles_after_completion():
    pool = BufferPool(limit_bytes=1 << 22)
    sink = MemorySink()
    engine = IOEngine(sink, buffer_pool=pool)
    arr = pool.take(4096)
    engine.write_extent(0, [memoryview(arr)[:4096]], 4096,
                        owner=Recyclable([arr]))
    assert pool.take(4096) is arr
    engine.close()


# ---------------------------------------------------------------------------
# ring submission: byte-identity, ordering, poisoning


def test_ring_write_behind_byte_identical_to_sync():
    a, b, c = MemorySink(), MemorySink(), MemorySink()
    write_file(a, WriteOptions(**BASE))  # synchronous reference
    write_file(b, WriteOptions(**BASE, io_inflight_bytes=1 << 20))
    write_file(c, WriteOptions(**BASE, io_inflight_bytes=1 << 20,
                               io_stripe_bytes=4096, pipelined_seal=True))
    assert bytes(a.buf) == bytes(b.buf)
    assert bytes(a.buf) == bytes(c.buf)


def test_ring_completion_ordering_vs_drain():
    """close() (via engine.drain) must not finalize before every queued
    ring write has landed."""
    sink = _GateSink()
    schema = vec_schema()
    rng = np.random.default_rng(3)
    opts = WriteOptions(**BASE, io_inflight_bytes=4 << 20, io_workers=2)
    sink.gate.set()  # the header write (writer construction) may pass
    w = SequentialWriter(schema, sink, opts)
    w.fill_batch(make_batch(schema, rng, 2000))
    sink.gate.clear()
    w.flush_cluster()  # queued behind the gate
    done = threading.Event()

    def closer():
        w.close()
        done.set()

    t = threading.Thread(target=closer)
    t.start()
    assert not done.wait(0.2)  # drain-before-footer is blocked on the gate
    sink.gate.set()
    t.join(10.0)
    assert done.is_set()
    r = RNTJReader(sink)
    assert r.n_entries == 2000
    np.testing.assert_array_equal(r.read_column("id"), np.arange(2000))


class _FailingSink(MemorySink):
    def __init__(self, fail_after: int):
        super().__init__()
        self.fail_after = fail_after
        self._writes = 0

    def pwrite(self, offset, data):
        self._writes += 1
        if self._writes > self.fail_after:
            raise IOError("injected ring failure")
        super().pwrite(offset, data)


def test_poisoning_through_emulated_ring():
    sink = _FailingSink(fail_after=1)  # header lands, clusters fail
    schema = vec_schema()
    rng = np.random.default_rng(5)
    w = SequentialWriter(schema, sink, WriteOptions(
        **BASE, io_inflight_bytes=4 << 20))
    w.fill_batch(make_batch(schema, rng, 2000))
    with pytest.raises(RuntimeError, match="NOT finalized") as ei:
        w.flush_cluster()
        w.close()
    assert isinstance(ei.value.__cause__, IOError)


def test_detached_buffers_survive_ring_write_behind_with_pool():
    """The PR-4 detach hazard, now with recycling in the loop: queued raw
    views must stay valid behind a slow sink while the SAME builder
    refills from the pool."""
    inner = MemorySink()
    slow = ThrottledSink(inner, bw=3e6)
    schema = vec_schema()
    rng = np.random.default_rng(7)
    opts = WriteOptions(codec="none", cluster_bytes=1 << 16,
                        io_inflight_bytes=4 << 20, pipelined_seal=True)
    with SequentialWriter(schema, slow, opts) as w:
        for i in range(8):
            w.fill_batch(make_batch(schema, rng, 500, id0=i * 500))
    r = RNTJReader(inner)
    np.testing.assert_array_equal(r.read_column("id"), np.arange(4000))


def test_steady_state_detach_hits_the_pool():
    sink = MemorySink()
    stats = write_file(sink, WriteOptions(**BASE), entries=12000, batches=12)
    d = stats.as_dict()
    assert d["pool_returns"] > 0
    assert d["pool_hits"] > 0  # later clusters recycled earlier buffers
    r = RNTJReader(sink)
    np.testing.assert_array_equal(r.read_column("id"), np.arange(12000))


# ---------------------------------------------------------------------------
# pooled merge and unbuffered page paths


def test_merge_raw_copy_uses_pool_and_stays_identical(tmp_path):
    paths = [str(tmp_path / f"in{i}.rntj") for i in range(3)]
    schema = vec_schema()
    rng = np.random.default_rng(11)
    for i, p in enumerate(paths):
        with SequentialWriter(schema, p, WriteOptions(**BASE)) as w:
            w.fill_batch(make_batch(schema, rng, 1000, id0=i * 1000))
    out_pool = str(tmp_path / "out_pool.rntj")
    out_plain = str(tmp_path / "out_plain.rntj")
    merge_files(paths, out_pool, WriteOptions(**BASE))
    merge_files(paths, out_plain, WriteOptions(**BASE, buffer_pool_bytes=0))
    with open(out_pool, "rb") as f1, open(out_plain, "rb") as f2:
        assert f1.read() == f2.read()
    r = RNTJReader(out_pool)
    np.testing.assert_array_equal(r.read_column("id"), np.arange(3000))
    r.close()


def test_unbuffered_pages_route_through_pool():
    sink = MemorySink()
    schema = vec_schema()
    rng = np.random.default_rng(13)
    opts = WriteOptions(codec="none", cluster_bytes=1 << 16, page_size=4096,
                        buffered=False)
    w = SequentialWriter(schema, sink, opts)
    for i in range(8):
        w.fill_batch(make_batch(schema, rng, 500, id0=i * 500))
    w.close()
    d = w.stats.as_dict()
    assert d["pool_returns"] > 0 and d["pool_hits"] > 0
    r = RNTJReader(sink)
    np.testing.assert_array_equal(r.read_column("id"), np.arange(4000))


def test_unbuffered_pool_off_byte_identical():
    a, b = MemorySink(), MemorySink()
    opts = dict(codec="none", cluster_bytes=1 << 16, page_size=4096,
                buffered=False)
    write_file(a, WriteOptions(**opts))
    write_file(b, WriteOptions(**opts, buffer_pool_bytes=0))
    assert bytes(a.buf) == bytes(b.buf)


def test_base_pread_into_raises_on_short_read():
    """A short read into a (possibly recycled) caller buffer must raise,
    never silently leave a stale tail."""
    from repro.core import Sink

    class ShortSink(Sink):
        def pread(self, offset, size):
            return b"x" * (size // 2)

        def readable(self):
            return True

    buf = np.zeros(64, np.uint8)
    with pytest.raises(EOFError, match="short read"):
        ShortSink().pread_into(0, memoryview(buf))


# ---------------------------------------------------------------------------
# reader-side pooling


def test_reader_recycle_buffers_round_trip():
    sink = MemorySink()
    write_file(sink, WriteOptions(**BASE), entries=8000, batches=8)
    ref = RNTJReader(sink)
    want = [cols[0].copy() for _, cols in ref.iter_clusters()]
    ref.close()
    r = RNTJReader(sink, options=ReadOptions(recycle_buffers=True,
                                             prefetch_clusters=0))
    got = []
    for (i, cols) in r.iter_clusters():
        got.append(cols[0].copy())  # valid only until the next iteration
    r.close()
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    d = r.stats.as_dict()
    assert d["pool_returns"] > 0
    assert d["pool_hits"] > 0  # later clusters decoded into recycled arrays


def test_reader_member_scratch_recycles():
    sink = MemorySink()
    write_file(sink, WriteOptions(codec="zlib", level=1,
                                  cluster_bytes=1 << 17, page_size=16 * 1024,
                                  codec_chunk_bytes=2 * 1024),
               entries=8000, batches=4)
    r = RNTJReader(sink, options=ReadOptions(decode_workers=2))
    total = sum(len(cols[0]) for _, cols in r.iter_clusters())
    assert total == 8000
    r.close()
    assert r.stats.as_dict()["pool_returns"] > 0


def test_read_column_ignores_recycle_option():
    """read_column holds views across clusters: recycle_buffers must not
    corrupt its output."""
    sink = MemorySink()
    write_file(sink, WriteOptions(**BASE), entries=8000, batches=8)
    r = RNTJReader(sink, options=ReadOptions(recycle_buffers=True))
    np.testing.assert_array_equal(r.read_column("id"), np.arange(8000))
    r.close()
