"""Write hot-path behaviour: ColumnBuffer, unified pooled seal, pipelined
sealing, unbuffered drain edge cases, and the Pallas offsets dispatch."""

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import (
    Collection, ColumnBatch, ColumnBuffer, Leaf, ParallelWriter, RNTJReader,
    Schema, SequentialWriter, WriteOptions,
)
from repro.core.cluster import ClusterBuilder
from repro.core.container import MemorySink
from repro.core import encoding as E


def vec_schema():
    return Schema([Leaf("id", "int64"), Collection("vals", Leaf("_0", "float32"))])


def make_batch(schema, rng, n, id0=0):
    sizes = rng.poisson(5, n).astype(np.int64)
    vals = rng.uniform(0, 100, int(sizes.sum())).astype(np.float32)
    return ColumnBatch.from_arrays(
        schema, n, {"id": np.arange(id0, id0 + n), "vals": sizes, "vals._0": vals}
    )


# ---------------------------------------------------------------------------
# ColumnBuffer


def test_column_buffer_growth_and_views():
    b = ColumnBuffer(np.int64, capacity=4)
    for i in range(10):
        b.extend(np.arange(i * 100, i * 100 + 7))
    assert len(b) == 70
    assert b.nbytes == 70 * 8
    v = b.view()
    assert v.base is not None  # zero-copy: a view, not a fresh array
    np.testing.assert_array_equal(
        v, np.concatenate([np.arange(i * 100, i * 100 + 7) for i in range(10)])
    )
    np.testing.assert_array_equal(b.view(7, 14), np.arange(100, 107))


def test_column_buffer_reserve_and_reset_keeps_storage():
    b = ColumnBuffer(np.int64, capacity=8)
    tail = b.reserve(5)
    tail[:] = np.arange(5)
    np.testing.assert_array_equal(b.view(), np.arange(5))
    cap = b.capacity
    b.reset()
    assert len(b) == 0 and b.capacity == cap
    b.extend(np.arange(3))  # refill reuses storage
    assert b.capacity == cap
    np.testing.assert_array_equal(b.view(), np.arange(3))


def test_column_buffer_empty_view_dtype():
    b = ColumnBuffer(np.float32)
    v = b.view()
    assert len(v) == 0 and v.dtype == np.float32


# ---------------------------------------------------------------------------
# unified seal code path: serial == pooled, builders reusable


def test_seal_pooled_equals_serial():
    schema = vec_schema()
    rng = np.random.default_rng(11)
    batch = make_batch(schema, rng, 500)
    b1 = ClusterBuilder(schema, page_size=512, codec=1)
    b2 = ClusterBuilder(schema, page_size=512, codec=1)
    b1.fill_batch(batch)
    b2.fill_batch(batch)
    sealed_serial = b1.seal()
    with ThreadPoolExecutor(max_workers=3) as pool:
        sealed_pooled = b2.seal(pool)
    assert bytes(sealed_serial.blob) == bytes(sealed_pooled.blob)
    assert sealed_serial.n_elements == sealed_pooled.n_elements
    assert [(p.column, p.offset, p.size, p.checksum) for p in sealed_serial.pages] \
        == [(p.column, p.offset, p.size, p.checksum) for p in sealed_pooled.pages]


def test_builder_reuse_across_clusters():
    schema = vec_schema()
    rng = np.random.default_rng(5)
    builder = ClusterBuilder(schema, page_size=512, codec=1)
    batch = make_batch(schema, rng, 200)
    builder.fill_batch(batch)
    first = builder.seal()
    # refill the SAME builder: offsets must restart cluster-relative
    builder.fill_batch(batch)
    second = builder.seal()
    assert bytes(first.blob) == bytes(second.blob)


# ---------------------------------------------------------------------------
# edge cases: empty flush, partial pages, never-full columns


def test_empty_cluster_flush_is_noop():
    schema = vec_schema()
    sink = MemorySink()
    with SequentialWriter(schema, sink, WriteOptions()) as w:
        w.flush_cluster()
        w.flush_cluster()
    r = RNTJReader(sink)
    assert r.n_entries == 0
    assert r.n_clusters == 0
    assert len(r.read_column("id")) == 0


def test_empty_parallel_context_close():
    schema = vec_schema()
    sink = MemorySink()
    with ParallelWriter(schema, sink, WriteOptions(pipelined_seal=True)) as w:
        ctx = w.create_fill_context()
        ctx.close()
    assert RNTJReader(sink).n_entries == 0


def test_final_partial_page_roundtrip():
    """Element counts that do not divide the page size leave a final
    partial page per column."""
    schema = vec_schema()
    rng = np.random.default_rng(2)
    sink = MemorySink()
    # page 512 B -> 64 int64 / 128 float32 per page; 100 entries won't align
    with SequentialWriter(schema, sink, WriteOptions(page_size=512)) as w:
        w.fill_batch(make_batch(schema, rng, 100))
    r = RNTJReader(sink)
    assert r.n_entries == 100
    rng = np.random.default_rng(2)
    expect = make_batch(schema, rng, 100)
    np.testing.assert_array_equal(r.read_column("id"), expect.data[0])
    np.testing.assert_array_equal(r.read_column("vals._0"), expect.data[2])


def test_unbuffered_column_never_fills_a_page():
    """A column whose elements never reach one full page must be emitted
    entirely by drain_rest at cluster finalization."""
    schema = vec_schema()
    rng = np.random.default_rng(3)
    sink = MemorySink()
    opts = WriteOptions(buffered=False, page_size=64 * 1024, cluster_bytes=1 << 30)
    with ParallelWriter(schema, sink, opts) as w:
        ctx = w.create_fill_context()
        ctx.fill_batch(make_batch(schema, rng, 50))  # far below one page
        ctx.close()
    r = RNTJReader(sink)
    assert r.n_entries == 50
    rng = np.random.default_rng(3)
    expect = make_batch(schema, rng, 50)
    np.testing.assert_array_equal(r.read_column("id"), expect.data[0])
    np.testing.assert_array_equal(r.read_column("vals._0"), expect.data[2])


def test_unbuffered_drain_interleaves_full_and_partial_pages():
    schema = vec_schema()
    rng = np.random.default_rng(4)
    sink = MemorySink()
    opts = WriteOptions(buffered=False, page_size=256, cluster_bytes=16 * 1024)
    with ParallelWriter(schema, sink, opts) as w:
        ctx = w.create_fill_context()
        for i in range(8):
            ctx.fill_batch(make_batch(schema, rng, 300, id0=i * 1000))
        ctx.close()
    r = RNTJReader(sink)
    assert r.n_entries == 8 * 300
    ids = np.sort(r.read_column("id"))
    expect = np.sort(np.concatenate([np.arange(i * 1000, i * 1000 + 300)
                                     for i in range(8)]))
    np.testing.assert_array_equal(ids, expect)


# ---------------------------------------------------------------------------
# pipelined vs synchronous sealing


def _write_sequential(schema, opts, n_batches=12, per=500):
    sink = MemorySink()
    with SequentialWriter(schema, sink, opts) as w:
        rng = np.random.default_rng(9)
        for i in range(n_batches):
            w.fill_batch(make_batch(schema, rng, per, id0=i * per))
    return sink


@pytest.mark.parametrize("imt", [0, 2])
def test_pipelined_seal_bytes_identical_single_producer(imt):
    """One producer, same cluster boundaries: the pipelined file must be
    byte-for-byte identical to the synchronous one."""
    schema = vec_schema()
    base = dict(cluster_bytes=1 << 16, imt_workers=imt)
    sync = _write_sequential(schema, WriteOptions(**base))
    pipe = _write_sequential(schema, WriteOptions(**base, pipelined_seal=True))
    assert bytes(sync.buf) == bytes(pipe.buf)


def test_pipelined_parallel_same_reader_output():
    """Many producers: cluster commit order may differ, but the logical
    reader output must match the synchronous writer's."""
    schema = vec_schema()

    def write(pipelined):
        sink = MemorySink()
        opts = WriteOptions(cluster_bytes=1 << 14, pipelined_seal=pipelined)
        w = ParallelWriter(schema, sink, opts)

        def worker(tid):
            rng = np.random.default_rng(tid)
            ctx = w.create_fill_context()
            for i in range(4):
                ctx.fill_batch(make_batch(schema, rng, 250, id0=tid * 10**6 + i * 250))
            ctx.close()

        ts = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        w.close()
        return sink

    sync_sink = write(False)
    pipe_sink = write(True)
    rs, rp = RNTJReader(sync_sink), RNTJReader(pipe_sink)
    assert rs.n_entries == rp.n_entries == 4000
    for colpath in ("id", "vals._0"):
        np.testing.assert_array_equal(
            np.sort(rs.read_column(colpath)), np.sort(rp.read_column(colpath))
        )
    # same total payload modulo cluster order
    assert rs.sink.size == rp.sink.size


class _FailingSink(MemorySink):
    """Fails cluster-sized writes after the first N, like a full disk."""

    def __init__(self, allowed_writes):
        super().__init__()
        self._allowed = allowed_writes

    def pwrite(self, offset, data):
        if len(data) > 256:  # let header/metadata through, fail blobs
            if self._allowed <= 0:
                raise IOError("injected ENOSPC")
            self._allowed -= 1
        super().pwrite(offset, data)


def test_failed_commit_poisons_finalization():
    """A failed blob write must prevent close() from emitting a footer
    that references bytes that never landed."""
    schema = vec_schema()
    sink = _FailingSink(allowed_writes=1)
    w = ParallelWriter(schema, sink,
                       WriteOptions(cluster_bytes=1 << 13, pipelined_seal=True))
    ctx = w.create_fill_context()
    rng = np.random.default_rng(0)
    with pytest.raises(Exception):
        for i in range(40):
            ctx.fill_batch(make_batch(schema, rng, 200, id0=i * 200))
        ctx.close()
    with pytest.raises(RuntimeError, match="NOT finalized"):
        w.close()
    # no valid footer/anchor: the reader must refuse the file
    with pytest.raises(Exception):
        RNTJReader(sink)


def test_failed_context_close_does_not_silently_drop_data():
    """ctx.close() failing must not mark the context closed; the writer's
    close surfaces the error instead of finalizing without the data."""
    schema = vec_schema()
    sink = MemorySink()
    w = ParallelWriter(schema, sink, WriteOptions())
    ctx = w.create_fill_context()
    rng = np.random.default_rng(1)
    ctx.fill_batch(make_batch(schema, rng, 50))
    ctx.builder.codec = 99  # seal will fail
    with pytest.raises(Exception):
        ctx.close()
    assert not ctx._ctx_closed  # retryable, not silently dropped
    with pytest.raises(RuntimeError, match="NOT finalized"):
        w.close()


def test_pipelined_background_error_surfaces():
    """Exceptions raised during a background seal propagate to the producer."""
    schema = vec_schema()
    sink = MemorySink()
    w = ParallelWriter(schema, sink, WriteOptions(pipelined_seal=True))
    ctx = w.create_fill_context()
    rng = np.random.default_rng(0)
    ctx.fill_batch(make_batch(schema, rng, 10))
    ctx.builder.codec = 99  # unknown codec id -> seal must fail
    with pytest.raises(Exception):
        ctx.flush_cluster()
        ctx._sealer.wait()
    w.sink.close()


# ---------------------------------------------------------------------------
# stats phase breakdown


def test_stats_phase_breakdown_reported():
    schema = vec_schema()
    sink = MemorySink()
    with SequentialWriter(schema, sink, WriteOptions(cluster_bytes=1 << 15)) as w:
        rng = np.random.default_rng(1)
        for i in range(4):
            w.fill_batch(make_batch(schema, rng, 500, id0=i * 500))
    d = w.stats.as_dict()
    phases = d["phases_ms"]
    assert set(phases) == {"fill", "seal", "compress", "commit", "io"}
    assert phases["fill"] > 0 and phases["seal"] > 0 and phases["compress"] > 0
    assert d["seal_ms"] >= 0 and d["commit_ms"] > 0
    # compress is the per-page CPU sum inside seal: same order of magnitude
    assert phases["compress"] <= phases["seal"] * 1.5 + 1.0


# ---------------------------------------------------------------------------
# column-batched preconditioning (the serial-seal fast path)


@pytest.mark.parametrize("per", [1, 3, 64, 100, 1000])
@pytest.mark.parametrize("enc,dtype", [
    ("none", np.uint8), ("none", np.float32),
    ("split", np.float32), ("split", np.int64), ("split", np.float16),
    ("dzs", np.int64),
])
def test_precondition_column_pages_matches_per_page(per, enc, dtype):
    rng = np.random.default_rng(42)
    n = 257
    if enc == "dzs":
        arr = np.cumsum(rng.poisson(5, n)).astype(np.int64)
    elif np.dtype(dtype).kind == "f":
        arr = rng.uniform(0, 100, n).astype(dtype)
    else:
        arr = rng.integers(0, 200, n).astype(dtype)
    batched = E.precondition_column_pages(arr, enc, per)
    itemb = arr.dtype.itemsize
    for start in range(0, n, per):
        count = min(per, n - start)
        got = bytes(batched[start * itemb : (start + count) * itemb])
        want = bytes(E.precondition_buffer(arr[start : start + count], enc))
        assert got == want, f"page at {start} differs"


def test_precondition_column_pages_empty():
    assert len(E.precondition_column_pages(np.empty(0, np.int64), "dzs", 64)) == 0


# ---------------------------------------------------------------------------
# integrate_sizes dispatch (numpy reference vs in-place vs Pallas kernel)


def test_integrate_sizes_matches_cumsum_and_base():
    rng = np.random.default_rng(0)
    sizes = rng.poisson(5, 1000).astype(np.int64)
    np.testing.assert_array_equal(
        E.integrate_sizes(sizes), np.cumsum(sizes, dtype=np.int64)
    )
    out = np.empty(1000, np.int64)
    res = E.integrate_sizes(sizes, base=17, out=out)
    assert res is out
    np.testing.assert_array_equal(out, np.cumsum(sizes, dtype=np.int64) + 17)


def test_integrate_sizes_empty():
    assert len(E.integrate_sizes(np.empty(0, np.int64))) == 0


def test_integrate_sizes_forced_pallas_matches_numpy(monkeypatch):
    """REPRO_OFFSETS_BACKEND=pallas must be bit-identical to numpy (runs
    the kernel in interpret mode on CPU backends)."""
    jax = pytest.importorskip("jax")
    monkeypatch.setattr(E.OFFSETS_SCAN, "backend", "pallas")
    rng = np.random.default_rng(1)
    sizes = rng.poisson(7, 300).astype(np.int64)
    got = E.integrate_sizes(sizes, base=5)
    np.testing.assert_array_equal(got, np.cumsum(sizes, dtype=np.int64) + 5)
    assert got.dtype == np.int64
