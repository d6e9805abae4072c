"""Codec-engine behaviour: registry errors, framed chunking, adaptive
per-column policy, per-column overrides, checksum edge cases, pool-worker
error propagation, and the Pallas byteshuffle dispatch."""

import threading
import zlib

import numpy as np
import pytest

from repro.core import (
    Collection, ColumnBatch, Leaf, ParallelWriter, RNTJReader, ReadOptions,
    Schema, SequentialWriter, WriteOptions,
)
from repro.core import compression as comp
from repro.core import encoding as E
from repro.core.container import MemorySink
from repro.core.pages import read_page


def vec_schema():
    return Schema([Leaf("id", "int64"), Collection("vals", Leaf("_0", "float32"))])


def make_batch(schema, rng, n, id0=0, compressible=False):
    sizes = rng.poisson(5, n).astype(np.int64)
    k = int(sizes.sum())
    if compressible:
        vals = (np.round(rng.gamma(2.0, 15.0, k) * 64) / 64).astype(np.float32)
    else:
        vals = rng.uniform(0, 100, k).astype(np.float32)
    return ColumnBatch.from_arrays(
        schema, n, {"id": np.arange(id0, id0 + n), "vals": sizes, "vals._0": vals}
    )


def roundtrip_ids(sink, n):
    r = RNTJReader(sink)
    try:
        np.testing.assert_array_equal(np.sort(r.read_column("id")), np.arange(n))
    finally:
        r.close()
    return r


# ---------------------------------------------------------------------------
# registry: errors and optional codecs


def test_unavailable_codec_raises_value_error_with_default_level():
    """Ids 4/5 must raise ValueError (not KeyError) even at level < 0 —
    the availability check precedes any level lookup."""
    for cid, pkg in [(comp.CODEC_LZ4, "lz4"), (comp.CODEC_ZSTD, "zstandard")]:
        if comp.is_available(cid):
            data = b"x" * 1000
            out = comp.compress(data, cid)  # installed: must round-trip
            assert comp.decompress(out, cid, len(data)) == data
        else:
            with pytest.raises(ValueError, match=pkg):
                comp.compress(b"x" * 1000, cid)
            with pytest.raises(ValueError, match=pkg):
                comp.decompress(b"x", cid, 1)


def test_unknown_codec_id_and_name():
    with pytest.raises(ValueError):
        comp.compress(b"x", 99, 1)
    with pytest.raises(ValueError):
        comp.codec_id("snappy")
    # reserved names always resolve to their stable ids
    assert comp.codec_id("lz4") == comp.CODEC_LZ4
    assert comp.codec_id("zstd") == comp.CODEC_ZSTD
    assert comp.codec_name(comp.CODEC_ZLIB) == "zlib"


# ---------------------------------------------------------------------------
# framed chunking


@pytest.mark.parametrize("codec", [comp.CODEC_ZLIB, comp.CODEC_LZMA, comp.CODEC_BZ2])
def test_chunked_members_roundtrip_and_crc(codec):
    rng = np.random.default_rng(0)
    data = rng.integers(0, 50, 300_000, dtype=np.uint8).tobytes()
    parts = comp.compress_parts(data, codec, -1, chunk_bytes=64 * 1024)
    assert len(parts) == 5
    payload = b"".join(parts)
    # the member loop reassembles the exact input
    assert comp.decompress(payload, codec, len(data)) == data
    # incremental member-CRC fold == whole-payload crc32
    assert comp.crc32_parts(parts) == zlib.crc32(payload)
    # single-member path unchanged
    whole = comp.compress(data, codec)
    assert comp.decompress(whole, codec, len(data)) == data


def test_chunk_ranges():
    assert comp.chunk_ranges(10, 0) == [(0, 10)]
    assert comp.chunk_ranges(10, 16) == [(0, 10)]
    assert comp.chunk_ranges(10, 4) == [(0, 4), (4, 8), (8, 10)]


def test_chunked_decompress_size_mismatch_raises():
    data = b"a" * 100_000
    payload = comp.compress(data, comp.CODEC_ZLIB, 1, chunk_bytes=16 * 1024)
    with pytest.raises(IOError, match="size mismatch"):
        comp.decompress(payload, comp.CODEC_ZLIB, len(data) + 1)


def test_chunked_file_roundtrip_and_legacy_page_reader():
    """Chunked pages must decode through the engine AND the unmodified
    page-at-a-time legacy path (read_page)."""
    schema = vec_schema()
    rng = np.random.default_rng(1)
    sink = MemorySink()
    opts = WriteOptions(codec="zlib", page_size=32 * 1024,
                        codec_chunk_bytes=4 * 1024, cluster_bytes=1 << 18)
    with SequentialWriter(schema, sink, opts) as w:
        for i in range(4):
            w.fill_batch(make_batch(schema, rng, 10_000, id0=i * 10_000,
                                    compressible=True))
    r = RNTJReader(sink)
    assert any(
        p.codec == comp.CODEC_ZLIB and p.uncompressed_size > 4 * 1024
        for cm in r.clusters for p in cm.pages
    ), "expected at least one chunk-framed page"
    np.testing.assert_array_equal(np.sort(r.read_column("id")),
                                  np.arange(40_000))
    # legacy page-at-a-time path over the same metadata
    for cm in r.clusters:
        for desc in cm.pages:
            col = r.schema.columns[desc.column]
            buf = sink.pread(desc.offset, desc.size)
            arr = read_page(buf, desc, col, verify=True)
            assert len(arr) == desc.n_elements
    r.close()


@pytest.mark.parametrize("adaptive", [False, True])
def test_pooled_seal_equals_serial_with_chunking(adaptive):
    """Chunk-framed + adaptive seals must stay byte-identical between the
    serial and pooled code paths (single producer)."""
    schema = vec_schema()

    def write(imt):
        rng = np.random.default_rng(7)
        sink = MemorySink()
        opts = WriteOptions(codec="zlib", page_size=16 * 1024,
                            codec_chunk_bytes=2 * 1024,
                            cluster_bytes=1 << 17, imt_workers=imt,
                            adaptive_codec=adaptive,
                            adaptive_sample_pages=2, adaptive_threshold=0.8)
        with SequentialWriter(schema, sink, opts) as w:
            for i in range(4):
                w.fill_batch(make_batch(schema, rng, 5_000, id0=i * 5_000))
        return sink

    assert bytes(write(0).buf) == bytes(write(3).buf)


# ---------------------------------------------------------------------------
# adaptive per-column policy


def test_adaptive_policy_downgrades_incompressible_column():
    schema = vec_schema()
    rng = np.random.default_rng(3)
    sink = MemorySink()
    opts = WriteOptions(codec="zlib", page_size=8 * 1024,
                        cluster_bytes=1 << 17, adaptive_codec=True,
                        adaptive_sample_pages=2, adaptive_threshold=0.8)
    w = SequentialWriter(schema, sink, opts)
    for i in range(8):
        w.fill_batch(make_batch(schema, rng, 5_000, id0=i * 5_000))
    w.close()
    vals_col = schema.column_of_path["vals._0"]
    id_col = schema.column_of_path["id"]
    assert w._policy.decision(vals_col) is False   # uniform floats: raw
    assert w._policy.decision(id_col) is True      # arange: keep zlib
    r = RNTJReader(sink)
    codecs_by_col = {}
    for cm in r.clusters:
        for p in cm.pages:
            codecs_by_col.setdefault(p.column, set()).add(p.codec)
    # after the trial, vals._0 pages are stored raw; id keeps zlib
    assert comp.CODEC_NONE in codecs_by_col[vals_col]
    assert codecs_by_col[id_col] == {comp.CODEC_ZLIB}
    np.testing.assert_array_equal(np.sort(r.read_column("id")),
                                  np.arange(40_000))
    # the per-codec breakdown attributes both codecs
    per = w.stats.as_dict()["per_codec"]
    assert "none" in per and "zlib" in per
    assert per["none"]["pages"] > 0 and per["zlib"]["pages"] > 0
    r.close()


def test_adaptive_policy_shared_across_parallel_producers():
    schema = vec_schema()
    sink = MemorySink()
    opts = WriteOptions(codec="zlib", page_size=8 * 1024,
                        cluster_bytes=1 << 16, adaptive_codec=True,
                        adaptive_sample_pages=2, adaptive_threshold=0.8)
    w = ParallelWriter(schema, sink, opts)

    def worker(tid):
        rng = np.random.default_rng(tid)
        ctx = w.create_fill_context()
        for i in range(4):
            ctx.fill_batch(make_batch(schema, rng, 2_000,
                                      id0=tid * 10**6 + i * 2_000))
        ctx.close()

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    w.close()
    assert w._policy.decision(schema.column_of_path["vals._0"]) is False
    r = RNTJReader(sink)
    assert r.n_entries == 4 * 4 * 2_000
    ids = r.read_column("id")
    assert len(ids) == r.n_entries
    r.close()


def test_codec_policy_unit():
    p = comp.CodecPolicy(2, sample_pages=2, threshold=0.5)
    assert p.decision(0) is None
    assert p.remaining_sample(0) == 2
    assert p.effective_codec(0, comp.CODEC_ZLIB) == comp.CODEC_ZLIB
    p.record(0, 100, 90)
    p.record(0, 100, 95)   # ratio 0.925 > 0.5 -> raw
    assert p.decision(0) is False
    assert p.effective_codec(0, comp.CODEC_ZLIB) == comp.CODEC_NONE
    assert p.remaining_sample(0) == 0
    p.record(1, 100, 10)
    p.record(1, 100, 10)   # ratio 0.1 <= 0.5 -> keep
    assert p.decision(1) is True
    assert p.effective_codec(1, comp.CODEC_ZLIB) == comp.CODEC_ZLIB
    d = p.as_dict()
    assert d["columns"][0]["keep"] is False


# ---------------------------------------------------------------------------
# per-column codec overrides


def test_write_options_column_codec_override():
    schema = vec_schema()
    rng = np.random.default_rng(5)
    sink = MemorySink()
    opts = WriteOptions(codec="zlib", page_size=8 * 1024,
                        column_codecs={"vals._0": "none",
                                       "vals": ("bz2", 5)})
    with SequentialWriter(schema, sink, opts) as w:
        w.fill_batch(make_batch(schema, rng, 20_000, compressible=True))
    r = RNTJReader(sink)
    by_col = {}
    for cm in r.clusters:
        for p in cm.pages:
            by_col.setdefault(r.schema.columns[p.column].path, set()).add(p.codec)
    assert by_col["vals._0"] == {comp.CODEC_NONE}
    assert comp.CODEC_BZ2 in by_col["vals"]
    assert comp.CODEC_ZLIB in by_col["id"]
    np.testing.assert_array_equal(np.sort(r.read_column("id")),
                                  np.arange(20_000))
    r.close()


def test_column_spec_codec_override():
    schema = vec_schema().set_column_codec("vals._0", "none")
    assert schema.columns[schema.column_of_path["vals._0"]].codec == "none"
    rng = np.random.default_rng(6)
    sink = MemorySink()
    with SequentialWriter(schema, sink, WriteOptions(codec="zlib")) as w:
        w.fill_batch(make_batch(schema, rng, 10_000))
    r = RNTJReader(sink)
    vals_col = schema.column_of_path["vals._0"]
    assert all(p.codec == comp.CODEC_NONE
               for cm in r.clusters for p in cm.pages if p.column == vals_col)
    np.testing.assert_array_equal(np.sort(r.read_column("id")),
                                  np.arange(10_000))
    r.close()
    # overrides survive the spec (de)serialization used by tools
    spec = schema.columns[vals_col]
    assert type(spec).from_dict(spec.to_dict()) == spec


def test_precondition_off_roundtrips_and_header_flag():
    schema = vec_schema()
    rng = np.random.default_rng(8)
    sink = MemorySink()
    with SequentialWriter(schema, sink,
                          WriteOptions(precondition=False)) as w:
        w.fill_batch(make_batch(schema, rng, 10_000))
    r = RNTJReader(sink)
    assert r.options["precondition"] is False
    # the parsed schema dropped the derived encodings
    assert all(c.encoding == "none" for c in r.schema.columns)
    np.testing.assert_array_equal(np.sort(r.read_column("id")),
                                  np.arange(10_000))
    rng = np.random.default_rng(8)
    expect = make_batch(schema, rng, 10_000)
    np.testing.assert_array_equal(r.read_column("vals._0"), expect.data[2])
    r.close()


# ---------------------------------------------------------------------------
# checksum edge cases


def test_checksum_false_pages_roundtrip():
    schema = vec_schema()
    rng = np.random.default_rng(9)
    sink = MemorySink()
    opts = WriteOptions(codec="zlib", checksum=False, page_size=8 * 1024,
                        codec_chunk_bytes=2 * 1024)
    with SequentialWriter(schema, sink, opts) as w:
        w.fill_batch(make_batch(schema, rng, 10_000))
    r = RNTJReader(sink)  # verify_checksums=True must be a no-op here
    assert all(p.checksum == 0 for cm in r.clusters for p in cm.pages)
    np.testing.assert_array_equal(np.sort(r.read_column("id")),
                                  np.arange(10_000))
    r.close()


def _chunked_file(checksum=True):
    schema = vec_schema()
    rng = np.random.default_rng(10)
    sink = MemorySink()
    opts = WriteOptions(codec="zlib", page_size=32 * 1024,
                        codec_chunk_bytes=4 * 1024, checksum=checksum,
                        cluster_bytes=1 << 19)
    with SequentialWriter(schema, sink, opts) as w:
        for i in range(4):
            w.fill_batch(make_batch(schema, rng, 10_000, id0=i * 10_000,
                                    compressible=True))
    return schema, sink


def _find_chunked_page(reader):
    for cm in reader.clusters:
        for p in cm.pages:
            if p.codec == comp.CODEC_ZLIB and p.uncompressed_size > 4 * 1024:
                return p
    raise AssertionError("no chunk-framed page found")


def test_mid_page_chunk_corruption_detected():
    """Flipping a byte inside a later member of a chunked page must fail
    the (incrementally folded) page checksum."""
    schema, sink = _chunked_file(checksum=True)
    r = RNTJReader(sink)
    p = _find_chunked_page(r)
    sink.buf[p.offset + p.size // 2] ^= 0xFF  # mid-page: not the 1st member
    with pytest.raises(IOError, match="checksum mismatch"):
        for _ci, _cols in r.iter_clusters(columns=[p.column]):
            pass
    r.close()


def test_corrupt_chunk_without_checksum_fails_decode():
    """With checksum=False the member loop itself must surface corruption
    (zlib error or size mismatch) — from decode-pool workers too."""
    schema, sink = _chunked_file(checksum=False)
    r = RNTJReader(sink, options=ReadOptions(decode_workers=2))
    p = _find_chunked_page(r)
    sink.buf[p.offset + p.size // 2] ^= 0xFF
    with pytest.raises(Exception):
        for _ci, _cols in r.iter_clusters(columns=[p.column]):
            pass
    r.close()


# ---------------------------------------------------------------------------
# errors propagating out of pool workers


def test_decompressed_size_mismatch_propagates_from_decode_pool():
    schema, sink = _chunked_file(checksum=False)
    r = RNTJReader(sink, options=ReadOptions(decode_workers=2))
    p = _find_chunked_page(r)
    p.uncompressed_size += 8  # poison the in-memory descriptor
    with pytest.raises(IOError, match="size mismatch"):
        for _ci, _cols in r.iter_clusters(columns=[p.column]):
            pass
    r.close()


def test_compress_error_propagates_from_writer_pool_sequential():
    schema = vec_schema()
    rng = np.random.default_rng(11)
    w = SequentialWriter(schema, MemorySink(),
                         WriteOptions(imt_workers=2))
    w.fill_batch(make_batch(schema, rng, 2_000))
    w._builder.codec = 99  # pool workers must surface the ValueError
    with pytest.raises(ValueError):
        w.flush_cluster()
    with pytest.raises(RuntimeError, match="NOT finalized"):
        w.close()


def test_compress_error_propagates_from_writer_pool_parallel():
    schema = vec_schema()
    rng = np.random.default_rng(12)
    w = ParallelWriter(schema, MemorySink(),
                       WriteOptions(imt_workers=2, pipelined_seal=True))
    ctx = w.create_fill_context()
    ctx.fill_batch(make_batch(schema, rng, 2_000))
    ctx.builder.codec = 99
    with pytest.raises(Exception):
        ctx.close()
    with pytest.raises(RuntimeError, match="NOT finalized"):
        w.close()


# ---------------------------------------------------------------------------
# header-recorded encodings: merge + schema reuse must never mis-decode


def _write_tmp(tmp_path, name, opts, n=5_000, seed=20):
    schema = vec_schema()
    rng = np.random.default_rng(seed)
    path = str(tmp_path / name)
    with SequentialWriter(schema, path, opts) as w:
        w.fill_batch(make_batch(schema, rng, n))
    rng = np.random.default_rng(seed)
    return path, make_batch(schema, rng, n)


def test_merge_raw_path_honors_source_encodings(tmp_path):
    """A precondition=False source raw-merged without a target codec must
    read back exactly (the output header records the real encodings)."""
    from repro.core import merge_files

    src, expect = _write_tmp(tmp_path, "src.rntj",
                             WriteOptions(codec="none", precondition=False))
    out = str(tmp_path / "out.rntj")
    merge_files([src], out)
    with RNTJReader(out) as r:
        np.testing.assert_array_equal(r.read_column("id"), expect.data[0])
        np.testing.assert_array_equal(r.read_column("vals._0"), expect.data[2])
        # verbatim copy: still stored with no preconditioning
        assert all(c.encoding == "none" for c in r.schema.columns)


def test_merge_reencode_path_on_encoding_mismatch(tmp_path):
    """Merging a precondition=False source with a preconditioned one must
    re-encode (not raw-copy) the mismatching input."""
    from repro.core import merge_files

    a, ea = _write_tmp(tmp_path, "a.rntj", WriteOptions(codec="zlib"), seed=21)
    b, eb = _write_tmp(tmp_path, "b.rntj",
                       WriteOptions(codec="zlib", precondition=False), seed=22)
    out = str(tmp_path / "out.rntj")
    merge_files([a, b], out, options=WriteOptions(codec="zlib"))
    with RNTJReader(out) as r:
        got = np.sort(r.read_column("id"))
        want = np.sort(np.concatenate([ea.data[0], eb.data[0]]))
        np.testing.assert_array_equal(got, want)
        vals = np.sort(r.read_column("vals._0"))
        np.testing.assert_array_equal(
            vals, np.sort(np.concatenate([ea.data[2], eb.data[2]]))
        )


def test_parsed_schema_reuse_for_new_writer(tmp_path):
    """Writing with a schema parsed from a precondition=False file must
    produce a self-consistent file (header records the ENC_NONE specs)."""
    src, expect = _write_tmp(tmp_path, "src.rntj",
                             WriteOptions(codec="zlib", precondition=False))
    with RNTJReader(src) as r:
        reused = r.schema
    sink = MemorySink()
    with SequentialWriter(reused, sink, WriteOptions(codec="zlib")) as w:
        rng = np.random.default_rng(20)
        w.fill_batch(make_batch(reused, rng, 5_000))
    with RNTJReader(sink) as r2:
        np.testing.assert_array_equal(r2.read_column("id"), expect.data[0])
        np.testing.assert_array_equal(r2.read_column("vals._0"),
                                      expect.data[2])


def test_unknown_column_codecs_path_raises():
    schema = vec_schema()
    with pytest.raises(KeyError, match="vals.0"):
        SequentialWriter(schema, MemorySink(),
                         WriteOptions(column_codecs={"vals.0": "none"}))


def test_unbuffered_per_codec_time_attributed():
    schema = vec_schema()
    rng = np.random.default_rng(23)
    sink = MemorySink()
    opts = WriteOptions(codec="zlib", buffered=False, page_size=8 * 1024,
                        cluster_bytes=1 << 18)
    with ParallelWriter(schema, sink, opts) as w:
        ctx = w.create_fill_context()
        for i in range(4):
            ctx.fill_batch(make_batch(schema, rng, 5_000, id0=i * 5_000))
        ctx.close()
    per = w.stats.as_dict()["per_codec"]
    assert per["zlib"]["pages"] > 0 and per["zlib"]["ms"] > 0


# ---------------------------------------------------------------------------
# reader per-codec stats


def test_reader_per_codec_breakdown():
    schema = vec_schema()
    rng = np.random.default_rng(13)
    sink = MemorySink()
    opts = WriteOptions(codec="zlib", adaptive_codec=True,
                        adaptive_sample_pages=1, adaptive_threshold=0.8,
                        page_size=8 * 1024, cluster_bytes=1 << 17)
    with SequentialWriter(schema, sink, opts) as w:
        for i in range(4):
            w.fill_batch(make_batch(schema, rng, 5_000, id0=i * 5_000))
    r = RNTJReader(sink)
    for _ci, _cols in r.iter_clusters():
        pass
    per = r.stats.as_dict()["per_codec"]
    assert "zlib" in per and "none" in per
    assert per["zlib"]["bytes_out"] > per["zlib"]["bytes_in"]  # it decompressed
    total_pages = sum(v["pages"] for v in per.values())
    assert total_pages == r.stats.pages
    r.close()


# ---------------------------------------------------------------------------
# Pallas byteshuffle dispatch


def test_forced_pallas_byteshuffle_matches_numpy(monkeypatch):
    """REPRO_SHUFFLE_BACKEND=pallas must be bit-identical to the numpy
    split (runs the kernel in interpret mode on CPU backends)."""
    pytest.importorskip("jax")
    monkeypatch.setattr(E.BYTESHUFFLE, "backend", "pallas")
    before = E.BYTESHUFFLE.calls
    rng = np.random.default_rng(14)
    for dtype, per in [(np.float32, 64), (np.int64, 100), (np.float64, 33)]:
        arr = rng.uniform(0, 100, 257).astype(dtype)
        got = bytes(E.precondition_column_pages(arr, "split", per))
        monkeypatch.setattr(E.BYTESHUFFLE, "backend", "numpy")
        want = bytes(E.precondition_column_pages(arr, "split", per))
        monkeypatch.setattr(E.BYTESHUFFLE, "backend", "pallas")
        assert got == want, f"pallas byteshuffle differs for {dtype}"
    assert E.BYTESHUFFLE.calls == before + 3  # the kernel actually ran


def test_shuffle_auto_backend_stays_numpy_on_cpu():
    """The auto dispatch must not engage on CPU-only jax (and never pay a
    cold jax import inside the seal path)."""
    rng = np.random.default_rng(15)
    arr = rng.uniform(0, 1, 200_000).astype(np.float64)  # above threshold
    out = bytes(E.precondition_column_pages(arr, "split", 8192))
    ref = bytes(E.split_encode(arr[:8192]))
    assert out[: len(ref)] == ref
