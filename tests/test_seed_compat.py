"""Byte identity across commit modes, and compatibility with the seed reader.

Every commit mode must produce the same file as the assembled commit, and
the vendored seed reader (``_legacy_seed_reader.py``, the page-at-a-time
read path of the original format) must decode it to the input:

* the commit matrix: assembled, scatter (with and without the buffer
  pool), synchronous striped, write-behind through the submission ring
  (with and without stripes) and the pipelined seal, on an in-memory and
  a file sink;
* every registered codec with split preconditioning and chunk-framed
  members;
* the skim (``skim_partitions``, ``imt`` at one thread, whose writers run
  write-behind) against the same loop driven by the seed reader.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from _legacy_seed_reader import SeedRNTJReader

from repro.core import (
    Collection,
    ColumnBatch,
    FileSink,
    Leaf,
    MemorySink,
    RNTJReader,
    Schema,
    SequentialWriter,
    WriteOptions,
)
from repro.core import compression as comp
from repro.skim import make_agc_dataset, skim_partitions
from repro.skim.engine import OUT_SCHEMA, Cuts, _skim_cluster_arrays

EVENT_SCHEMA = Schema([
    Leaf("id", "int64"),
    Collection("vals", Leaf("_0", "float32")),
])

PAGE = 16 * 1024
STRIPE = 16 * 1024


def event_batches(make, n_batches=8, per_batch=2000, seed=0):
    rng = np.random.default_rng(seed)
    return [make(rng, per_batch, i * per_batch) for i in range(n_batches)]


def synth_events(rng, n, id0):
    """The paper's synthetic events: Poisson(5) uniform floats per id."""
    sizes = rng.poisson(5, n).astype(np.int64)
    vals = rng.uniform(0, 100, int(sizes.sum())).astype(np.float32)
    return ColumnBatch.from_arrays(EVENT_SCHEMA, n, {
        "id": np.arange(id0, id0 + n), "vals": sizes, "vals._0": vals})


def write(sink, schema, batches, opts):
    w = SequentialWriter(schema, sink, opts)
    for b in batches:
        w.fill_batch(b)
    w.close()


def assert_seed_decodes(source, schema, batches):
    """The seed reader decodes every cluster of ``source`` to the input
    batches (one cluster per batch; offsets are cluster-local ends) and
    agrees with the read engine."""
    seed, engine = SeedRNTJReader(source), RNTJReader(source)
    try:
        assert seed.n_entries == sum(b.n_entries for b in batches)
        assert seed.n_clusters == engine.n_clusters == len(batches)
        for ci, batch in enumerate(batches):
            got, want = seed.read_cluster(ci), engine.read_cluster(ci)
            assert sorted(got) == sorted(want) == list(range(schema.n_columns))
            for col in schema.columns:
                expected = batch.data[col.index]
                if col.kind == 1:  # offset column: sizes -> end offsets
                    expected = np.cumsum(expected)
                np.testing.assert_array_equal(got[col.index], expected)
                np.testing.assert_array_equal(want[col.index], expected)
    finally:
        seed.close()
        engine.close()


# ---------------------------------------------------------------------------
# the commit matrix

#: codec none and no preconditioning: raw pages are zero-copy views of the
#: builders' buffers, which the scatter, pooled and queued commits must
#: keep alive until their writes land
BASE = dict(codec="none", precondition=False, page_size=PAGE,
            cluster_bytes=64 * 1024)

MODES = {
    "assembled": dict(scatter_commit=False),
    "scatter": dict(scatter_commit=True),
    "scatter_nopool": dict(scatter_commit=True, buffer_pool_bytes=0),
    "striped": dict(io_stripe_bytes=STRIPE, io_workers=2),
    "write_behind": dict(io_inflight_bytes=256 * 1024, io_workers=2),
    "write_behind_striped": dict(io_inflight_bytes=256 * 1024,
                                 io_stripe_bytes=STRIPE, io_workers=2),
    "pipelined_seal": dict(pipelined_seal=True),
}


@pytest.fixture(scope="module")
def events():
    return event_batches(synth_events)


@pytest.fixture(scope="module")
def reference(events):
    sink = MemorySink()
    write(sink, EVENT_SCHEMA, events,
          WriteOptions(**BASE, **MODES["assembled"]))
    return bytes(sink.buf)


@pytest.mark.parametrize("sink_kind", ["memory", "file"])
@pytest.mark.parametrize("mode", list(MODES))
def test_commit_mode_byte_identical_and_seed_readable(
        mode, sink_kind, events, reference, tmp_path):
    opts = WriteOptions(**BASE, **MODES[mode])
    if sink_kind == "memory":
        sink = MemorySink()
        write(sink, EVENT_SCHEMA, events, opts)
        data, source = bytes(sink.buf), sink
    else:
        path = str(tmp_path / f"{mode}.rntj")
        write(FileSink(path), EVENT_SCHEMA, events, opts)
        data, source = Path(path).read_bytes(), path
    assert data == reference
    assert_seed_decodes(source, EVENT_SCHEMA, events)


# ---------------------------------------------------------------------------
# codecs: split preconditioning and chunk-framed members

CODEC_SCHEMA = Schema([
    Leaf("t", "int64"),
    Leaf("e", "float64"),
    Collection("hits", Leaf("_0", "float32")),
])

CODECS = [comp.codec_name(c) for c in (
    comp.CODEC_NONE, comp.CODEC_ZLIB, comp.CODEC_LZMA, comp.CODEC_BZ2,
    comp.CODEC_LZ4, comp.CODEC_ZSTD) if comp.is_available(c)]


def detector_events(rng, n, id0):
    """Timestamps, quantized energies and hits: columns that split
    preconditioning and the codecs work on."""
    t = np.arange(id0, id0 + n, dtype=np.int64) * 40_000 + rng.integers(
        0, 25_000, n)
    e = np.round(rng.gamma(2.0, 15.0, n) * 64) / 64
    sizes = rng.poisson(5, n).astype(np.int64)
    hits = (np.round(rng.gamma(2.0, 15.0, int(sizes.sum())) * 64) / 64
            ).astype(np.float32)
    return ColumnBatch.from_arrays(CODEC_SCHEMA, n, {
        "t": t, "e": e, "hits": sizes, "hits._0": hits})


@pytest.mark.parametrize("codec", CODECS)
def test_codec_split_chunked_seed_readable(codec, tmp_path):
    batches = event_batches(detector_events, n_batches=4)
    path = str(tmp_path / f"{codec}.rntj")
    write(FileSink(path), CODEC_SCHEMA, batches, WriteOptions(
        codec=codec, page_size=PAGE, cluster_bytes=64 * 1024,
        precondition=True, codec_chunk_bytes=4 * 1024, imt_workers=2))
    assert_seed_decodes(path, CODEC_SCHEMA, batches)


# ---------------------------------------------------------------------------
# the skim


def seed_imt_skim(parts, out_dir: Path, cuts: Cuts) -> int:
    """``skim_partitions(strategy="imt", n_threads=1)`` with the seed
    reader on the read side and a synchronous writer of the same format
    options on the write side."""
    opts = WriteOptions(codec="zlib", level=1, cluster_bytes=2 * 1024 * 1024,
                        imt_workers=1)
    out_dir.mkdir()
    kept = 0
    for part, files in parts.items():
        w = SequentialWriter(OUT_SCHEMA, str(out_dir / f"skim_{part}.rntj"),
                             opts)
        for f in files:
            r = SeedRNTJReader(f)
            for ci in range(r.n_clusters):
                batch = _skim_cluster_arrays(r.schema, r.read_cluster(ci),
                                             r.clusters[ci].n_entries, cuts)
                if batch is not None:
                    w.fill_batch(batch)
                    kept += batch.n_entries
            r.close()
        w.close()
    return kept


def test_skim_matches_seed_reader_skim(tmp_path):
    cuts = Cuts()
    parts = make_agc_dataset(
        str(tmp_path / "agc"), n_partitions=2, files_per_partition=2,
        events_per_file=4000, seed=0,
        options=WriteOptions(codec="zlib", level=1, cluster_bytes=64 * 1024))
    kept_seed = seed_imt_skim(parts, tmp_path / "seed", cuts)
    res = skim_partitions(parts, str(tmp_path / "engine"), "imt", n_threads=1,
                          cuts=cuts)
    assert kept_seed > 0
    assert res["kept_events"] == kept_seed
    for part in parts:
        name = f"skim_{part}.rntj"
        assert ((tmp_path / "engine" / name).read_bytes()
                == (tmp_path / "seed" / name).read_bytes())
