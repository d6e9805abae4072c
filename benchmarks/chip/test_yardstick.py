"""Self-checks of the yardstick: the trace reduction on a small trace
recorded on a TPU v5e (``testdata/loader_small.xplane.pb``, 0.2 s of the
loader cell), the operation and byte counts, and the generators'
distributions.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import common  # noqa: E402
import counts  # noqa: E402
import generators  # noqa: E402

TRACE = HERE / "testdata" / "loader_small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return common.trace_mod.from_profile(ProfileData.from_file(str(TRACE)))


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_window_is_the_benchmark_span(summary):
    assert summary.window_s == pytest.approx(0.201866911, abs=1e-9)
    assert len(summary.ops()) > 100
    assert all(e.module for e in summary.ops())


def test_busy_is_the_union_of_device_ops(summary):
    """A sweep over start/end points, written independently of
    ``union_ns``, gives the same busy time."""
    lo, hi = summary.window
    points = []
    for e in summary.ops():
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            points += [(s, 1), (t, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    depth, busy, since = 0, 0.0, None
    for x, d in points:
        if depth == 0 and d == 1:
            since = x
        depth += d
        if depth == 0:
            busy += x - since
    assert summary.busy_s == pytest.approx(busy / 1e9, rel=1e-12)
    idle = 1 - summary.busy_s / summary.window_s
    assert 0.5 < idle < 1.0


def test_union_and_gaps_by_hand():
    ivals = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 45)]
    assert common.trace_mod.union_ns(ivals, 0, 50) == 15 + 11 + 5
    assert common.trace_mod.union_ns(ivals, 8, 42) == 7 + 11 + 2
    assert common.trace_mod.gaps(ivals, 0, 50) == [(15, 20), (31, 40), (45, 50)]


def test_breakdown_is_bounded(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["device_ops"]) <= summary.busy_s * 1.0001 + 1e-9
    assert b["idle_gaps"][0][0].startswith("bench.")


def test_decode_kernel_bytes_by_hand(summary):
    roof = _metric("decode_kernel_roofline.loader")
    calls = [e for e in summary.ops() if "unsplit_pages" in e.name.split(" = ")[0]]
    assert calls
    for e in calls:
        operand = e.name.split("custom-call(u8[", 1)[1].split("]", 1)[0]
        p, nb, per = (int(x) for x in operand.split(","))
        assert roof.cost(e) == {"flops": 0.0, "bytes": 2.0 * p * nb * per}
    # the recorded calls: 66 and 65 pages of 16384 int32 tokens
    assert sorted(roof.cost(e)["bytes"] for e in calls) == [
        2 * 65 * 4 * 16384, 2 * 66 * 4 * 16384]
    # the offsets decode: one page of 4096 offsets as 32 rows of 128 lanes,
    # scanned by four byte planes of two (32,128)@(128,128) and two
    # (32,32)@(32,128) matmuls
    offs = [e for e in summary.ops()
            if e.name.startswith("%decode_offset_pages")]
    assert offs
    for e in offs:
        assert "custom-call(u8[1,8,32,128]" in e.name
        assert roof.cost(e) == {
            "flops": 4.0 * (2 * 2 * 32 * 128 * 128 + 2 * 2 * 32 * 32 * 128),
            "bytes": 4096.0 * 4 + 4096.0 * 4}
    others = [e for e in summary.ops() if e not in calls and e not in offs]
    assert all(roof.cost(e) is None for e in others)


def test_roofline_share_is_below_one(summary):
    from peaks import peaks_for

    roof = _metric("decode_kernel_roofline.loader")
    p = peaks_for("TPU v5 lite")
    for e in summary.ops():
        c = roof.cost(e)
        if c is not None:
            least, bound = counts.roofline_seconds(c, p)
            want = "compute" if e.name.startswith("%decode_offset") else "memory"
            assert bound == want and least < e.seconds


def test_offsets_decode_cost_by_hand():
    # 3 pages of 8192 uint64 offsets; 64-row tiles of 128 lanes: 1 tile/page
    c = counts.offsets_decode_cost(3, 8192, rows=64)
    assert c["bytes"] == 3 * 8192 * 8
    per_tile = 4 * (2 * 2 * 64 * 128 * 128 + 2 * 2 * 64 * 64 * 128)
    assert c["flops"] == 3 * per_tile


def test_smollm_train_flops_by_hand():
    import json

    cfg = json.loads((HERE / "configs" / "smollm-360m.json").read_text())
    per_layer = 960 * 960 + 2 * 960 * 320 + 960 * 960 + 3 * 960 * 2560
    matmul = 32 * per_layer + 960 * 49152           # tied head still multiplies
    attn = 6 * 32 * 2048 * 960                      # causal QK^T and PV, fwd+bwd
    assert counts.llama_train_flops_per_token(cfg, 2048) == 6 * matmul + attn
    assert 6 * matmul + attn == 2_548_039_680


def test_corpus_distribution():
    c = generators.synth_corpus(12345, 3_000_000, 49152)
    lens = c.lengths
    assert int(lens.sum()) >= 3_000_000 and int(lens[:-1].sum()) < 3_000_000
    assert lens.min() >= 8
    assert np.median(lens) == pytest.approx(512, rel=0.05)
    assert np.std(np.log(lens[lens > 8])) == pytest.approx(0.6, rel=0.05)
    assert c.tokens.dtype == np.int32 and len(c.tokens) == lens.sum()
    assert 0 <= c.tokens.min() and c.tokens.max() < 49152
    # Zipf phrases: the most frequent token pair repeats far more than
    # uniform draws over 49152 ids would allow
    _, counts_ = np.unique(c.tokens, return_counts=True)
    assert counts_.max() > 100 * len(c.tokens) / 49152


def test_corpus_is_seeded():
    a = generators.synth_corpus(7, 100_000, 1000)
    b = generators.synth_corpus(7, 100_000, 1000)
    d = generators.synth_corpus(8, 100_000, 1000)
    assert np.array_equal(a.tokens, b.tokens) and np.array_equal(a.lengths, b.lengths)
    assert not np.array_equal(a.lengths[:50], d.lengths[:50])


def test_corpus_lengths_fixed_by_length_seed():
    """With ``length_seed`` every seed gives the same lengths (so the same
    file shapes) and tokens of its own."""
    a = generators.synth_corpus(7, 100_000, 1000, length_seed=0)
    d = generators.synth_corpus(2 ** 33 + 8, 100_000, 1000, length_seed=0)
    assert np.array_equal(a.lengths, d.lengths)
    assert np.mean(a.tokens != d.tokens) > 0.5


def test_corpus_shapes_do_not_follow_the_seed(tmp_path):
    """The cells' ingest puts the same documents in each cluster whatever
    the seed and however the producer threads run."""
    from drivers.corpus import ingest
    from repro.core import RNTJReader

    data = {"producers": 4, "batch_docs": 16, "codec": "zlib", "level": 1,
            "cluster_bytes": 1 << 16}
    shapes = set()
    for seed in (1, 2 ** 31 + 5, 99):
        c = generators.synth_corpus(seed, 100_000, 1000, length_seed=0)
        path = tmp_path / f"{seed}.rntj"
        ingest(c, path, data)
        r = RNTJReader(str(path))
        try:
            c_id = r.schema.column_of_path["doc_id"]
            shapes.add(frozenset(
                tuple(r.read_cluster(ci, [c_id])[c_id].tolist())
                for ci in range(r.n_clusters)))
        finally:
            r.close()
    assert len(shapes) == 1 and len(next(iter(shapes))) > 4


def test_events_distribution():
    ev = generators.synth_events(np.random.default_rng(3), 200_000, id0=10)
    assert ev.sizes.mean() == pytest.approx(5.0, rel=0.01)
    assert ev.sizes.var() == pytest.approx(5.0, rel=0.03)
    assert ev.values.dtype == np.float32 and len(ev.values) == ev.sizes.sum()
    assert 0.0 <= ev.values.min() and ev.values.max() < 100.0
    assert ev.values.mean() == pytest.approx(50.0, rel=0.01)
    assert np.array_equal(ev.ids, np.arange(10, 200_010))
