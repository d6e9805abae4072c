"""Program spans (``repro.core.stats.span``): kept only while a profiler
session is active, nested per thread, bounded, written into the
profiler's own trace, and placed at the train loop's, loader's, reader's
and writer's layer boundaries; the benchmark's span metrics read them."""

import importlib.util
import sys
import threading
import time
from collections import Counter, defaultdict
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core import (Collection, ColumnBatch, Leaf, ParallelWriter,
                        Schema, WriteOptions, stats)
from repro.core.stats import SpanRecord, span
from repro.pipeline import PackedLoader, ingest_corpus, synth_corpus

METRICS = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "metrics"


@pytest.fixture
def recording(monkeypatch):
    """Spans record as under a profiler session, without starting one."""
    assert stats._bind_jax()
    monkeypatch.setattr(stats, "_is_enabled", lambda: True)
    stats.clear()
    yield
    stats.clear()


def by_name(recs):
    out = defaultdict(list)
    for r in recs:
        out[r.name].append(r)
    return out


def children(recs, parent):
    return [r for r in recs if r.parent == parent.id]


# -- the facility ------------------------------------------------------------


def test_nothing_recorded_without_a_profiler_session():
    stats.clear()
    assert not stats.profiler_active()
    with span("outer", 1):
        with span("inner") as s:
            pass
    with span("timed", timed=True) as t:
        time.sleep(0.001)
    assert stats.records() == []
    assert s is not None and not hasattr(s, "t0")
    assert t.ns >= 1_000_000   # a timed span keeps its clock regardless


def test_nesting_parent_and_self_time(recording):
    with span("outer", 7, timed=True) as o:
        time.sleep(0.002)
        with span("inner", 7):
            time.sleep(0.004)
        with span("inner", 8):
            with span("leaf"):
                time.sleep(0.001)
    recs = stats.records()
    assert [r.name for r in recs] == ["inner", "leaf", "inner", "outer"]
    named = by_name(recs)
    outer = named["outer"][0]
    assert outer.parent is None and outer.key == 7
    assert (outer.start_ns, outer.end_ns) == (o.t0, o.t1)
    assert [r.key for r in children(recs, outer)] == [7, 8]
    assert named["leaf"][0].parent == named["inner"][1].id
    inside = sum(r.ns for r in children(recs, outer))
    assert inside >= 5_000_000 and outer.ns - inside >= 2_000_000
    assert all(r.thread == threading.get_ident() for r in recs)
    for child in children(recs, outer):
        assert outer.start_ns <= child.start_ns <= child.end_ns <= outer.end_ns


def test_record_is_bounded_and_counts_drops(recording, monkeypatch):
    assert stats._log._items.maxlen == stats.SPAN_LOG_CAPACITY == 1 << 18
    monkeypatch.setattr(stats, "_log", stats._SpanLog(16))
    for k in range(40):
        with span("s", k):
            pass
    recs = stats.records()
    assert len(recs) == 16 and stats.dropped() == 24
    assert [r.key for r in recs] == list(range(24, 40))   # the newest kept
    stats.clear()
    assert stats.records() == [] and stats.dropped() == 0


def test_eight_threads_record_safely(recording):
    n_threads, per = 8, 1500
    errors = []

    def work(t):
        try:
            for k in range(per):
                with span("writer.fill", (t, k)):
                    with span("writer.seal", (t, k)):
                        pass
        except BaseException as e:   # reported on the main thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts) and not errors
    recs = stats.records()
    assert len(recs) == 2 * n_threads * per and stats.dropped() == 0
    assert len({r.id for r in recs}) == len(recs)
    fills = {r.id: r for r in recs if r.name == "writer.fill"}
    for r in recs:
        if r.name == "writer.seal":
            parent = fills[r.parent]
            assert parent.key == r.key and parent.thread == r.thread
        else:
            assert r.parent is None


def test_compile_recorded_under_the_open_span(recording):
    with span("outer"):
        jax.jit(lambda x: x * 3 + 1)(np.arange(5.0)).block_until_ready()
    recs = stats.records()
    outer = by_name(recs)["outer"][0]
    compiles = [r for r in recs if r.name == "compile"]
    assert compiles and all(r.parent == outer.id for r in compiles)
    assert any("lambda" in str(r.key) for r in compiles)
    assert all(outer.start_ns <= r.start_ns <= r.end_ns <= outer.end_ns
               for r in compiles)


def _xplane_spans(log_dir):
    from jax.profiler import ProfileData

    path = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("rntj."):
                    out.append((e.name[len("rntj."):], float(e.start_ns),
                                float(e.duration_ns), (plane.name, li)))
    return out


def test_profiler_trace_holds_the_same_spans(tmp_path):
    stats.clear()
    with jax.profiler.trace(str(tmp_path)):
        assert stats.profiler_active()
        for k in (1, 2):
            with span("train.step", k, step=True):
                with span("train.place", k):
                    time.sleep(0.003)
                with span("train.loss_wait", k):
                    time.sleep(0.006)
    assert not stats.profiler_active()
    recs = stats.records()
    stats.clear()
    trace = _xplane_spans(tmp_path)
    assert Counter(n for n, *_ in trace) == Counter(r.name for r in recs) \
        == Counter({"train.step": 2, "train.place": 2, "train.loss_wait": 2})

    def ordered(items):
        return sorted(items, key=lambda x: x[1])

    mine = ordered([(r.name, r.start_ns, r.ns) for r in recs])
    theirs = ordered([(n, s, d) for n, s, d, _ in trace])
    for (n1, _, d1), (n2, _, d2) in zip(mine, theirs):
        assert n1 == n2
        assert abs(d1 - d2) <= max(0.05 * d2, 50_000), (n1, d1, d2)
    # the same nesting: each child lies inside its step on the trace's line
    steps = [t for t in trace if t[0] == "train.step"]
    for name, s, d, line in trace:
        if name == "train.step":
            continue
        host = [st for st in steps
                if st[3] == line and st[1] <= s and s + d <= st[1] + st[2]]
        assert len(host) == 1, name


# -- the layer boundaries ----------------------------------------------------


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    p = str(tmp_path_factory.mktemp("spans") / "c.rntj")
    ingest_corpus(synth_corpus(300, seed=0, mean_len=80, vocab=256), p,
                  n_workers=2,
                  options=WriteOptions(codec="zlib", level=1,
                                       cluster_bytes=16 * 1024))
    return p


def _tiny_loop(corpus, ckpt_dir, loader=None):
    from repro.configs import get_arch
    from repro.launch.mesh import make_local_mesh
    from repro.models import build
    from repro.train import LoopConfig, TrainLoop, make_optimizer

    cfg = get_arch("smollm-360m").with_(
        name="tiny", n_layers=1, d_model=32, n_heads=2, n_kv_heads=1,
        head_dim=16, d_ff=64, vocab_size=256, remat=False)
    return TrainLoop(
        build(cfg), make_local_mesh(),
        loader or PackedLoader(corpus, batch=2, seq_len=16, device="device"),
        ckpt_dir,
        config=LoopConfig(steps=3, ckpt_every=1000, log_every=1000,
                          ckpt_async=False),
        optimizer=make_optimizer(peak_lr=1e-3, warmup=1, total=100))


STEP_CHILDREN = ["train.next_batch", "train.place", "train.dispatch",
                 "train.loss_wait", "train.bookkeeping"]


def test_train_loop_records_each_step_with_its_children(corpus, tmp_path,
                                                        recording):
    loop = _tiny_loop(corpus, str(tmp_path / "ck"))
    hist = loop.run(3)
    recs = stats.records()
    steps = by_name(recs)["train.step"]
    assert [s.key for s in steps] == [1, 2, 3]
    for s, ev in zip(steps, hist):
        kids = children(recs, s)
        assert [k.name for k in kids] == STEP_CHILDREN
        assert all(k.key == s.key for k in kids)
        # wall_s: the step span's start to the end of its loss wait
        assert ev.wall_s == (kids[3].end_ns - s.start_ns) / 1e9
    # the loader's and reader's spans sit under the batch fetch
    nexts = by_name(recs)["loader.next"]
    assert len(nexts) == 3
    assert {r.parent for r in nexts} == {
        k.id for s in steps for k in children(recs, s)
        if k.name == "train.next_batch"}


class _StallingLoader:
    """A loader whose batches arrive ``delay`` seconds late."""

    def __init__(self, inner) -> None:
        self.inner, self.delay = inner, 0.0

    def batches(self):
        for b in self.inner.batches():
            time.sleep(self.delay)
            yield b

    def state(self):
        return self.inner.state()

    def load_state(self, state):
        self.inner.load_state(state)


def test_straggler_detection_sees_a_loader_stall(corpus, tmp_path):
    """The step's wall time covers its input: a loader stall before a
    fast step is a straggler."""
    feed = _StallingLoader(
        PackedLoader(corpus, batch=2, seq_len=16, device="device"))
    events = []
    loop = _tiny_loop(corpus, str(tmp_path / "st"), feed)
    loop.on_straggler = events.append
    loop.run(10)   # compile, then a baseline
    seen = len(events)
    feed.delay = max(0.3, 10 * float(np.median(loop._step_times)))
    loop.run(1)
    assert len(events) == seen + 1 and events[-1].step == 11
    assert events[-1].straggler and events[-1].wall_s >= feed.delay


def test_step_times_bounded_to_the_median_window(corpus, tmp_path):
    from repro.train.loop import STRAGGLER_WINDOW

    loop = _tiny_loop(corpus, str(tmp_path / "bw"))
    loop.run(STRAGGLER_WINDOW + 5)
    assert len(loop._step_times) == STRAGGLER_WINDOW
    assert list(loop._step_times) == [e.wall_s for e in
                                      loop.history[-STRAGGLER_WINDOW:]]


def test_device_loader_records_batches_and_refills(corpus, recording):
    ld = PackedLoader(corpus, batch=2, seq_len=64, device="device")
    assert ld.reader.n_clusters >= 2
    gen = ld.batches()
    for _ in range(400):   # two epochs and more
        next(gen)
    ld.close()
    recs = stats.records()
    named = by_name(recs)
    assert [r.key for r in named["loader.next"]] == list(range(400))
    refills = named["loader.refill"]
    assert len(refills) >= 2
    assert [r.key for r in refills] == list(range(len(refills)))
    nexts = {r.id for r in named["loader.next"]}
    assert all(r.parent in nexts for r in refills)
    refill_ids = {r.id for r in refills}
    for name in ("reader.wait", "reader.decode"):
        assert named[name] and all(r.parent in refill_ids for r in named[name])
    staged = named["reader.stage"]   # on the prefetch thread
    assert staged and all(r.thread != threading.get_ident() for r in staged)
    assert {r.key for r in named["reader.decode"]} <= {r.key for r in staged}
    assert ld.reader.stats.wait_ns == sum(r.ns for r in named["reader.wait"])


def _events_batch(schema, rng, n, id0):
    sizes = rng.poisson(5, n).astype(np.int64)
    vals = rng.uniform(0, 100, int(sizes.sum())).astype(np.float32)
    return ColumnBatch.from_arrays(
        schema, n, {"id": np.arange(id0, id0 + n), "vals": sizes,
                    "vals._0": vals})


def test_parallel_writer_records_fill_seal_commit_close(tmp_path, recording):
    schema = Schema([Leaf("id", "int64"),
                     Collection("vals", Leaf("_0", "float32"))])
    w = ParallelWriter(schema, str(tmp_path / "w.rntj"),
                       WriteOptions(cluster_bytes=8192))
    barrier = threading.Barrier(2)

    def producer(p):
        rng = np.random.default_rng(p)
        ctx = w.create_fill_context()
        barrier.wait(timeout=30)
        for k in range(5):   # 3 fills a cluster: one left for close()
            ctx.fill_batch(_events_batch(schema, rng, 100, p * 10_000 + k * 100))

    ts = [threading.Thread(target=producer, args=(p,)) for p in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in ts)
    named = by_name(stats.records())
    # fill_ns is fed at each fill's end: true before close
    assert w.stats.fill_ns == sum(r.ns for r in named["writer.fill"]) > 0
    w.close()
    recs = stats.records()
    named = by_name(recs)
    assert sorted(r.key for r in named["writer.fill"]) == sorted(
        (p, k) for p in range(2) for k in range(5))
    seals = named["writer.seal"]
    assert seals and sorted(r.key for r in seals) == sorted(
        r.key for r in named["writer.commit"])
    assert w.stats.seal_ns == sum(r.ns for r in seals)
    assert w.stats.commit_ns == sum(r.ns for r in named["writer.commit"])
    (close,) = named["writer.close"]
    kids = [r.name for r in children(recs, close)]
    assert kids[-3:] == ["writer.drain", "writer.finalize", "writer.fsync"]
    assert set(kids[:-3]) <= {"writer.seal", "writer.commit"} and kids[:-3]


def _drop_profiler_module(monkeypatch):
    import jax._src.lib as lib

    monkeypatch.delattr(lib, "_profiler")
    monkeypatch.setitem(sys.modules, "jax._src.lib._profiler", None)


def _drop_session_check(monkeypatch):
    from jax._src.lib import _profiler

    monkeypatch.delattr(_profiler, "TraceMe")


@pytest.mark.parametrize("drop", [_drop_profiler_module, _drop_session_check])
def test_a_jax_without_the_session_check_still_writes(drop, tmp_path,
                                                      monkeypatch):
    from repro.core import RNTJReader

    monkeypatch.setattr(stats, "_is_enabled", None)
    drop(monkeypatch)
    with pytest.warns(RuntimeWarning, match="program spans are off"):
        assert not stats.profiler_active()
    stats.clear()
    schema = Schema([Leaf("id", "int64"),
                     Collection("vals", Leaf("_0", "float32"))])
    path = str(tmp_path / "w.rntj")
    w = ParallelWriter(schema, path, WriteOptions(cluster_bytes=8192))

    def producer(p):
        rng = np.random.default_rng(p)
        ctx = w.create_fill_context()
        for k in range(4):
            ctx.fill_batch(_events_batch(schema, rng, 100, p * 10_000 + k * 100))

    ts = [threading.Thread(target=producer, args=(p,)) for p in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    w.close()
    assert stats.records() == [] and w.stats.fill_ns > 0
    with RNTJReader(path) as r:
        ids = np.sort(r.read_column("id"))
    assert np.array_equal(ids, np.sort(np.concatenate(
        [np.arange(p * 10_000, p * 10_000 + 400) for p in range(2)])))


def test_sequential_writer_feeds_fill_ns_per_fill(tmp_path):
    from repro.core import SequentialWriter

    schema = Schema([Leaf("id", "int64"),
                     Collection("vals", Leaf("_0", "float32"))])
    w = SequentialWriter(schema, str(tmp_path / "s.rntj"),
                         WriteOptions(cluster_bytes=1 << 20))
    w.fill_batch(_events_batch(schema, np.random.default_rng(0), 200, 0))
    first = w.stats.fill_ns
    assert first > 0
    w.fill({"id": 7, "vals": [1.5, 2.5]})
    assert w.stats.fill_ns > first
    w.close()


# -- the benchmark's span metrics ------------------------------------------------


def _load_metric(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _rec(id, name, start_ms, end_ms, parent=None, key=None):
    return SpanRecord(id, name, key, int(start_ms * 1e6), int(end_ms * 1e6),
                      parent, 1)


SYNTHETIC = [
    # three train steps: 10 ms each, loss waits of 8, 7 and 9 ms; the
    # fourth was cut by the deadline and has no loss wait
    _rec(2, "train.loss_wait", 2, 10, 1), _rec(1, "train.step", 0, 10),
    _rec(4, "train.loss_wait", 13, 20, 3), _rec(3, "train.step", 10, 20),
    _rec(6, "train.loss_wait", 21, 30, 5), _rec(5, "train.step", 20, 30),
    _rec(7, "train.step", 30, 31),
    # loader batches of 1, 2 and 12 ms, the last holding a 9.5 ms refill
    _rec(10, "loader.next", 40, 41), _rec(11, "loader.next", 41, 43),
    _rec(13, "loader.refill", 43, 52.5, 12), _rec(14, "loader.refill", 52.5, 54.5, 12),
    _rec(12, "loader.next", 43, 55),
    # two producers' fills (3 + 1 + 4 ms) and two closes
    _rec(20, "writer.fill", 60, 63), _rec(21, "writer.fill", 60, 61),
    _rec(22, "writer.fill", 62, 66),
    _rec(23, "writer.close", 70, 70.5), _rec(24, "writer.close", 80, 1279.5),
]
EXPECTED = {
    "step_host_ms.train": 2.0,                 # median of 2, 3, 1
    "loader_host_ms_per_batch.loader": 1.0,    # median of 1, 2, 12 - 11.5
    "refill_ms_per_cluster.loader": 5.75,      # (9.5 + 2) / 2
    "fill_share.write": 0.5,                   # 8 ms / (2 x (2 - 1.2) s)
    "close_s.write": 1.1995,
}


def _ctx():
    return SimpleNamespace(trace=SimpleNamespace(window_s=2.0),
                           traffic={"producers": 2}, window={})


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_span_metric_on_a_synthetic_record(metric, monkeypatch):
    monkeypatch.setattr(stats, "records", lambda: list(SYNTHETIC))
    assert _load_metric(metric)(_ctx()) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("close_s", [0.05, 0.9, 3.0])
def test_fill_share_ignores_the_close(close_s, monkeypatch):
    # the same 1 s of filling by 2 producers, then a close of any length
    recs = [_rec(1, "writer.fill", 0, 600), _rec(2, "writer.fill", 0, 400),
            _rec(3, "writer.close", 1000, 1000 + close_s * 1e3)]
    monkeypatch.setattr(stats, "records", lambda: recs)
    ctx = SimpleNamespace(trace=SimpleNamespace(window_s=1.0 + close_s),
                          traffic={"producers": 2}, window={})
    assert _load_metric("fill_share.write")(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_span_metric_empty_record_reads_none(metric, monkeypatch):
    monkeypatch.setattr(stats, "records", lambda: [])
    assert _load_metric(metric)(_ctx()) is None
    # a program without span records (an older one) reads None too
    monkeypatch.delattr(stats, "records")
    assert _load_metric(metric)(_ctx()) is None
