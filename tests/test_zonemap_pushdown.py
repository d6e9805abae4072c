"""Zone-map + predicate-pushdown suite (DESIGN.md §11).

Core property, checked by hand-built cases and by randomized
(hypothesis-shimmed) schemas/predicates alike: a pruned filtered read is
**exactly** a full scan followed by the predicate — never a subset, never
a superset — while reading no more pages than the unpruned path.  The
regression half pins the compat surface: files written without zone maps
read unpruned with no warnings, new files stay readable by the vendored
seed reader, merges preserve or recompute the stats, recovery drops them
with an explicit reason instead of serving unattested bounds, and the
skim strategies produce byte-identical outputs pruned vs. unpruned.
"""

import math
import os
import warnings
from pathlib import Path

import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st
from _legacy_seed_reader import SeedRNTJReader

from repro.core import (
    Collection,
    ColumnBatch,
    F,
    Leaf,
    MemorySink,
    ParallelWriter,
    ReadOptions,
    Record,
    RNTJReader,
    Schema,
    SequentialWriter,
    WriteOptions,
    merge_files,
    recompose_entries,
    recover_container,
    write_entries,
)
from repro.core import metadata as md
from repro.core.filter import (
    EvalContext,
    T_FALSE,
    T_MAYBE,
    T_TRUE,
    Zone,
    required_columns,
)

# a page/cluster geometry small enough that modest datasets produce many
# pages per column and several clusters per file
SMALL = dict(page_size=256, cluster_bytes=16 * 1024, codec="none")


def _norm(v):
    """Recursively normalize recomposed entries for equality (NaN-safe)."""
    if isinstance(v, dict):
        return {k: _norm(x) for k, x in v.items()}
    if isinstance(v, (list, tuple, np.ndarray)):
        return [_norm(x) for x in v]
    if isinstance(v, (float, np.floating)):
        f = float(v)
        return "NaN" if math.isnan(f) else f
    if isinstance(v, (int, np.integer, bool, np.bool_)):
        return int(v)
    return v


def _filtered_scan(source, expr, prune, fields=None):
    """-> (normalized matching entries, pages read, stats) for one scan."""
    r = RNTJReader(source, options=ReadOptions(filter=expr, prune=prune))
    try:
        ents = [_norm(e) for e in r.iter_filtered_entries(fields)]
        return ents, r.stats.pages, r.stats
    finally:
        r.close()


def _assert_pruned_equals_full(source, expr, fields=None, expect_prune=None):
    """The tentpole property: pruned ≡ full-scan-then-filter, fewer pages."""
    got, pages_pruned, stats = _filtered_scan(source, expr, True, fields)
    ref, pages_full, _ = _filtered_scan(source, expr, False, fields)
    assert got == ref
    assert pages_pruned <= pages_full
    if expect_prune is not None:
        # zone pruning manifests as skipped clusters or fewer pages read
        # than the unpruned scan (late materialization happens in both)
        pruned = stats.clusters_pruned > 0 or pages_pruned < pages_full
        assert pruned == expect_prune
    return got, stats


# ---------------------------------------------------------------------------
# zone-evaluation unit tests (tri-state logic on hand-built zones)


class TestZoneEval:
    SCHEMA = Schema([Leaf("x", "float64"), Collection("c", Leaf("_0", "float64"))])

    def _z(self, lo, hi, nulls=0, count=8, nested=False):
        return Zone(lo=lo, hi=hi, nulls=nulls, count=count, nested=nested)

    def test_cmp_tristate(self):
        e = F("x")
        z = {"x": self._z(10.0, 20.0)}
        assert (e > 5.0).zone_eval(z) == T_TRUE
        assert (e > 25.0).zone_eval(z) == T_FALSE
        assert (e > 15.0).zone_eval(z) == T_MAYBE
        assert (e < 25.0).zone_eval(z) == T_TRUE
        assert (e < 5.0).zone_eval(z) == T_FALSE
        assert (e == 30.0).zone_eval(z) == T_FALSE
        assert (e == 15.0).zone_eval(z) == T_MAYBE
        # eq is only definitely true when the zone is a single point
        assert (e == 7.0).zone_eval({"x": self._z(7.0, 7.0)}) == T_TRUE
        assert (e != 30.0).zone_eval(z) == T_TRUE
        assert e.between(12.0, 13.0).zone_eval(z) == T_MAYBE
        assert e.between(30.0, 40.0).zone_eval(z) == T_FALSE
        assert e.between(0.0, 100.0).zone_eval(z) == T_TRUE

    def test_null_checks(self):
        e = F("x")
        assert e.is_null().zone_eval({"x": self._z(1.0, 2.0, nulls=0)}) == T_FALSE
        assert e.is_null().zone_eval({"x": self._z(None, None, nulls=8)}) == T_TRUE
        assert e.is_null().zone_eval({"x": self._z(1.0, 2.0, nulls=3)}) == T_MAYBE
        assert e.not_null().zone_eval({"x": self._z(None, None, nulls=8)}) == T_FALSE

    def test_all_nan_zone_never_matches_cmp(self):
        z = {"x": self._z(None, None, nulls=8)}  # every element NaN
        for expr in (F("x") > 0.0, F("x") < 0.0, F("x") == 0.0):
            assert expr.zone_eval(z) == T_FALSE
        # IEEE: NaN != c is TRUE elementwise, and zone_eval agrees
        assert (F("x") != 0.0).zone_eval(z) == T_TRUE

    def test_nan_constant_is_false(self):
        z = {"x": self._z(1.0, 2.0)}
        assert (F("x") == float("nan")).zone_eval(z) == T_FALSE
        assert (F("x") > float("nan")).zone_eval(z) == T_FALSE

    def test_nested_atom_never_definitely_true(self):
        # existential semantics: a nested zone covering the constant still
        # says nothing definite about ANY single entry — must stay MAYBE,
        # else NOT over it would wrongly prune
        z = {"c._0": self._z(10.0, 20.0, nested=True)}
        assert (F("c._0") > 5.0).zone_eval(z) == T_MAYBE
        assert (F("c._0") > 25.0).zone_eval(z) == T_FALSE
        assert (~(F("c._0") > 5.0)).zone_eval(z) == T_MAYBE

    def test_kleene_connectives(self):
        zt = {"x": self._z(10.0, 20.0)}
        t, f, m = F("x") > 0.0, F("x") > 99.0, F("x") > 15.0
        assert (t & m).zone_eval(zt) == T_MAYBE
        assert (f & t).zone_eval(zt) == T_FALSE
        assert (t | m).zone_eval(zt) == T_TRUE
        assert (f | m).zone_eval(zt) == T_MAYBE
        assert (f | f).zone_eval(zt) == T_FALSE
        assert (~t).zone_eval(zt) == T_FALSE
        assert (~f).zone_eval(zt) == T_TRUE
        assert (~m).zone_eval(zt) == T_MAYBE

    def test_empty_zone(self):
        # an entry range with zero elements in the column: comparisons and
        # null-checks are vacuously false / existentially false
        z = {"c._0": Zone.empty(nested=True)}
        assert (F("c._0") > 0.0).zone_eval(z) == T_FALSE
        assert F("c._0").is_null().zone_eval(z) == T_FALSE

    def test_validate_rejects(self):
        with pytest.raises(ValueError):
            (F("nope") > 1).validate(self.SCHEMA)
        with pytest.raises(ValueError):
            (F("c") > 1).validate(self.SCHEMA)  # offset column, not a leaf
        s8 = Schema([Leaf("b", "int8")])
        with pytest.raises(ValueError):
            (F("b") > 300).validate(s8)  # constant outside int8's range


# ---------------------------------------------------------------------------
# footer round-trip + defensive decoding


class TestFooterCodec:
    def test_roundtrip(self):
        per = [{0: {"fe": [0, 3], "le": [2, 7], "lo": [1.0, -2.0],
                    "hi": [5.0, 9.0], "nn": [0, 1]},
                1: {"fe": [0], "le": [7]}},
               None]
        enc = md.encode_zonemaps(per)
        assert enc is not None and enc["v"] == 1
        dec = md.decode_zonemaps(enc, 2)
        assert dec[1] is None
        assert dec[0][0]["lo"] == [1.0, -2.0]
        assert dec[0][1] == {"fe": [0], "le": [7]}

    def test_all_none_encodes_to_nothing(self):
        assert md.encode_zonemaps([None, None]) is None
        assert md.encode_zonemaps([]) is None

    def test_unknown_version_rejected(self):
        enc = md.encode_zonemaps([{0: {"fe": [0], "le": [1]}}])
        enc["v"] = 99
        assert md.decode_zonemaps(enc, 1) is None

    def test_cluster_count_mismatch_rejected(self):
        enc = md.encode_zonemaps([{0: {"fe": [0], "le": [1]}}])
        assert md.decode_zonemaps(enc, 3) is None

    def test_inconsistent_column_dropped(self):
        enc = md.encode_zonemaps([{0: {"fe": [0, 1], "le": [1]},  # ragged
                                   1: {"fe": [0], "le": [4]}}])
        dec = md.decode_zonemaps(enc, 1)
        assert dec is not None and 0 not in dec[0] and 1 in dec[0]


# ---------------------------------------------------------------------------
# write-then-read integration


def _flat_file(sink, n=4000, codec="none", zone_maps=True, buffered=True):
    """Monotonic id + noisy float, small pages, several clusters."""
    schema = Schema([Leaf("id", "int64"), Leaf("val", "float32")])
    rng = np.random.default_rng(7)
    opts = WriteOptions(**{**SMALL, "codec": codec}, zone_maps=zone_maps,
                        buffered=buffered)
    with SequentialWriter(schema, sink, opts) as w:
        step = 257
        for a in range(0, n, step):
            b = min(a + step, n)
            w.fill_batch(ColumnBatch(schema, b - a, {
                0: np.arange(a, b, dtype=np.int64),
                1: rng.normal(0, 100, b - a).astype(np.float32),
            }))
    return schema


def test_zonemaps_written_with_correct_bounds():
    sink = MemorySink()
    _flat_file(sink)
    r = RNTJReader(sink)
    try:
        assert len(r.zonemaps) == len(r.clusters) > 1
        for i, zm in enumerate(r.zonemaps):
            assert zm is not None
            cols = r.read_cluster(i)
            cm = r.clusters[i]
            for ci in (0, 1):
                d = zm[ci]
                # page geometry: fe/le per page, monotone, covering
                assert len(d["fe"]) == len(d["le"]) == sum(
                    1 for p in cm.pages if p.column == ci)
                assert d["fe"][0] == 0 and d["le"][-1] == cm.n_entries - 1
                assert all(a <= b for a, b in zip(d["fe"], d["le"]))
            # id column is monotone: page bounds are exactly first/last
            assert zm[0]["lo"][0] == float(cols[0][0])
            assert zm[0]["hi"][-1] == float(cols[0][-1])
            assert all(n == 0 for n in zm[0]["nn"])
    finally:
        r.close()


def test_pruned_equals_fullscan_flat():
    sink = MemorySink()
    _flat_file(sink)
    got, stats = _assert_pruned_equals_full(
        sink, (F("id") >= 100) & (F("id") < 140), expect_prune=True)
    assert [e["id"] for e in got] == list(range(100, 140))
    assert stats.clusters_pruned > 0


def test_cluster_skip_accounting_and_iter_clusters():
    sink = MemorySink()
    _flat_file(sink)
    expr = F("id").between(0, 50)
    r = RNTJReader(sink, options=ReadOptions(filter=expr))
    try:
        seen = [i for i, _ in r.iter_clusters()]
        assert len(seen) < len(r.clusters)  # later clusters skipped outright
        assert r.stats.clusters_pruned == len(r.clusters) - len(seen)
    finally:
        r.close()


def test_pages_read_leq_unpruned():
    sink = MemorySink()
    _flat_file(sink)
    for expr in (F("id") == 1234, F("val") > 250.0, F("id") < 0):
        _, pp, _ = _filtered_scan(sink, expr, True)
        _, pf, _ = _filtered_scan(sink, expr, False)
        assert pp <= pf
    # the needle query must actually prune hard, not just tie
    _, pp, _ = _filtered_scan(sink, F("id") == 1234, True)
    _, pf, _ = _filtered_scan(sink, F("id") == 1234, False)
    assert pp < pf


NESTED = Schema([
    Leaf("id", "int64"),
    Collection("js", Record("_0", [Leaf("pt", "float32")])),
])


def _nested_file(sink, n=1500, empties=True, codec="none"):
    rng = np.random.default_rng(11)
    entries = []
    for i in range(n):
        k = int(rng.integers(0, 5))
        if not empties:
            k = max(k, 1)
        entries.append({
            "id": i,
            "js": [{"pt": float(rng.normal(50, 30))} for _ in range(k)],
        })
    write_entries(NESTED, sink, entries,
                  WriteOptions(**{**SMALL, "codec": codec}))
    return entries


def test_pruned_equals_fullscan_nested_existential():
    sink = MemorySink()
    _nested_file(sink)
    _assert_pruned_equals_full(sink, F("js._0.pt") > 120.0)
    _assert_pruned_equals_full(sink, (F("js._0.pt") > 60.0) & (F("id") < 400))


def test_gap_entries_with_negated_predicate():
    # entries with EMPTY collections have no elements in any page of the
    # nested column; ~(exists pt > x) must keep them
    sink = MemorySink()
    _nested_file(sink, empties=True)
    expr = ~(F("js._0.pt") > -1e9)  # matches exactly the empty-collection entries
    got, _ = _assert_pruned_equals_full(sink, expr)
    ref = [e for e in RNTJReader(sink).iter_entries() if len(e["js"]) == 0]
    assert [e["id"] for e in got] == [e["id"] for e in ref]
    assert len(got) > 0


def test_straddling_entries_conjunction():
    # huge collections so single entries span multiple pages: a
    # conjunction whose witnesses live in different pages must not prune
    # the straddling entry from per-page verdicts
    schema = Schema([Leaf("id", "int64"),
                     Collection("c", Leaf("_0", "float64"))])
    entries = []
    for i in range(40):
        vals = [float(i)] * 200          # 200 elems × 8B ≫ 256B pages
        vals[0] = -1000.0 - i            # low witness at the front
        vals[-1] = 1000.0 + i            # high witness at the back
        entries.append({"id": i, "c": vals})
    sink = MemorySink()
    write_entries(schema, sink, entries, WriteOptions(**SMALL))
    expr = (F("c._0") > 999.0) & (F("c._0") < -999.0)
    got, _ = _assert_pruned_equals_full(sink, expr)
    assert len(got) == 40  # every entry has both witnesses


def test_nan_inf_bounds():
    schema = Schema([Leaf("x", "float64")])
    rng = np.random.default_rng(3)
    x = rng.normal(0, 10, 3000)
    x[::7] = np.nan
    x[::11] = np.inf
    x[::13] = -np.inf
    sink = MemorySink()
    opts = WriteOptions(**SMALL)
    with SequentialWriter(schema, sink, opts) as w:
        for a in range(0, len(x), 300):
            b = min(a + 300, len(x))
            w.fill_batch(ColumnBatch(schema, b - a, {0: x[a:b]}))
    for expr in (F("x") > 25.0, F("x") < -25.0, F("x") == np.inf,
                 F("x").is_null(), F("x").not_null(),
                 F("x").between(-5.0, 5.0), ~(F("x") > 0.0)):
        _assert_pruned_equals_full(sink, expr)


def test_all_nan_pages():
    schema = Schema([Leaf("x", "float32")])
    sink = MemorySink()
    with SequentialWriter(schema, sink, WriteOptions(**SMALL)) as w:
        w.fill_batch(ColumnBatch(schema, 512,
                                 {0: np.full(512, np.nan, np.float32)}))
        w.fill_batch(ColumnBatch(schema, 512,
                                 {0: np.arange(512, dtype=np.float32)}))
    got, _ = _assert_pruned_equals_full(sink, F("x").is_null(),
                                        expect_prune=True)
    assert len(got) == 512
    got, _ = _assert_pruned_equals_full(sink, F("x") >= 0.0)
    assert len(got) == 512


def test_parallel_writer_zonemaps():
    schema = Schema([Leaf("id", "int64")])
    sink = MemorySink()
    w = ParallelWriter(schema, sink, WriteOptions(**SMALL))
    ctxs = [w.create_fill_context() for _ in range(2)]
    try:
        for t, ctx in enumerate(ctxs):
            ctx.fill_batch(ColumnBatch(schema, 1000, {
                0: np.arange(t * 1000, (t + 1) * 1000, dtype=np.int64)}))
    finally:
        for ctx in ctxs:
            ctx.close()
        w.close()
    r = RNTJReader(sink)
    try:
        assert all(zm is not None for zm in r.zonemaps)
    finally:
        r.close()
    got, _ = _assert_pruned_equals_full(sink, F("id") == 1500,
                                        expect_prune=True)
    assert got == [{"id": 1500}]


def test_unbuffered_mode_zonemaps():
    sink = MemorySink()
    _flat_file(sink, buffered=False)
    r = RNTJReader(sink)
    try:
        assert all(zm is not None for zm in r.zonemaps)
    finally:
        r.close()
    got, _ = _assert_pruned_equals_full(sink, F("id").between(77, 99),
                                        expect_prune=True)
    assert [e["id"] for e in got] == list(range(77, 100))


# ---------------------------------------------------------------------------
# randomized property: pruned ≡ full-scan-then-filter


@st.composite
def _random_case(draw):
    n = draw(st.integers(min_value=1, max_value=400))
    seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
    # predicate shape: pick 1-3 atoms over the three leaves, random glue
    atoms = draw(st.lists(st.tuples(
        st.sampled_from(["id", "val", "js._0.pt"]),
        st.sampled_from(["gt", "lt", "eq", "between", "is_null", "not_null"]),
        st.floats(min_value=-150.0, max_value=150.0),
    ), min_size=1, max_size=3))
    glue = draw(st.lists(st.sampled_from(["and", "or"]),
                         min_size=2, max_size=2))
    negate = draw(st.sampled_from([False, True]))
    return n, seed, atoms, glue, negate


def _build_expr(atoms, glue, negate):
    parts = []
    for path, op, c in atoms:
        f = F(path)
        if op == "gt":
            parts.append(f > c)
        elif op == "lt":
            parts.append(f < c)
        elif op == "eq":
            parts.append(f == (int(c) if path == "id" else c))
        elif op == "between":
            parts.append(f.between(c - 25.0, c + 25.0))
        elif op == "is_null":
            parts.append(f.is_null())
        else:
            parts.append(f.not_null())
    e = parts[0]
    for i, p in enumerate(parts[1:]):
        e = (e & p) if glue[i] == "and" else (e | p)
    return ~e if negate else e


RANDOM_SCHEMA = Schema([
    Leaf("id", "int64"),
    Leaf("val", "float64"),
    Collection("js", Record("_0", [Leaf("pt", "float32")])),
])


@given(_random_case())
@settings(max_examples=40, deadline=None)
def test_property_pruned_equals_fullscan(case):
    n, seed, atoms, glue, negate = case
    rng = np.random.default_rng(seed)
    entries = []
    for i in range(n):
        val = float(rng.normal(0, 60))
        r = rng.random()
        if r < 0.08:
            val = float("nan")
        elif r < 0.12:
            val = float("inf") if r < 0.10 else float("-inf")
        k = int(rng.integers(0, 4))
        entries.append({"id": i, "val": val,
                        "js": [{"pt": float(rng.normal(40, 40))}
                               for _ in range(k)]})
    sink = MemorySink()
    write_entries(RANDOM_SCHEMA, sink, entries, WriteOptions(**SMALL))
    expr = _build_expr(atoms, glue, negate)
    _assert_pruned_equals_full(sink, expr)


# ---------------------------------------------------------------------------
# compatibility: old files, old readers, merge, recovery


def test_backcompat_zone_maps_off_reads_unpruned_without_warnings():
    sink = MemorySink()
    _flat_file(sink, zone_maps=False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        r = RNTJReader(sink, options=ReadOptions(filter=F("id") < 10))
        try:
            assert all(zm is None for zm in r.zonemaps)
            got = [_norm(e) for e in r.iter_filtered_entries()]
            # no zone plan: nothing is pruned at cluster level and every
            # cluster is scanned (late materialization of non-filter
            # columns still applies — that's not zone pruning)
            assert r.stats.clusters_pruned == 0
            assert r.stats.clusters == len(r.clusters)
        finally:
            r.close()
    assert [e["id"] for e in got] == list(range(10))


def test_forward_compat_seed_reader_reads_zonemapped_file(tmp_path):
    path = str(tmp_path / "zm.rntj")
    _flat_file(path, codec="zlib")
    new, old = RNTJReader(path), SeedRNTJReader(path)
    try:
        assert old.n_clusters == len(new.clusters)
        for i in range(old.n_clusters):
            a, b = new.read_cluster(i), old.read_cluster(i)
            for ci in a:
                np.testing.assert_array_equal(a[ci], b[ci])
    finally:
        new.close()
        old.close()


def test_merge_raw_copy_preserves_zonemaps(tmp_path):
    p1, p2, out = (str(tmp_path / f) for f in ("a.rntj", "b.rntj", "m.rntj"))
    _flat_file(p1, n=1000)
    _flat_file(p2, n=1000)
    merge_files([p1, p2], out)  # same codec: raw byte-verbatim path
    r1, r2, rm = RNTJReader(p1), RNTJReader(p2), RNTJReader(out)
    try:
        assert rm.zonemaps == r1.zonemaps + r2.zonemaps
    finally:
        r1.close(); r2.close(); rm.close()
    _assert_pruned_equals_full(out, F("id") == 5, expect_prune=True)


def test_merge_reencode_recomputes_zonemaps(tmp_path):
    p1, out = str(tmp_path / "a.rntj"), str(tmp_path / "m.rntj")
    _flat_file(p1, n=1000, codec="none")
    merge_files([p1], out, WriteOptions(**{**SMALL, "codec": "zlib"}),
                recompress=True)
    r = RNTJReader(out)
    try:
        assert any(zm is not None for zm in r.zonemaps)
    finally:
        r.close()
    got, _ = _assert_pruned_equals_full(out, F("id").between(10, 20),
                                        expect_prune=True)
    assert [e["id"] for e in got] == list(range(10, 21))


def test_recover_drops_zonemaps_with_reason(tmp_path):
    path = str(tmp_path / "torn.rntj")
    _flat_file(path, n=1000)
    # tear off the footer chain: recovery must rebuild from the journal
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 100)
    report = recover_container(path)
    assert report.rebuilt
    assert report.zonemaps is not None
    assert report.zonemaps["preserved"] is False
    assert report.zonemaps["reason"]
    assert report.as_dict()["zonemaps"]["preserved"] is False
    r = RNTJReader(path)
    try:
        assert all(zm is None for zm in r.zonemaps)
    finally:
        r.close()
    got, _ = _assert_pruned_equals_full(path, F("id") < 5, expect_prune=False)
    assert [e["id"] for e in got] == list(range(5))


def test_poisoned_bounds_regression(tmp_path):
    """A footer claiming wrong bounds wrongly prunes; recovery must drop
    the unattested stats so reads are correct again."""
    path = str(tmp_path / "poison.rntj")
    _flat_file(path, n=1000)
    ref, _, _ = _filtered_scan(path, F("id") < 50, prune=False)
    assert len(ref) == 50
    # forge a footer whose zone maps exclude every real value
    with open(path, "rb") as f:
        raw = f.read()
    anchor = md.parse_anchor(raw[-md.ANCHOR_SIZE:])
    foff, fsize = anchor["footer"]
    footer = md.parse_footer(raw[foff:foff + fsize])
    zm = footer["extra"]["zonemaps"]
    for cl in zm["clusters"]:
        for d in (cl or {}).values():
            if "lo" in d:
                d["lo"] = [1e18] * len(d["lo"])
                d["hi"] = [1e18] * len(d["hi"])
    size = len(raw)
    new_footer = md.build_footer(footer["n_entries"], footer["n_clusters"],
                                 tuple(footer["pagelist"]), footer["extra"])
    new_anchor = md.build_anchor(anchor["header"], (size, len(new_footer)),
                                 anchor["n_entries"], anchor["n_clusters"])
    with open(path, "ab") as f:
        f.write(new_footer + new_anchor)
    # the poison bites: the pruned read now wrongly drops everything
    poisoned, _, _ = _filtered_scan(path, F("id") < 50, prune=True)
    assert poisoned == []
    # forced recovery rebuilds from the journal and drops the bounds
    report = recover_container(path, force=True)
    assert report.zonemaps is not None and not report.zonemaps["preserved"]
    healed, _ = _assert_pruned_equals_full(path, F("id") < 50)
    assert healed == ref


# ---------------------------------------------------------------------------
# skim strategies: pruned vs unpruned byte identity (partition-boundary pin)


def _digest(path):
    import hashlib
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.mark.parametrize("strategy", ["imt", "separate", "buffermerger",
                                      "parallel"])
def test_skim_pruned_output_byte_identical(tmp_path, strategy):
    from repro.skim.engine import Cuts, make_agc_dataset, skim_partitions

    parts = make_agc_dataset(str(tmp_path / "in"), n_partitions=2,
                             files_per_partition=2, events_per_file=1500)
    cuts = Cuts(pt_cut=35.0, min_jets=2)
    outs, kept = {}, {}
    for mode in ("pruned", "full"):
        d = str(tmp_path / mode)
        res = skim_partitions(parts, d, strategy, n_threads=1, cuts=cuts,
                              pushdown=(mode == "pruned"))
        kept[mode] = res["kept_events"]
        outs[mode] = sorted(Path(d).glob("skim_*.rntj"))
    assert kept["pruned"] == kept["full"]
    assert [p.name for p in outs["pruned"]] == [p.name for p in outs["full"]]
    for a, b in zip(outs["pruned"], outs["full"]):
        assert _digest(a) == _digest(b), f"{strategy}: {a.name} differs"


def test_skim_segments_match_unpruned_partitioning(tmp_path):
    # the shared entry-range helper must yield one (cluster, segments)
    # group per surviving cluster in BOTH modes, same cluster order
    from repro.skim.engine import Cuts, cuts_expr, make_agc_dataset

    parts = make_agc_dataset(str(tmp_path / "in"), n_partitions=1,
                             files_per_partition=1, events_per_file=2000)
    f = parts[0][0]
    expr = cuts_expr(Cuts(pt_cut=35.0))
    rp = RNTJReader(f, options=ReadOptions(filter=expr))
    rf = RNTJReader(f, options=ReadOptions(filter=expr, prune=False))
    try:
        gp = [(i, len(segs)) for i, segs in rp.iter_cluster_segments()]
        gf = [(i, len(segs)) for i, segs in rf.iter_cluster_segments()]
        # full mode reads whole clusters; pruned mode may split one into
        # ranges or skip it, but never reorders or invents clusters
        assert [i for i, _ in gp if _ > 0] == [
            i for i, n in gf if n > 0 and rp._prune_plan()[i] != []]
        assert rp.stats.pages <= rf.stats.pages
    finally:
        rp.close()
        rf.close()


# ---------------------------------------------------------------------------
# review regressions: zero-min cuts, full-scan accessors, run-shared pages


def test_cuts_expr_drops_zero_min_collections():
    # a collection with min_* == 0 imposes no existential requirement:
    # its atom must not appear in the pushdown predicate, and all-zero
    # mins imply no predicate at all
    from repro.skim.engine import EVENT_SCHEMA, Cuts, cuts_expr

    assert cuts_expr(Cuts(min_electrons=0, min_muons=0, min_jets=0)) is None
    expr = cuts_expr(Cuts(min_muons=0))
    assert expr is not None
    paths = {EVENT_SCHEMA.columns[ci].path
             for ci in required_columns(EVENT_SCHEMA, expr)}
    assert "muons_pt._0" not in paths
    assert {"electrons_pt._0", "jets_pt._0"} <= paths
    # defaults (every min >= 1): all three atoms present
    full = {EVENT_SCHEMA.columns[ci].path
            for ci in required_columns(EVENT_SCHEMA, cuts_expr(Cuts()))}
    assert {"electrons_pt._0", "muons_pt._0", "jets_pt._0"} <= full


def test_skim_pushdown_zero_min_channel_no_loss(tmp_path):
    # an electron+jet channel (min_muons=0) over a file whose muons are
    # ALL below the cut: an unconditional muon atom would zone-prune
    # every cluster (silent total loss); the cuts-implied predicate must
    # skip the muon atom so pruned ≡ unpruned
    from repro.skim.engine import Cuts, EVENT_SCHEMA, skim_file

    rng = np.random.default_rng(3)
    n = 3000
    ne = rng.poisson(1.5, n).astype(np.int64)
    nm = rng.poisson(1.0, n).astype(np.int64)
    nj = rng.poisson(6.0, n).astype(np.int64)
    hot = lambda k: (rng.exponential(18.0, int(k)) + 15.0).astype(np.float32)
    src = str(tmp_path / "mu_cold.rntj")
    with SequentialWriter(EVENT_SCHEMA, src,
                          WriteOptions(page_size=1024,
                                       cluster_bytes=32 * 1024,
                                       codec="none")) as w:
        w.fill_batch(ColumnBatch.from_arrays(EVENT_SCHEMA, n, {
            "event_id": np.arange(n, dtype=np.int64),
            "met": rng.exponential(30.0, n).astype(np.float32),
            "electrons_pt": ne, "electrons_pt._0": hot(ne.sum()),
            "muons_pt": nm,
            "muons_pt._0": rng.uniform(1.0, 10.0, int(nm.sum()))
                              .astype(np.float32),
            "jets_pt": nj, "jets_pt._0": hot(nj.sum()),
        }))
    cuts = Cuts(pt_cut=20.0, min_electrons=1, min_muons=0, min_jets=2)
    got = {}
    for mode in (True, False):
        ids = []

        def fill(b, ids=ids):
            ci = b.schema.column_of_path["event_id"]
            ids.extend(np.asarray(b.data[ci]).tolist())

        kept = skim_file(src, fill, cuts, pushdown=mode)
        assert kept == len(ids)
        got[mode] = ids
    assert got[True] == got[False]
    assert len(got[True]) > 0


def test_full_scan_accessors_ignore_filter():
    # iter_entries / read_column are whole-file APIs: with a filter set
    # they must not silently drop zone-pruned clusters
    sink = MemorySink()
    _flat_file(sink)
    expr = F("id").between(0, 50)
    ref = RNTJReader(sink)
    r = RNTJReader(sink, options=ReadOptions(filter=expr))
    try:
        n = len(list(ref.iter_entries()))
        assert len(list(r.iter_entries())) == n
        np.testing.assert_array_equal(r.read_column("id"),
                                      ref.read_column("id"))
        np.testing.assert_array_equal(r.read_column("val"),
                                      ref.read_column("val"))
        assert r.stats.clusters_pruned == 0
    finally:
        r.close()
        ref.close()


def test_iter_filtered_run_shared_pages_counted_once():
    # many short matching runs inside one cluster: late-materialization
    # pages shared by adjacent runs decode once, and skipped pages are
    # accounted once per cluster — so neither pages nor pages_pruned can
    # exceed the file's total page count (the old per-run accounting did)
    schema = Schema([Leaf("id", "int64"), Leaf("val", "float64")])
    n = 128
    sink = MemorySink()
    opts = WriteOptions(page_size=256, cluster_bytes=1 << 20, codec="none")
    with SequentialWriter(schema, sink, opts) as w:
        w.fill_batch(ColumnBatch.from_arrays(schema, n, {
            "id": np.arange(n, dtype=np.int64),
            "val": np.arange(n, dtype=np.float64) * 0.5,
        }))
    expr = F("id").between(0, 1)
    for a in range(4, n, 4):
        expr = expr | F("id").between(a, a + 1)
    r = RNTJReader(sink, options=ReadOptions(filter=expr))
    try:
        got = [e["id"] for e in r.iter_filtered_entries()]
        assert got == [i for i in range(n) if i % 4 in (0, 1)]
        total_pages = sum(len(cm.pages) for cm in r.clusters)
        assert r.stats.pages <= total_pages
        assert r.stats.pages_pruned <= total_pages
    finally:
        r.close()
