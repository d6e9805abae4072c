"""Instrumentation: lock, I/O, and per-phase time counters.

The paper diagnoses the buffered-vs-unbuffered scalability gap by counting
futex system calls under strace (§6.1: ~300 vs >27,000 at 64 threads).  On
Linux a futex syscall only happens when a lock is *contended*, so we count
both acquisitions and contended acquisitions, plus time held, and the sinks
count write syscalls and bytes.  These measurements are hardware-independent
and reproduce the paper's diagnosis exactly.

:class:`WriterStats` additionally breaks the write path into phases —
``fill`` (decompose + buffer append), ``seal`` (serialize, wall time),
``compress`` (summed per-page build time, a CPU-time view that exceeds the
seal wall time when a compression pool is active), ``commit`` (reserve +
metadata + write path) and ``io`` (time inside ``pwrite``) — so benchmarks
can attribute wins to the right layer.  All mutation goes through locked
``add_*``/``merge_*`` methods: with pipelined sealing, commits run on
background threads concurrently with producer fills.

:func:`span` marks one layer's unit of work (a train step, a loader
batch, a cluster staged or sealed) on the JAX profiler's clock, beside
the device ops; while a profiler session runs, :func:`records` keeps them
for the benchmark's per-layer metrics.  Where a span and a counter time
the same interval, the counter is read from the span's clock.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, NamedTuple, Optional


def _merge_codec_stats(into: Dict[int, List[int]],
                       stats: Optional[Dict[int, List[int]]]) -> None:
    """Fold one ``{codec: [pages, bytes_in, bytes_out, ns]}`` map into
    another (the shared shape of writer and reader per-codec entries)."""
    if not stats:
        return
    for cid, vals in stats.items():
        st = into.setdefault(cid, [0, 0, 0, 0])
        for k in range(4):
            st[k] += vals[k]


def _codec_stats_dict(per_codec: Dict[int, List[int]]) -> dict:
    from . import compression as comp

    return {
        comp.codec_name(cid): {
            "pages": st[0],
            "bytes_in": st[1],
            "bytes_out": st[2],
            "ms": st[3] / 1e6,
        }
        for cid, st in sorted(per_codec.items())
    }


@dataclass
class LockStats:
    acquisitions: int = 0
    contended: int = 0
    held_ns: int = 0
    wait_ns: int = 0

    def merge(self, other: "LockStats") -> None:
        self.acquisitions += other.acquisitions
        self.contended += other.contended
        self.held_ns += other.held_ns
        self.wait_ns += other.wait_ns


class CountingLock:
    """A mutex that records acquisition counts, contention, and held time."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._meta = threading.Lock()  # guards the counters
        self.stats = LockStats()
        self._acquired_at = 0

    def acquire(self) -> None:
        t0 = time.perf_counter_ns()
        fast = self._lock.acquire(blocking=False)
        if not fast:
            self._lock.acquire()
        t1 = time.perf_counter_ns()
        with self._meta:
            self.stats.acquisitions += 1
            if not fast:
                self.stats.contended += 1
                self.stats.wait_ns += t1 - t0
        self._acquired_at = t1

    def release(self) -> None:
        held = time.perf_counter_ns() - self._acquired_at
        self._lock.release()
        with self._meta:
            self.stats.held_ns += held

    def snapshot(self) -> LockStats:
        """Consistent copy of the counters (safe to merge while live)."""
        with self._meta:
            return replace(self.stats)

    def __enter__(self) -> "CountingLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


@dataclass
class IOStats:
    write_calls: int = 0
    writev_calls: int = 0     # vectored (scatter-gather) submissions
    bytes_written: int = 0
    read_calls: int = 0
    bytes_read: int = 0
    fallocate_calls: int = 0
    fsync_calls: int = 0
    # fault handling (DESIGN.md §8.2): operations retried by the I/O
    # engine's RetryPolicy, operations that exhausted their retry budget,
    # and fsyncs that still failed after retrying
    retries: int = 0
    giveups: int = 0
    fsync_failures: int = 0
    # remote transport (DESIGN.md §10): hedged ranged reads launched, races
    # the hedge won, and multipart→serial-put degradations
    hedges: int = 0
    hedge_wins: int = 0
    degradations: int = 0

    def merge(self, other: "IOStats") -> None:
        self.write_calls += other.write_calls
        self.writev_calls += other.writev_calls
        self.bytes_written += other.bytes_written
        self.read_calls += other.read_calls
        self.bytes_read += other.bytes_read
        self.fallocate_calls += other.fallocate_calls
        self.fsync_calls += other.fsync_calls
        self.retries += other.retries
        self.giveups += other.giveups
        self.fsync_failures += other.fsync_failures
        self.hedges += other.hedges
        self.hedge_wins += other.hedge_wins
        self.degradations += other.degradations

    def snapshot(self) -> "IOStats":
        return replace(self)


@dataclass
class WriterStats:
    """Aggregated per-writer statistics, reported by the benchmarks.

    Thread-safe: concurrent producers and background seal/commit threads
    funnel updates through the locked ``add_*`` methods.
    """

    lock: LockStats = field(default_factory=LockStats)
    io: IOStats = field(default_factory=IOStats)
    uncompressed_bytes: int = 0
    compressed_bytes: int = 0
    fill_ns: int = 0         # producer time in decompose + buffer append
    seal_ns: int = 0         # wall time in serialization+compression (no lock held)
    compress_ns: int = 0     # summed per-page build time (CPU view of seal)
    commit_ns: int = 0       # time in commit path (reserve+metadata+write)
    io_ns: int = 0           # time inside pwrite/pwritev (any thread)
    # -- I/O engine (write-behind / striping, DESIGN.md §6) -----------------
    io_stall_ns: int = 0     # producer time blocked on the in-flight budget
    io_jobs: int = 0         # write jobs executed by the engine
    io_inflight_peak: int = 0  # max write-behind bytes in flight at once
    # -- async submission + buffer pool (DESIGN.md §6.7/§6.8) ---------------
    io_submit_ns: int = 0    # producer time spent submitting queued extents
    # -- fault handling / degradation (DESIGN.md §8.2) -----------------------
    io_stripe_fallbacks: int = 0  # striping disabled after a stripe failure
    io_ring_fallbacks: int = 0    # native ring degraded to synchronous ops
    pool_hits: int = 0       # buffer-pool takes served from a size class
    pool_misses: int = 0     # buffer-pool takes that had to allocate
    pool_returns: int = 0    # buffers returned to the pool
    pool_drops: int = 0      # returns rejected (residency bound / foreign)
    entries: int = 0
    clusters: int = 0
    pages: int = 0
    # codec id -> [pages, bytes_in (uncompressed), bytes_out (stored),
    # compress_ns]: the per-codec attribution of the engine's work
    per_codec: Dict[int, List[int]] = field(default_factory=dict)

    def __post_init__(self) -> None:
        self._mu = threading.Lock()

    # -- race-safe mutation -------------------------------------------------

    def add_sealed_cluster(self, sealed, commit_ns: int, io_ns: int = 0) -> None:
        with self._mu:
            self.commit_ns += commit_ns
            self.io_ns += io_ns
            self.seal_ns += sealed.seal_ns
            self.compress_ns += sealed.compress_ns
            self.clusters += 1
            self.pages += len(sealed.pages)
            self.entries += sealed.n_entries
            self.uncompressed_bytes += sealed.uncompressed_bytes
            self.compressed_bytes += sealed.size
            _merge_codec_stats(self.per_codec,
                               getattr(sealed, "codec_stats", None))

    def add_page(self, compressed_size: int, commit_ns: int = 0,
                 io_ns: int = 0, codec: Optional[int] = None,
                 uncompressed_size: int = 0, build_ns: int = 0) -> None:
        with self._mu:
            self.pages += 1
            self.compressed_bytes += compressed_size
            self.commit_ns += commit_ns
            self.io_ns += io_ns
            self.compress_ns += build_ns
            if codec is not None:
                _merge_codec_stats(self.per_codec, {
                    codec: [1, uncompressed_size, compressed_size, build_ns]
                })

    def add_cluster_meta(self, n_entries: int, uncompressed_bytes: int) -> None:
        with self._mu:
            self.clusters += 1
            self.entries += n_entries
            self.uncompressed_bytes += uncompressed_bytes

    def add_fill_ns(self, ns: int) -> None:
        with self._mu:
            self.fill_ns += ns

    def add_io_ns(self, ns: int) -> None:
        """Time inside pwrite/pwritev on an engine worker (write-behind:
        the io phase no longer happens on the committing thread)."""
        with self._mu:
            self.io_ns += ns

    def add_io_stall_ns(self, ns: int) -> None:
        with self._mu:
            self.io_stall_ns += ns

    def add_io_submit_ns(self, ns: int) -> None:
        """Producer time spent handing a queued extent to the engine
        (ring append / pool dispatch) — the submission overhead the async
        engine exists to shrink."""
        with self._mu:
            self.io_submit_ns += ns

    def merge_pool(self, snapshot) -> None:
        """Fold a :class:`~repro.core.bufpool.PoolStats` snapshot in."""
        with self._mu:
            self.pool_hits += snapshot.pool_hits
            self.pool_misses += snapshot.pool_misses
            self.pool_returns += snapshot.pool_returns
            self.pool_drops += snapshot.pool_drops

    def note_stripe_fallback(self) -> None:
        with self._mu:
            self.io_stripe_fallbacks += 1

    def note_ring_fallback(self) -> None:
        with self._mu:
            self.io_ring_fallbacks += 1

    def note_io_job(self, inflight: int) -> None:
        """One engine write job observed with ``inflight`` write-behind
        bytes admitted."""
        with self._mu:
            self.io_jobs += 1
            if inflight > self.io_inflight_peak:
                self.io_inflight_peak = inflight

    def merge_lock(self, snapshot: LockStats) -> None:
        with self._mu:
            self.lock.merge(snapshot)

    def merge_io(self, snapshot: IOStats) -> None:
        with self._mu:
            self.io.merge(snapshot)

    # -- reporting ----------------------------------------------------------

    def phases_ms(self) -> dict:
        """The per-phase time breakdown, in milliseconds."""
        return {
            "fill": self.fill_ns / 1e6,
            "seal": self.seal_ns / 1e6,
            "compress": self.compress_ns / 1e6,
            "commit": self.commit_ns / 1e6,
            "io": self.io_ns / 1e6,
        }

    def as_dict(self) -> dict:
        return {
            "entries": self.entries,
            "clusters": self.clusters,
            "pages": self.pages,
            "uncompressed_bytes": self.uncompressed_bytes,
            "compressed_bytes": self.compressed_bytes,
            "lock_acquisitions": self.lock.acquisitions,
            "lock_contended": self.lock.contended,
            "lock_held_ms": self.lock.held_ns / 1e6,
            "lock_wait_ms": self.lock.wait_ns / 1e6,
            "fill_ms": self.fill_ns / 1e6,
            "seal_ms": self.seal_ns / 1e6,
            "compress_ms": self.compress_ns / 1e6,
            "commit_ms": self.commit_ns / 1e6,
            "io_ms": self.io_ns / 1e6,
            "io_stall_ms": self.io_stall_ns / 1e6,
            "io_submit_ms": self.io_submit_ns / 1e6,
            "io_jobs": self.io_jobs,
            "io_inflight_peak_bytes": self.io_inflight_peak,
            "pool_hits": self.pool_hits,
            "pool_misses": self.pool_misses,
            "pool_returns": self.pool_returns,
            "pool_drops": self.pool_drops,
            "phases_ms": self.phases_ms(),
            "per_codec": _codec_stats_dict(self.per_codec),
            "write_calls": self.io.write_calls,
            "writev_calls": self.io.writev_calls,
            "bytes_written": self.io.bytes_written,
            "fallocate_calls": self.io.fallocate_calls,
            "io_retries": self.io.retries,
            "io_giveups": self.io.giveups,
            "io_fsync_failures": self.io.fsync_failures,
            "io_hedges": self.io.hedges,
            "io_hedge_wins": self.io.hedge_wins,
            "io_degradations": self.io.degradations,
            "io_stripe_fallbacks": self.io_stripe_fallbacks,
            "io_ring_fallbacks": self.io_ring_fallbacks,
        }


@dataclass
class ReaderStats:
    """Aggregated per-reader statistics — the read-side mirror of
    :class:`WriterStats`.

    Phase breakdown (``phases_ms``):
      * ``io``         — time inside ``pread`` (after coalescing)
      * ``decompress`` — summed per-page entropy-decode time
      * ``decode``     — summed per-page unprecondition + offset-integration
        time (writes straight into the per-column output arrays)
      * ``wait``       — time the consumer blocked on the prefetch pipeline

    ``decompress``/``decode`` are summed per-page times: a CPU-time view
    that exceeds wall time when the decode pool is active (exactly like
    ``WriterStats.compress_ns`` on the write side).  Thread-safe: decode
    workers and the prefetch pipeline funnel updates through the locked
    ``add_*`` methods.
    """

    io: IOStats = field(default_factory=IOStats)
    clusters: int = 0
    pages: int = 0
    coalesced_reads: int = 0  # preads issued for page data after coalescing
    compressed_bytes: int = 0
    uncompressed_bytes: int = 0
    io_ns: int = 0            # time inside pread
    decompress_ns: int = 0    # summed per-page entropy decode
    decode_ns: int = 0        # summed per-page unprecondition/integration
    wait_ns: int = 0          # consumer blocked on the prefetch pipeline
    h2d_ns: int = 0           # staging upload (host->device transfer, §9)
    device_clusters: int = 0  # clusters decoded through the device chain
    pool_hits: int = 0        # reader buffer-pool takes served from a class
    pool_misses: int = 0      # reader buffer-pool takes that allocated
    pool_returns: int = 0
    pool_drops: int = 0
    # read-path retry accounting (DESIGN.md §8.2/§10): preads retried by
    # the reader's RetryPolicy and preads that exhausted their budget.
    # Sink-internal retries (the remote sink's transport loop) live in
    # ``io.retries`` instead, merged at close.
    retries: int = 0
    giveups: int = 0
    # zone-map pruning (DESIGN.md §11): clusters/pages the prune plan
    # skipped before any pread was issued for them
    clusters_pruned: int = 0
    pages_pruned: int = 0
    # codec id -> [pages, bytes_in (stored), bytes_out (decoded),
    # decompress_ns]: the read-side mirror of WriterStats.per_codec
    per_codec: Dict[int, List[int]] = field(default_factory=dict)

    def __post_init__(self) -> None:
        self._mu = threading.Lock()

    # -- race-safe mutation -------------------------------------------------

    def add_cluster_read(
        self,
        pages: int,
        reads: int,
        compressed_bytes: int,
        uncompressed_bytes: int,
        io_ns: int,
        decompress_ns: int,
        decode_ns: int,
        per_codec: Optional[Dict[int, List[int]]] = None,
        clusters: int = 1,
    ) -> None:
        with self._mu:
            self.clusters += clusters
            self.pages += pages
            self.coalesced_reads += reads
            self.compressed_bytes += compressed_bytes
            self.uncompressed_bytes += uncompressed_bytes
            self.io_ns += io_ns
            self.decompress_ns += decompress_ns
            self.decode_ns += decode_ns
            _merge_codec_stats(self.per_codec, per_codec)

    def add_wait_ns(self, ns: int) -> None:
        with self._mu:
            self.wait_ns += ns

    def add_device_cluster(self, h2d_ns: int) -> None:
        with self._mu:
            self.device_clusters += 1
            self.h2d_ns += h2d_ns

    def add_decode_ns(self, ns: int) -> None:
        with self._mu:
            self.decode_ns += ns

    def add_retry(self) -> None:
        with self._mu:
            self.retries += 1

    def add_giveup(self) -> None:
        with self._mu:
            self.giveups += 1

    def add_pruned(self, clusters: int = 0, pages: int = 0) -> None:
        with self._mu:
            self.clusters_pruned += clusters
            self.pages_pruned += pages

    def merge_io(self, snapshot: IOStats) -> None:
        with self._mu:
            self.io.merge(snapshot)

    def merge_pool(self, snapshot) -> None:
        """Fold a :class:`~repro.core.bufpool.PoolStats` snapshot in."""
        with self._mu:
            self.pool_hits += snapshot.pool_hits
            self.pool_misses += snapshot.pool_misses
            self.pool_returns += snapshot.pool_returns
            self.pool_drops += snapshot.pool_drops

    # -- reporting ----------------------------------------------------------

    def phases_ms(self) -> dict:
        return {
            "io": self.io_ns / 1e6,
            "decompress": self.decompress_ns / 1e6,
            "decode": self.decode_ns / 1e6,
            "wait": self.wait_ns / 1e6,
            "h2d": self.h2d_ns / 1e6,
        }

    def as_dict(self) -> dict:
        return {
            "clusters": self.clusters,
            "pages": self.pages,
            "coalesced_reads": self.coalesced_reads,
            "compressed_bytes": self.compressed_bytes,
            "uncompressed_bytes": self.uncompressed_bytes,
            "io_ms": self.io_ns / 1e6,
            "decompress_ms": self.decompress_ns / 1e6,
            "decode_ms": self.decode_ns / 1e6,
            "wait_ms": self.wait_ns / 1e6,
            "h2d_ms": self.h2d_ns / 1e6,
            "device_clusters": self.device_clusters,
            "pool_hits": self.pool_hits,
            "pool_misses": self.pool_misses,
            "pool_returns": self.pool_returns,
            "pool_drops": self.pool_drops,
            "phases_ms": self.phases_ms(),
            "per_codec": _codec_stats_dict(self.per_codec),
            "read_calls": self.io.read_calls,
            "bytes_read": self.io.bytes_read,
            "retries": self.retries,
            "giveups": self.giveups,
            "clusters_pruned": self.clusters_pruned,
            "pages_pruned": self.pages_pruned,
            "io_retries": self.io.retries,
            "io_giveups": self.io.giveups,
            "io_hedges": self.io.hedges,
            "io_hedge_wins": self.io.hedge_wins,
        }


# ---------------------------------------------------------------------------
# Program spans on the profiler's clock

_ns = time.perf_counter_ns

#: spans kept in memory at most (the newest); older ones are dropped
SPAN_LOG_CAPACITY = 1 << 18

#: JAX's compile events recorded as spans: one ``compile`` per program
#: lowered (the key is the program)
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile",
}


class SpanRecord(NamedTuple):
    """One finished span: ``parent`` is the ``id`` of the span open on the
    same thread when it began (None at the top), times are
    ``perf_counter_ns``."""

    id: int
    name: str
    key: Any
    start_ns: int
    end_ns: int
    parent: Optional[int]
    thread: int

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


class _SpanLog:
    """The newest ``capacity`` records and a count of those dropped."""

    def __init__(self, capacity: int) -> None:
        self._mu = threading.Lock()
        self._items: deque = deque(maxlen=capacity)
        self.dropped = 0

    def add(self, rec: SpanRecord) -> None:
        with self._mu:
            if len(self._items) == self._items.maxlen:
                self.dropped += 1
            self._items.append(rec)

    def records(self) -> List[SpanRecord]:
        with self._mu:
            return list(self._items)

    def clear(self) -> None:
        with self._mu:
            self._items.clear()
            self.dropped = 0


# process-wide, like the profiler session it mirrors
_log = _SpanLog(SPAN_LOG_CAPACITY)
_ids = itertools.count(1)
_local = threading.local()
_bind_mu = threading.Lock()
_is_enabled = None      # the profiler's session check, bound with jax
_annotation = None      # jax.profiler.TraceAnnotation
_step_annotation = None  # jax.profiler.StepTraceAnnotation


def _never() -> bool:
    return False


def _bind_jax() -> bool:
    """Bind the profiler's hooks once jax is imported (a process that
    never imports jax has no profiler session to check).  A jax without
    the session check reads as no session, with one warning: spans must
    never stop the data path."""
    global _is_enabled, _annotation, _step_annotation
    if "jax" not in sys.modules:
        return False
    with _bind_mu:
        if _is_enabled is None:
            try:
                import jax
                from jax._src.lib import _profiler

                is_enabled = _profiler.TraceMe.is_enabled   # jax 0.9.0
                _annotation = jax.profiler.TraceAnnotation
                _step_annotation = jax.profiler.StepTraceAnnotation
                jax.monitoring.register_event_duration_secs_listener(
                    _on_compile)
            except (ImportError, AttributeError) as exc:
                warnings.warn(f"program spans are off: this jax has no "
                              f"profiler session check ({exc})",
                              RuntimeWarning, stacklevel=2)
                is_enabled = _never
            _is_enabled = is_enabled
    return True


def profiler_active() -> bool:
    """True while a JAX profiler session collects host events."""
    if _is_enabled is None and not _bind_jax():
        return False
    return _is_enabled()


def _stack() -> List[int]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class _Clock:
    """A span's two clock reads (``timed`` spans read them always)."""

    __slots__ = ("t0", "t1")

    def __enter__(self):
        self.t0 = _ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = _ns()
        return False

    @property
    def ns(self) -> int:
        return self.t1 - self.t0


class _Span(_Clock):
    __slots__ = ("name", "key", "step", "id", "parent", "ann", "stack")

    def __init__(self, name: str, key, step: bool) -> None:
        self.name, self.key, self.step = name, key, step

    def __enter__(self):
        label = "rntj." + self.name
        self.ann = (_step_annotation(label, step_num=self.key) if self.step
                    else _annotation(label))
        self.ann.__enter__()
        self.stack = _stack()
        self.parent = self.stack[-1] if self.stack else None
        self.id = next(_ids)
        self.stack.append(self.id)
        self.t0 = _ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = _ns()
        self.stack.remove(self.id)
        self.ann.__exit__(*exc)
        _log.add(SpanRecord(self.id, self.name, self.key, self.t0, self.t1,
                            self.parent, threading.get_ident()))
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullSpan()


def span(name: str, key=None, *, timed: bool = False, step: bool = False):
    """Context manager marking ``rntj.<name>`` in the profiler's trace and
    keeping its :class:`SpanRecord` while a session is active; with none
    active it costs one check.  ``key`` names the unit of work (spans of
    one unit share it); ``step=True`` writes a ``StepTraceAnnotation``
    whose step number is ``key``; ``timed=True`` returns an object whose
    ``t0``/``t1``/``ns`` hold the span's clock reads, session or not, for
    a counter that times the same interval."""
    if profiler_active():
        return _Span(name, key, step)
    return _Clock() if timed else _NULL


def _on_compile(event: str, duration: float, fun_name=None, **_kw) -> None:
    """``jax.monitoring`` listener: a compile as a span that ends now, under
    the span open on the compiling thread.  Kept in memory only: the
    profiler's trace holds JAX's own compile events."""
    name = _COMPILE_EVENTS.get(event)
    if name is None or not _is_enabled():
        return
    t1 = _ns()
    st = _stack()
    _log.add(SpanRecord(next(_ids), name, fun_name, t1 - int(duration * 1e9),
                        t1, st[-1] if st else None, threading.get_ident()))


def records() -> List[SpanRecord]:
    """The spans kept while a profiler session was active, oldest first
    (a span is kept when it ends, so children precede their parent)."""
    return _log.records()


def dropped() -> int:
    """Records dropped to keep :func:`records` bounded."""
    return _log.dropped


def clear() -> None:
    """Forget every kept record and the dropped count."""
    _log.clear()
