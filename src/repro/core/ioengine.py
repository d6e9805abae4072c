"""The sink-side I/O engine: scatter-gather, striped, async-ring commits.

The paper's evaluation (§5) scales the CPU side of parallel writing until
it is "only limited by storage bandwidth" — this module makes our commit
path actually behave that way (DESIGN.md §6).  Four cooperating levers,
each individually optional:

* **scatter-gather** — a sealed cluster's iovec plan goes to
  ``Sink.pwritev`` with no assembly memcpy (the plan comes from
  ``ClusterBuilder._gather``; this engine only chooses *how* to submit);
* **striping** — an extent larger than ``stripe_bytes`` splits into
  independent sub-extent jobs at computed offsets inside the reserved
  extent, executed concurrently, so ONE producer can keep a deep device
  queue busy the way chunked compression keeps the codec pool busy;
* **write-behind** — with ``inflight_bytes > 0`` a commit only *enqueues*
  its extent; producers seal cluster N+1..N+k while earlier extents
  drain.  ``admit()`` is the backpressure gate (called before the
  writer's critical section, so a stalled producer never holds the
  commit lock), errors poison the writer through ``on_error`` exactly
  like a synchronous failed ``pwrite``, and ``drain()`` is the
  drain-before-footer barrier ``close()`` runs;
* **ring submission** (DESIGN.md §6.7) — queued extents go onto a
  **submission ring** (:class:`WriteBehindRing`) instead of one executor
  future per stripe: producers append to a deque and completion threads
  write each op through ``Sink.pwritev``.  A producer's submit cost is
  one deque append + notify (``io_submit_ns`` counts it).

The engine also closes the commit path's last allocation: with a
:class:`~repro.core.bufpool.BufferPool` attached, an extent owner's
detached scatter buffers are **returned to the pool when the extent's
last write lands** — never earlier, because a queued commit's iovecs
alias them until then (DESIGN.md §6.8).

The fsync policy rides here too: ``"on_close"`` (default; the writer's
close() fsyncs, as always), ``"every_cluster"`` (fsync when an extent's
last stripe lands), or an ``int`` byte interval (fsync each time that
many bytes have landed since the previous fsync).

With every lever off the engine degenerates to exactly the seed's
behavior: one synchronous ``pwrite``/``pwritev`` on the committing
thread.
"""

from __future__ import annotations

import errno
import random
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

_ns = time.perf_counter_ns

# pool size when striping / write-behind is enabled without an explicit
# WriteOptions.io_workers: enough concurrent submissions to keep an NVMe
# queue (or a sleeping ThrottledSink window) busy without thread bloat
DEFAULT_IO_WORKERS = 4

FSYNC_ON_CLOSE = "on_close"
FSYNC_EVERY_CLUSTER = "every_cluster"


# ---------------------------------------------------------------------------
# retry policy (DESIGN.md §8.2)

#: errnos worth retrying: transient device/medium hiccups and interruptions.
#: ENOSPC is included deliberately — on shared/quota'd storage it is often
#: transient (another writer freeing space, quota refresh); a genuinely full
#: disk just exhausts the attempts and poisons like any permanent error.
DEFAULT_RETRYABLE_ERRNOS = (
    errno.EIO, errno.EAGAIN, errno.ENOSPC, errno.EINTR, errno.ETIMEDOUT,
    errno.EBUSY,
)


@dataclass(frozen=True)
class RetryPolicy:
    """Bounded-retry policy applied by the I/O engine to every write and
    fsync (DESIGN.md §8.2).

    A failed operation whose errno is in ``retryable_errnos`` is retried
    up to ``max_attempts`` total attempts with exponential backoff
    (``backoff_base * 2**k`` seconds, capped at ``backoff_cap``, with
    ±50% deterministic jitter when ``jitter``).  ``deadline`` bounds one
    logical operation's total retry time in seconds (0 = unbounded).
    Positioned writes are idempotent — a retry rewrites the same extent
    bytes at the same offsets — so retrying after a *partial* (torn)
    write is always safe.  Non-``OSError`` failures (including the fault
    harness's :class:`~repro.core.faults.ProcessKilled`) are never
    retried.  Only an exhausted retry budget poisons the writer.
    """

    max_attempts: int = 4
    backoff_base: float = 0.002
    backoff_cap: float = 0.25
    jitter: bool = True
    retryable_errnos: Tuple[int, ...] = DEFAULT_RETRYABLE_ERRNOS
    deadline: float = 0.0

    def retryable(self, exc: BaseException) -> bool:
        return isinstance(exc, OSError) and exc.errno in self.retryable_errnos

    def backoff(self, attempt: int, rng=None) -> float:
        """Sleep before retry number ``attempt`` (1-based)."""
        delay = min(self.backoff_cap, self.backoff_base * (2 ** (attempt - 1)))
        if self.jitter and rng is not None:
            delay *= 0.5 + rng.random()
        return delay


class Retrier:
    """The retry chokepoint as a standalone object: run any callable under
    a :class:`RetryPolicy`.

    Originally the loop lived inside :meth:`IOEngine._retrying` and covered
    only engine-issued writes and fsyncs; it is factored out here so the
    *read* paths — :class:`~repro.core.reader.RNTJReader` preads and the
    remote :class:`~repro.core.remote.ObjectStoreSink` transport ops —
    apply the identical semantics: retry ``retryable_errnos`` up to
    ``max_attempts`` with exponential backoff + deterministic jitter,
    honor the policy's per-logical-op ``deadline``, re-raise everything
    else (non-``OSError`` failures such as
    :class:`~repro.core.faults.ProcessKilled` are never retried).

    Thread-safe: the jitter RNG is seeded (same backoff schedule every
    run) and guarded by a lock; ``on_retry``/``on_giveup`` fire once per
    retried / abandoned operation so callers can wire their own counters
    (sink IOStats, ReaderStats, engine mirrors).
    """

    def __init__(self, policy: Optional[RetryPolicy],
                 seed: int = 0x52455452,
                 on_retry: Optional[Callable] = None,
                 on_giveup: Optional[Callable] = None) -> None:
        self.policy = policy
        self._rng = random.Random(seed)
        self._mu = threading.Lock()
        self._on_retry = on_retry
        self._on_giveup = on_giveup

    def call(self, fn, *args):
        """``fn(*args)`` under the policy; a plain call when policy is
        ``None``."""
        policy = self.policy
        if policy is None:
            return fn(*args)
        deadline = (
            time.monotonic() + policy.deadline if policy.deadline else None
        )
        attempt = 0
        while True:
            try:
                return fn(*args)
            except OSError as e:
                attempt += 1
                if not policy.retryable(e):
                    raise
                if attempt >= policy.max_attempts or (
                        deadline is not None
                        and time.monotonic() >= deadline):
                    if self._on_giveup is not None:
                        self._on_giveup()
                    raise
                if self._on_retry is not None:
                    self._on_retry()
                with self._mu:
                    delay = policy.backoff(attempt, self._rng)
                if deadline is not None:
                    delay = min(delay, max(0.0, deadline - time.monotonic()))
                if delay > 0:
                    time.sleep(delay)


class _ExtentGroup:
    """One logical extent (a cluster or page) split into 1..n stripe jobs."""

    __slots__ = ("remaining", "nbytes", "owner", "striped")

    def __init__(self, remaining: int, nbytes: int, owner,
                 striped: bool = False):
        self.remaining = remaining
        self.nbytes = nbytes
        # the SealedCluster (or any object) whose buffers back the iovecs:
        # referenced until the last stripe lands, then recycled + released
        self.owner = owner
        self.striped = striped


# ---------------------------------------------------------------------------
# the submission ring


class _RingOp:
    __slots__ = ("group", "off", "parts", "nbytes")

    def __init__(self, group, off, parts, nbytes):
        self.group = group
        self.off = off
        self.parts = parts
        self.nbytes = nbytes


class WriteBehindRing:
    """The write-behind submission ring: completion threads + ``pwritev``.

    Producers append ops under one condition variable (a deque append —
    no future allocation, no executor work-queue churn); ``workers``
    completion threads pop small batches and execute them through the
    engine's job body (:meth:`IOEngine._run_job`), which owns accounting,
    poisoning and drain semantics.
    """

    # ops claimed per lock acquisition: amortizes wakeups without letting
    # one thread hoard the queue
    BATCH = 8

    def __init__(self, engine: "IOEngine", workers: int):
        self._engine = engine
        self._cv = threading.Condition()
        self._ops: deque = deque()
        self._stop = False
        self._workers = max(1, workers)
        # completion threads start lazily at the first submit, so a
        # writer that never enters write-behind (or a skim spawning many
        # writers) pays no idle threads
        self._threads: List[threading.Thread] = []

    def _ensure_threads(self) -> None:
        if self._threads:
            return
        self._threads = [
            threading.Thread(
                target=self._loop, daemon=True, name=f"rntj-ring-{i}"
            )
            for i in range(self._workers)
        ]
        for t in self._threads:
            t.start()

    def submit(self, group, off, parts, nbytes) -> None:
        with self._cv:
            self._ensure_threads()
            self._ops.append(_RingOp(group, off, parts, nbytes))
            self._cv.notify()

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._ops and not self._stop:
                    self._cv.wait()
                if not self._ops:
                    return  # stopping and drained
                # claim a share of the queue, not the whole head: with
                # fewer ops than workers each claims one (a high-latency
                # sink keeps every worker busy); only a queue deeper than
                # the worker pool amortizes wakeups with bigger batches
                share = max(1, len(self._ops) // self._workers)
                batch = [
                    self._ops.popleft()
                    for _ in range(min(len(self._ops), self.BATCH, share))
                ]
            for op in batch:
                self._engine._run_job(op.group, op.off, op.parts, op.nbytes)

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join()


# ---------------------------------------------------------------------------
# the engine


class IOEngine:
    """Positioned-write executor for one writer's sink.

    ``write_extent(off, parts, nbytes)`` is the single entry point used by
    every commit path (buffered clusters, unbuffered pages, merge's raw
    cluster copies).  Synchronous mode writes on the calling thread
    (striped over the pool when configured) and returns the measured
    io_ns; write-behind mode (``inflight_bytes > 0``) enqueues onto the
    submission ring and returns 0: the ring's workers add their io time
    to ``stats`` directly and report drained bytes through ``on_drain``
    (the rate-aware codec policy's bandwidth signal).  ``buffer_pool``
    receives an extent owner's recyclable buffers when its last write
    lands.
    """

    def __init__(
        self,
        sink,
        workers: int = 0,
        inflight_bytes: int = 0,
        stripe_bytes: int = 0,
        fsync_policy=FSYNC_ON_CLOSE,
        stats=None,
        on_error: Optional[Callable] = None,
        on_drain: Optional[Callable] = None,
        buffer_pool=None,
        retry: Optional[RetryPolicy] = None,
    ):
        self.sink = sink
        self.stripe_bytes = int(stripe_bytes)
        self.inflight_bytes = int(inflight_bytes)
        self.stats = stats
        self.buffer_pool = buffer_pool
        self._on_error = on_error
        self._on_drain = on_drain
        # -- retry + degradation state (DESIGN.md §8.2) ---------------------
        self.retry = retry
        # seeded Retrier: fault-injection runs replay the same backoff
        # schedule; the counters mirror into the engine AND the sink
        self._retrier = Retrier(retry, on_retry=self._count_retry,
                                on_giveup=self._count_giveup)
        self._retry_mu = threading.Lock()
        self.retries = 0             # retried operations (mirror of IOStats)
        self.giveups = 0             # operations that exhausted the budget
        self.stripe_fallbacks = 0    # striping disabled after stripe failure
        self._stripe_disabled = False
        self._closed = False
        if not workers and (self.stripe_bytes > 0 or self.inflight_bytes > 0):
            workers = DEFAULT_IO_WORKERS
        self._workers = workers
        # the submission ring exists only in write-behind mode and owns all
        # queued submissions; the executor runs only the stripes of a
        # synchronous striped write — create one or the other, never both
        self._ring = (
            WriteBehindRing(self, workers) if self.inflight_bytes > 0 else None
        )
        self._pool = (
            ThreadPoolExecutor(max_workers=workers, thread_name_prefix="rntj-io")
            if workers and self._ring is None
            else None
        )
        self._cv = threading.Condition()
        self._inflight = 0      # admitted write-behind bytes not yet drained
        self._pending = 0       # queued/running async jobs
        self._error: Optional[BaseException] = None
        # busy-window drain accounting for on_drain: concurrent jobs must
        # not each report their own wall time (that would under-report the
        # sink's bandwidth by the concurrency factor) — instead bytes
        # accumulate and are reported over the union busy window whenever
        # the last running job finishes
        self._running = 0
        self._busy_start = 0
        self._drained_bytes = 0
        # fsync policy state
        self._fsync_every = fsync_policy == FSYNC_EVERY_CLUSTER
        self._fsync_interval = (
            int(fsync_policy) if isinstance(fsync_policy, int) else 0
        )
        self._since_fsync = 0
        self._fsync_lock = threading.Lock()

    # -- mode ----------------------------------------------------------------

    @property
    def async_mode(self) -> bool:
        """True when commits are queued (write-behind) instead of written
        on the committing thread."""
        return self._ring is not None

    @property
    def ring(self):
        """The write-behind submission ring, or ``None`` (synchronous
        mode)."""
        return self._ring

    # -- backpressure ---------------------------------------------------------

    def admit(self, nbytes: int) -> None:
        """Block until ``nbytes`` fits in the in-flight budget.

        Called by producers BEFORE the writer's critical section: a
        producer stalled on storage must never stall the other producers'
        commits.  An extent larger than the whole budget is admitted alone
        (the engine never deadlocks on one oversized cluster).  No-op in
        synchronous mode.
        """
        if not self.async_mode:
            return
        t0 = _ns()
        with self._cv:
            while self._inflight and self._inflight + nbytes > self.inflight_bytes:
                self._cv.wait()
            self._inflight += nbytes
        stall = _ns() - t0
        if self.stats is not None and stall:
            self.stats.add_io_stall_ns(stall)

    def _release(self, nbytes: int) -> None:
        with self._cv:
            self._inflight -= nbytes
            self._cv.notify_all()

    # -- retrying (DESIGN.md §8.2) --------------------------------------------

    def _count_retry(self) -> None:
        with self._retry_mu:
            self.retries += 1
        counter = getattr(self.sink, "_count_retry", None)
        if counter is not None:
            counter()

    def _count_giveup(self) -> None:
        with self._retry_mu:
            self.giveups += 1
        counter = getattr(self.sink, "_count_giveup", None)
        if counter is not None:
            counter()

    def _retrying(self, fn, *args):
        """Run ``fn(*args)`` under the engine's retry policy.  The single
        choke point every engine-issued write and fsync goes through:
        the sync, striped and ring paths all call it via
        :meth:`_pwritev`.  The loop itself lives in :class:`Retrier` —
        shared with the reader's retrying preads and the remote sink's
        transport ops.  Without a policy it is a plain call."""
        return self._retrier.call(fn, *args)

    def _note_stripe_fallback(self) -> None:
        """A striped sub-extent failed even with retries: stop striping
        for the rest of this engine's life (the device is telling us it
        dislikes concurrent sub-extent writes) and count the event."""
        with self._retry_mu:
            self.stripe_fallbacks += 1
            self._stripe_disabled = True
        if self.stats is not None:
            self.stats.note_stripe_fallback()

    # -- submission -----------------------------------------------------------

    def write_extent(self, off: int, parts: List, nbytes: int,
                     owner=None) -> int:
        """Write ``parts`` contiguously at ``off`` — inline, striped, or
        queued.  Returns the io_ns spent on THIS thread (0 when queued).

        The caller has already ``admit()``ed ``nbytes`` in write-behind
        mode and already reserved the extent; stripes never overlap, so
        no ordering between jobs is needed.  A failed write calls
        ``on_error`` (the writer's commit-poison hook) — synchronous
        failures also raise, exactly like the direct ``pwrite`` they
        replace.
        """
        stripes = self._stripes(off, parts, nbytes)
        if not self.async_mode:
            t0 = _ns()
            try:
                try:
                    if len(stripes) == 1 or self._pool is None:
                        for s_off, s_parts, _n in stripes:
                            self._pwritev(s_off, s_parts)
                    else:
                        futs = [
                            self._pool.submit(self._pwritev, s_off, s_parts)
                            for s_off, s_parts, _n in stripes
                        ]
                        for f in futs:
                            f.result()
                except OSError:
                    if len(stripes) <= 1 or self.retry is None:
                        raise
                    # stripe degradation: the reserved extent is untouched
                    # by readers until the footer lands, so rewriting it
                    # monolithically (with a fresh retry budget) is
                    # idempotent; striping stays off from here on
                    self._note_stripe_fallback()
                    self._pwritev(off, list(parts))
            except BaseException as e:
                self._fail(e)
                raise
            io_ns = _ns() - t0
            try:
                # fsync policy failures poison exactly like write failures
                # (they used to be able to slip through mid-run)
                self._extent_done(nbytes)
            except BaseException as e:
                self._fail(e)
                raise
            self._recycle(owner)
            if self._on_drain is not None:
                self._on_drain(nbytes, io_ns)
            return io_ns
        # write-behind: enqueue one ring op per stripe
        if self._error is not None:
            # the writer is poisoned: drop the bytes (finalization will
            # refuse anyway) but keep the budget accounting balanced
            self._release(nbytes)
            return 0
        t0 = _ns()
        group = _ExtentGroup(len(stripes), nbytes, owner, len(stripes) > 1)
        with self._cv:
            self._pending += len(stripes)
        if self.stats is not None:
            for _ in stripes:
                self.stats.note_io_job(self._inflight)
        for s_off, s_parts, s_n in stripes:
            self._ring.submit(group, s_off, s_parts, s_n)
        if self.stats is not None:
            self.stats.add_io_submit_ns(_ns() - t0)
        return 0

    def _stripes(self, off: int, parts: List, nbytes: int
                 ) -> List[Tuple[int, List, int]]:
        """Split an extent's iovec plan into ``[(offset, parts, nbytes)]``
        stripe sub-extents of at most ``stripe_bytes`` each."""
        if (
            self.stripe_bytes <= 0
            or self._stripe_disabled
            or nbytes <= self.stripe_bytes
            or (self._pool is None and self._ring is None)
        ):
            return [(off, list(parts), nbytes)]
        out: List[Tuple[int, List, int]] = []
        cur: List = []
        cur_n = 0
        cur_off = off
        for part in parts:
            mv = memoryview(part)
            pos = 0
            while pos < len(mv):
                take = min(len(mv) - pos, self.stripe_bytes - cur_n)
                cur.append(mv[pos : pos + take])
                cur_n += take
                pos += take
                if cur_n == self.stripe_bytes:
                    out.append((cur_off, cur, cur_n))
                    cur_off += cur_n
                    cur, cur_n = [], 0
        if cur:
            out.append((cur_off, cur, cur_n))
        return out

    def _pwritev(self, off: int, parts: List) -> None:
        self._retrying(self._pwritev_once, off, parts)

    def _pwritev_once(self, off: int, parts: List) -> None:
        if len(parts) == 1:
            self.sink.pwrite(off, parts[0])
        else:
            self.sink.pwritev(off, parts)

    # -- job body (run by the ring's workers) ----------------------------------

    def _job_end(self, group: _ExtentGroup, nbytes: int, t0: int,
                 err: Optional[BaseException]) -> None:
        """Completion fold of one ring op: stats, budget release,
        busy-window drain reporting, last-stripe recycling + fsync,
        poisoning."""
        io_ns = _ns() - t0
        if err is not None:
            self._fail(err)
        if self.stats is not None:
            self.stats.add_io_ns(io_ns)
        last = False
        drained = None
        with self._cv:
            self._running -= 1
            self._drained_bytes += nbytes
            if self._running == 0:
                # window closed: report accumulated bytes over the
                # union busy time — the sink's actual drain bandwidth
                drained = (self._drained_bytes, _ns() - self._busy_start)
                self._drained_bytes = 0
            self._pending -= 1
            self._inflight -= nbytes
            group.remaining -= 1
            last = group.remaining == 0
            self._cv.notify_all()
        if drained is not None and self._on_drain is not None:
            self._on_drain(*drained)
        if last:
            # the extent's final byte has landed (or failed): only now is
            # it safe to hand its buffers back to the pool — a queued
            # write referenced them until this moment
            self._recycle(group.owner)
            group.owner = None  # release the sealed cluster's buffers
            if self._error is None:
                try:
                    self._extent_done(group.nbytes)
                except BaseException as e:
                    self._fail(e)

    def _run_job(self, group: _ExtentGroup, off: int, parts: List,
                 nbytes: int) -> None:
        t0 = _ns()
        with self._cv:
            if self._running == 0:
                self._busy_start = t0
            self._running += 1
        err = None
        try:
            if self._error is None:
                self._pwritev(off, parts)
        except BaseException as e:
            err = e
            if isinstance(e, OSError) and group.striped:
                # the group's other stripes may already be in flight, so
                # this extent cannot be rewritten monolithically; poison,
                # but stop striping future extents
                self._note_stripe_fallback()
        self._job_end(group, nbytes, t0, err)

    def _recycle(self, owner) -> None:
        """Return an extent owner's pooled buffers (``owner.recycle``)."""
        if owner is None or self.buffer_pool is None:
            return
        bufs = getattr(owner, "recycle", None)
        if bufs:
            self.buffer_pool.put_all(bufs)
            try:
                owner.recycle = None
            except AttributeError:
                pass

    def _fail(self, e: BaseException) -> None:
        with self._cv:
            if self._error is None:
                self._error = e
            self._cv.notify_all()
        if self._on_error is not None:
            self._on_error(e)

    # -- fsync policy ---------------------------------------------------------

    def _do_fsync(self) -> None:
        """One retried fsync; a final failure is accounted in IOStats
        before it propagates (callers decide how it poisons)."""
        try:
            self._retrying(self.sink.fsync)
        except BaseException:
            counter = getattr(self.sink, "_count_fsync_failure", None)
            if counter is not None:
                counter()
            raise

    def fsync(self) -> None:
        """Retrying fsync that poisons the writer on failure — the entry
        point the writer's journal barrier and close() use, so a failed
        final sync surfaces exactly like a failed write."""
        try:
            self._do_fsync()
        except BaseException as e:
            self._fail(e)
            raise

    def _extent_done(self, nbytes: int) -> None:
        """Apply the every-cluster / byte-interval fsync policy after an
        extent's bytes have fully landed.  Raises on (retry-exhausted)
        fsync failure: both the sync path and ``_job_end`` route that
        into ``_fail`` — the mid-run fsync error is never swallowed."""
        if self._fsync_every:
            self._do_fsync()
        elif self._fsync_interval:
            due = False
            with self._fsync_lock:
                self._since_fsync += nbytes
                if self._since_fsync >= self._fsync_interval:
                    self._since_fsync = 0
                    due = True
            if due:
                self._do_fsync()

    # -- drain / shutdown ------------------------------------------------------

    @property
    def error(self) -> Optional[BaseException]:
        return self._error

    def drain(self) -> None:
        """Block until every queued write job has finished (successfully
        or not).  The drain-before-footer barrier: any failure is already
        latched in the writer via ``on_error``; this never raises."""
        with self._cv:
            while self._pending:
                self._cv.wait()

    def close(self) -> None:
        """Drain and release workers.  Idempotent: a poisoned writer's
        second close (``__exit__`` after the first raised) must not touch
        an already-shut-down ring or pool."""
        if self._closed:
            return
        self._closed = True
        self.drain()
        if self._ring is not None:
            self._ring.close()
            self._ring = None
        if self._pool is not None:
            self._pool.shutdown(wait=True)
