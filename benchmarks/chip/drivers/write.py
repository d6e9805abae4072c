"""Write cells: the paper's parallel writer, ``ParallelWriter`` fill
contexts on producer threads filling one file, closed by ``close()``.

Set-up generates, per producer, one cluster's worth of seeded event
batches (``batch_entries`` entries each, as many as reach
``cluster_bytes``), opens the writer, and warms the write-side kernels by
filling one cluster per producer into a second writer.  In the window
every producer fills its batches in turn, each time with fresh ids
(producer ``p``'s ``k``-th fill holds ids ``p * 2**40 + k *
batch_entries + row``), until the deadline, finishing the cluster it is
in; the window ends when ``close()`` has flushed, written the footer and
synced.

Storage is a file in the run's scratch directory on the checkout's file
system, synced once when the writer closes (``fsync_policy`` on_close),
and removed when the run ends.

The check reads the whole file back through the program's reader: the
entry count; every cluster's ids (each cluster holds whole fills of one
producer, so its ids are one contiguous run, and every producer's runs
must tile its fills exactly); and every collection size and value
against the generator's.
"""

from __future__ import annotations

import os
import shutil
import threading
import time
from pathlib import Path

import numpy as np

import common
from generators import synth_events

ID_SHIFT = 40
def schema_and_options(config: dict):
    from repro.core import Collection, Leaf, Schema, WriteOptions

    schema = Schema([Leaf("id", "int64"),
                     Collection("vals", Leaf("_0", "float32"))])
    opts = WriteOptions(page_size=config["page_size"], codec=config["codec"],
                        level=config["level"],
                        cluster_bytes=config["cluster_bytes"],
                        buffered=config["buffered"],
                        fsync_policy=config["fsync_policy"])
    return schema, opts


class Run:
    def __init__(self, config, traffic, seed, devices, scratch: Path):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.scratch = devices, scratch
        self.spans = common.Spans()
        self.writer = None

    def setup(self) -> None:
        from repro.core import ColumnBatch, DevNullSink, ParallelWriter
        from repro.core import encoding

        cfg, tr = self.config, self.traffic
        self.schema, self.options = schema_and_options(cfg)
        e = tr["batch_entries"]
        rngs = [np.random.default_rng([self.seed, p])
                for p in range(tr["producers"])]
        self.pools = []
        for rng in rngs:
            pool, used = [], 0
            while used < cfg["cluster_bytes"]:
                ev = synth_events(rng, e, mean_size=cfg["collection_mean"],
                                  low=cfg["value_low"], high=cfg["value_high"])
                pool.append(ev)
                used += 16 * e + 4 * len(ev.values)   # id + offset + values
            self.pools.append(pool)
        self.batches = [[ColumnBatch.from_arrays(
            self.schema, e, {"id": ev.ids, "vals": ev.sizes,
                             "vals._0": ev.values}) for ev in pool]
            for pool in self.pools]
        self.id_col = self.schema.column_of_path["id"]
        self.arange = np.arange(e, dtype=np.int64)

        t0 = time.perf_counter()
        calls0 = encoding.BYTESHUFFLE.calls + encoding.OFFSETS_SCAN.calls
        warm = ParallelWriter(self.schema, DevNullSink(), self.options)
        for p in range(tr["producers"]):
            ctx = warm.create_fill_context()
            for k in range(len(self.batches[p])):
                self._fill(ctx, p, k)
            ctx.close()
        warm.close()
        print(f"[setup] warm-up: one cluster per producer in "
              f"{time.perf_counter() - t0:.3f} s, "
              f"{encoding.BYTESHUFFLE.calls + encoding.OFFSETS_SCAN.calls - calls0}"
              f" kernel calls", flush=True)
        self.path = self.scratch / "events.rntj"
        free = shutil.disk_usage(self.scratch).free
        print(f"[setup] file {self.path}: {free / 1e9:.3f} GB free on its "
              f"file system", flush=True)
        self.writer = ParallelWriter(self.schema, str(self.path), self.options)
        self.ctxs = [self.writer.create_fill_context()
                     for _ in range(tr["producers"])]

    def _fill(self, ctx, p: int, k: int) -> int:
        """Producer ``p``'s ``k``-th fill; returns its user bytes."""
        pool = self.batches[p]
        b = pool[k % len(pool)]
        np.add(self.arange, (p << ID_SHIFT) + k * len(self.arange),
               out=b.data[self.id_col])
        ctx.fill_batch(b)
        return 8 * b.n_entries + 4 * len(self.pools[p][k % len(pool)].values)

    def window(self, seconds: float, tracer) -> dict:
        from repro.core import encoding

        tr = self.traffic
        n_p = tr["producers"]
        self.fills = [0] * n_p
        nbytes = [0] * n_p
        errors = []
        st = self.writer.stats
        calls0 = (encoding.BYTESHUFFLE.calls, encoding.OFFSETS_SCAN.calls)
        t0 = time.perf_counter()
        deadline = t0 + seconds

        def producer(p):
            try:
                k, per = 0, len(self.batches[p])
                while k % per or time.perf_counter() < deadline:
                    with self.spans.span("fill"):
                        nbytes[p] += self._fill(self.ctxs[p], p, k)
                    k += 1
                self.fills[p] = k
            except BaseException as exc:   # re-raised on the main thread
                errors.append(exc)

        threads = [threading.Thread(target=producer, args=(p,))
                   for p in range(n_p)]
        for t in threads:
            t.start()
        if tracer is not None:
            time.sleep(max(0.0, t0 + seconds - tr["trace_seconds"]
                           - time.perf_counter()))
            tracer.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        with self.spans.span("close"):
            self.writer.close()
        t1 = time.perf_counter()
        if tracer is not None:
            tracer.stop()
        window_s = t1 - t0
        entries = sum(self.fills) * tr["batch_entries"]
        s = st.as_dict()
        print(f"[window] writer stats {s}", flush=True)
        return {
            "write_mb_per_s": sum(nbytes) / 1e6 / window_s,
            "attempted": entries, "failed": 0, "window_s": window_s,
            "entries": entries, "user_bytes": sum(nbytes),
            "file_bytes": os.path.getsize(self.path),
            "producers": n_p,
            "compress_ns": st.compress_ns, "io_ns": st.io_ns,
            "io_stall_ns": st.io_stall_ns, "lock_wait_ns": st.lock.wait_ns,
            "byteshuffle_calls": encoding.BYTESHUFFLE.calls - calls0[0],
            "offsets_scan_calls": encoding.OFFSETS_SCAN.calls - calls0[1],
        }

    def release(self) -> None:
        self.writer = None
        self.ctxs = None

    def check(self) -> list:
        from repro.core import ReadOptions, RNTJReader

        tr = self.traffic
        e = tr["batch_entries"]
        c_id = self.id_col
        c_off = self.schema.column_of_path["vals"]
        c_val = self.schema.column_of_path["vals._0"]
        t0 = time.perf_counter()
        r = RNTJReader(str(self.path), ReadOptions(
            decode_workers=os.cpu_count() or 1, prefetch_clusters=4))
        runs = {p: [] for p in range(tr["producers"])}
        bad_ids = bad_vals = 0
        try:
            wrong_count = abs(r.n_entries - sum(self.fills) * e)
            for ci, cols in r.iter_clusters([c_id, c_off, c_val]):
                ids = cols[c_id]
                n = len(ids)
                p = int(ids[0]) >> ID_SHIFT
                low = int(ids[0]) - (p << ID_SHIFT)
                if p not in runs or low % e or n % e:
                    bad_ids += n
                    continue
                bad_ids += int(np.count_nonzero(
                    ids != np.arange(ids[0], ids[0] + n, dtype=np.int64)))
                runs[p].append((low, low + n))
                sizes = np.diff(cols[c_off], prepend=0)
                want_s, want_v = self._expected(p, low, low + n)
                if not np.array_equal(sizes, want_s):
                    bad_vals += n
                    continue
                bad_vals += int(np.count_nonzero(
                    cols[c_val].view(np.uint32) != want_v.view(np.uint32)))
            n_clusters = r.n_clusters
        finally:
            r.close()
        for p, rs in runs.items():
            at = 0
            for low, high in sorted(rs):
                bad_ids += abs(low - at)
                at = max(at, high)
            bad_ids += abs(self.fills[p] * e - at)
        print(f"[check] {n_clusters} clusters, every entry read back in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        lim = tr["limits"]
        return [
            common.Check("entry_count_gap", wrong_count, lim["entry_count_gap"]),
            common.Check("ids_missing_or_extra", bad_ids,
                         lim["ids_missing_or_extra"]),
            common.Check("values_wrong", bad_vals, lim["values_wrong"]),
        ]

    def _expected(self, p: int, low: int, high: int):
        """Sizes and values of producer ``p``'s entries ``low..high-1``
        (whole fills), as the generator made them."""
        e = self.traffic["batch_entries"]
        pool = self.pools[p]
        sizes, vals = [], []
        for k in range(low // e, -(-high // e)):
            ev = pool[k % len(pool)]
            sizes.append(ev.sizes)
            vals.append(ev.values)
        return np.concatenate(sizes), np.concatenate(vals)

    def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
