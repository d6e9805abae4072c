"""Packing data loader: nested columnar docs -> fixed (B, S) token batches.

Deterministic and exactly resumable: the loader state is
``(entry_cursor, leftover_tokens)`` and is stored inside the training
checkpoint, so a restarted run continues mid-epoch on the same tokens.
Reads go cluster-at-a-time (the format's natural unit) with column
projection — no entry-by-entry Python loop on the hot path.

Two engines behind one contract (DESIGN.md §9):

* **host** — the original numpy path: ``read_cluster`` + a per-document
  Python loop feeding ``np.concatenate`` packing.
* **device** — built on :meth:`RNTJReader.iter_clusters_device`: stored
  page bytes upload once per cluster, columns materialize as JAX device
  arrays (offset columns as exact int32 ends), and the batch packing —
  document gather with EOS insertion, ``(B, S)`` reshape — runs as
  jitted device ops.  The training loop consumes the yielded batches
  with zero host-side copies, and cluster *N+1*'s I/O + decompression +
  H2D upload overlap cluster *N*'s decode and packing.

Both engines emit the byte-identical token stream (EOS-joined documents
in entry order, wrapped over epochs) and keep the same
``(entry_cursor, leftover)`` state: ``entry_cursor`` counts documents
pulled from the stream, ``leftover`` holds pulled-but-unemitted tokens.
A checkpoint written under either engine restores under either.
"""

from __future__ import annotations

import sys
from typing import Dict, Iterator, Optional

import numpy as np

from repro.core import RNTJReader
from repro.core.reader import ReadOptions
from repro.core.stats import span


def _pack_cluster(vals, offs, ndocs: int, eos_id: int):
    """Jitted device pack: a cluster's value column + offset column ->
    the packed token stream ``doc0 .. EOS doc1 .. EOS ...``.

    Gather formulation (an order of magnitude faster than the naive
    token scatter on CPU XLA): document ``k``'s EOS lands at output
    position ``offs[k] + k``, so a tiny ``ndocs``-element scatter marks
    the EOS slots, a cumsum over the marks counts completed documents
    before each position, and every other slot gathers token
    ``j - docs_before(j)``.
    """
    import jax.numpy as jnp

    n = vals.shape[0]
    n_out = n + ndocs
    eos_pos = offs + jnp.arange(ndocs, dtype=offs.dtype)
    mark = jnp.zeros(n_out, jnp.int32).at[eos_pos].set(1)
    docs_before = jnp.cumsum(mark) - mark
    j = jnp.arange(n_out, dtype=jnp.int32)
    tok = jnp.clip(j - docs_before, 0, max(n - 1, 0))
    return jnp.where(mark == 1, jnp.int32(eos_id),
                     vals.astype(jnp.int32)[tok])


def _pack_cluster_with_carry(carry, vals, offs, ndocs: int, eos_id: int):
    """Fused refill: carry-prefix concat + cluster pack in ONE jitted
    call, so the packed stream is written exactly once (a separate
    pack-then-concatenate costs an extra full sweep over the cluster's
    tokens on every refill)."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [carry, _pack_cluster(vals, offs, ndocs, eos_id)])


_jit_cache: Dict[str, object] = {}


def _jitted(name: str, fn, **kw):
    """Lazily ``jax.jit`` a module-level helper (jax imports on first use)."""
    if name not in _jit_cache:
        import jax

        _jit_cache[name] = jax.jit(fn, **kw)
    return _jit_cache[name]


def _batch_views(flat, pos, batch: int, seq_len: int):
    import jax

    grid = jax.lax.dynamic_slice(
        flat, (pos,), (batch * (seq_len + 1),)
    ).reshape(batch, seq_len + 1)
    return grid[:, :-1], grid[:, 1:]


class PackedLoader:
    """``device``: ``"auto"`` (device engine when jax is already imported
    by the application and the reader allows it), ``"device"`` (force),
    or ``"host"`` (the numpy path).  ``read_options`` tunes the
    underlying reader — in particular ``device_decode`` picks the fused
    decode backend and ``"off"`` pins the loader to the host engine.
    """

    def __init__(self, path: str, batch: int, seq_len: int,
                 eos_id: int = 0, state: Optional[Dict] = None,
                 device: str = "auto",
                 read_options: Optional[ReadOptions] = None):
        self.reader = RNTJReader(path, options=read_options)
        self.batch = batch
        self.seq_len = seq_len
        self.eos_id = eos_id
        self.device = device
        schema = self.reader.schema
        self._col_off = schema.column_of_path["tokens"]
        self._col_val = schema.column_of_path["tokens._0"]
        self.entry_cursor = 0
        self.leftover = np.empty(0, np.int32)
        # device-engine buffer: the packed stream lives on device as
        # (_flat, _pos) — flat tokens plus a consumed-prefix cursor — so
        # per-batch state updates are O(1) (no leftover re-slice copy)
        self._flat = None
        self._pos = 0
        if state:
            self.load_state(state)

    # -- resumable state ---------------------------------------------------

    def state(self) -> Dict:
        """The exact-resume state ``{entry_cursor, leftover}``.

        Under the device engine the leftover materializes to host here —
        checkpoint time is the one place the device stream syncs.
        """
        if self._flat is not None:
            left = np.asarray(self._flat)[self._pos:].copy()
        else:
            left = np.asarray(self.leftover, np.int32).copy()
        return {"entry_cursor": self.entry_cursor, "leftover": left}

    def load_state(self, state: Dict) -> None:
        """Restore ``(entry_cursor, leftover)``; applies to the next
        :meth:`batches` call (generators already running keep their own
        position, exactly like the host path)."""
        self.entry_cursor = int(state["entry_cursor"])
        self.leftover = np.asarray(state["leftover"], np.int32)
        self._flat = None
        self._pos = 0

    @property
    def n_docs(self) -> int:
        return self.reader.n_entries

    # -- engine selection --------------------------------------------------

    def _use_device(self) -> bool:
        if self.device == "host":
            return False
        if self.reader.read_options.device_decode == "off":
            return False
        if self.device == "device":
            return True
        # auto: never pay a cold jax import for data loading — the
        # training application has always already imported jax
        return "jax" in sys.modules

    # -- iteration ------------------------------------------------------------

    def batches(self) -> Iterator[Dict[str, np.ndarray]]:
        """Yields ``{tokens (B,S), labels (B,S)}`` forever (epoch-wrapped).

        Host engine yields numpy arrays; device engine yields JAX device
        arrays (``jnp.asarray`` in the train step is then a no-op).
        """
        if self._use_device():
            return self._device_batches()
        return self._host_batches()

    # -- host engine -------------------------------------------------------

    def _doc_stream(self) -> Iterator[np.ndarray]:
        """Docs starting at entry_cursor, wrapping around epochs."""
        while True:
            for ci in range(self.reader.n_clusters):
                first, last = self.reader.cluster_entry_range(ci)
                if last <= self.entry_cursor:
                    continue
                cols = self.reader.read_cluster(ci, [self._col_off, self._col_val])
                offs = cols[self._col_off]
                vals = cols[self._col_val]
                starts = np.concatenate([[0], offs[:-1]])
                lo = self.entry_cursor - first
                for j in range(lo, last - first):
                    self.entry_cursor += 1
                    yield vals[starts[j]:offs[j]].astype(np.int32)
            self.entry_cursor = 0  # next epoch

    def _host_batches(self) -> Iterator[Dict[str, np.ndarray]]:
        need = self.batch * (self.seq_len + 1)
        stream = self._doc_stream()
        buf = self.leftover
        while True:
            parts = [buf]
            total = len(buf)
            while total < need:
                doc = next(stream)
                parts.append(doc)
                parts.append(np.array([self.eos_id], np.int32))
                total += len(doc) + 1
            flat = np.concatenate(parts)
            chunk, self.leftover = flat[:need], flat[need:]
            buf = self.leftover
            grid = chunk.reshape(self.batch, self.seq_len + 1)
            yield {"tokens": grid[:, :-1].copy(), "labels": grid[:, 1:].copy()}

    # -- device engine -----------------------------------------------------

    def _device_stream(self):
        """Raw per-cluster device chunks ``(vals, offs, ndocs, drop)``
        starting at ``entry_cursor``, wrapping around epochs — the
        cluster-granular analog of :meth:`_doc_stream` (pulling a
        cluster advances ``entry_cursor`` to its end; the chunk's
        unemitted tail is the leftover).  ``drop`` is the count of
        already-consumed leading packed elements (mid-cluster resume
        only; 0 in steady state) — packing itself happens in
        :meth:`_device_batches` so the refill can fuse it with the
        carry concat."""
        import jax.numpy as jnp

        want = [self._col_off, self._col_val]
        while True:
            start_ci = None
            for ci in range(self.reader.n_clusters):
                _f, last = self.reader.cluster_entry_range(ci)
                if last > self.entry_cursor:
                    start_ci = ci
                    break
            if start_ci is None:
                self.entry_cursor = 0  # next epoch
                continue
            for i, cols in self.reader.iter_clusters_device(want, start=start_ci):
                first, last = self.reader.cluster_entry_range(i)
                o = cols[self._col_off]
                if isinstance(o, np.ndarray):  # host-fallback column
                    o = o.astype(np.int32)
                offs = jnp.asarray(o)
                vals = jnp.asarray(cols[self._col_val])
                lo = self.entry_cursor - first
                # mid-cluster resume: docs < lo and their EOS slots are
                # already consumed.  The one host sync of the stream
                # (restore only, never steady state).
                drop = (int(offs[lo - 1]) + lo) if lo > 0 else 0
                self.entry_cursor = last
                yield vals, offs, int(last - first), drop

    def _device_batches(self):
        import jax.numpy as jnp

        need = self.batch * (self.seq_len + 1)
        stream = self._device_stream()
        views = _jitted("batch_views", _batch_views,
                        static_argnames=("batch", "seq_len"))
        pack = _jitted("pack", _pack_cluster,
                       static_argnames=("ndocs", "eos_id"))
        pack_carry = _jitted("pack_carry", _pack_cluster_with_carry,
                             static_argnames=("ndocs", "eos_id"))
        if self._flat is None:
            left = np.asarray(self.leftover, np.int32)
            if left.shape[0] < need:
                # left-pad so _flat is always at least `need` long — the
                # refill below can then take a fixed (need,) carry slice
                pad = np.zeros(need - left.shape[0], np.int32)
                self._pos = pad.shape[0]
                self._flat = jnp.asarray(np.concatenate([pad, left]))
            else:
                self._flat = jnp.asarray(left)
                self._pos = 0
        n_batches = n_refills = 0
        while True:
            # one batch is the loader.next span; each cluster pulled for
            # it, a loader.refill span inside (no span stays open across
            # the yield)
            with span("loader.next", n_batches):
                avail = int(self._flat.shape[0]) - self._pos
                if avail < need:
                    # Right-align the remainder inside a fixed (need,)
                    # carry window so the concatenated shape depends only
                    # on WHICH clusters this refill pulls (per-cluster
                    # constants), not on the drifting remainder length.
                    # Shape drift here recompiles concatenate + the views
                    # jit on every refill, forever — the carry keeps
                    # steady state compile-free after the first epoch.
                    buf = self._flat[-need:]
                    total = avail
                    while total < need:
                        with span("loader.refill", n_refills):
                            vals, offs, ndocs, drop = next(stream)
                            if drop:  # restore-only: pack, slice, concat
                                chunk = pack(vals, offs, ndocs=ndocs,
                                             eos_id=self.eos_id)[drop:]
                                buf = jnp.concatenate([buf, chunk])
                            else:
                                buf = pack_carry(buf, vals, offs, ndocs=ndocs,
                                                 eos_id=self.eos_id)
                        n_refills += 1
                        total += int(vals.shape[0]) + ndocs - drop
                    self._flat = buf
                    self._pos = need - avail
                tokens, labels = views(self._flat, self._pos,
                                       batch=self.batch, seq_len=self.seq_len)
                self._pos += need
            n_batches += 1
            yield {"tokens": tokens, "labels": labels}

    def close(self) -> None:
        self.reader.close()
