"""Single-file parallel checkpointing of sharded pytrees.

This is the paper's technique applied to the checkpoint path of a training
framework: every (virtual) host serializes + compresses its parameter
shards into relocatable clusters of ONE RNT-J file in parallel — no
per-host file tree and no post-hoc merge step (contrast: Orbax/tensorstore
write per-host files = the paper's "independent files + merge" baseline).

Checkpoint schema (nested, variable length — exactly the data shape the
format exists for)::

    entry := { param_id:int32, shard_index:int32,
               shape:[int64], row_start:int64, row_end:int64,
               data:[uint8] }

Entry param_id == -1 carries the JSON manifest (tree structure, names,
dtypes, step metadata).  Restore is mesh-shape-agnostic: clusters are
self-describing, so any number of readers can re-partition them (elastic
restart across different host counts).
"""

from __future__ import annotations

import dataclasses
import json
import multiprocessing
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import ParallelWriter, RNTJReader, WriteOptions
from repro.core.mpwrite import MultiWriterCoordinator

from ._mpworker import CKPT_SCHEMA, _entry_batch, _np_dtype, run_save_worker


def _flatten_with_names(tree) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        out.append((name, leaf))
    return out, treedef


def _host_arrays(leaves) -> List[np.ndarray]:
    def _host(l):
        a = np.asarray(l)
        # ascontiguousarray promotes 0-d to 1-d; keep true rank
        return np.ascontiguousarray(a) if a.ndim else a

    return [_host(l) for _, l in leaves]


def _work_units(arrays: List[np.ndarray],
                row_block_bytes: int) -> List[Tuple[int, int, int]]:
    """(param_id, row range) blocks so large tensors spread across
    writers; every unit is independent (paper §1's reorderable rows)."""
    units: List[Tuple[int, int, int]] = []
    for pid, arr in enumerate(arrays):
        rows = arr.shape[0] if arr.ndim else 1
        row_bytes = max(1, arr.nbytes // max(rows, 1))
        block = max(1, row_block_bytes // row_bytes)
        start = 0
        while start < rows or (rows == 0 and start == 0):
            end = min(rows, start + block)
            units.append((pid, start, end))
            if end >= rows:
                break
            start = end
    return units


def _build_manifest(leaves, metadata: Optional[Dict]) -> Dict:
    return {
        "names": [n for n, _ in leaves],
        "dtypes": [str(l.dtype) for _, l in leaves],
        "shapes": [list(np.shape(l)) for _, l in leaves],
        "treedef": None,  # reconstructed from names at load
        "metadata": metadata or {},
    }


def _manifest_entry(manifest: Dict) -> Dict:
    return {
        "param_id": -1, "shard_index": 0, "shape": [],
        "row_start": 0, "row_end": 0,
        "data": json.dumps(manifest).encode(),
    }


def _unit_entry(arrays, u: int, unit: Tuple[int, int, int]) -> Dict:
    pid, r0, r1 = unit
    arr = arrays[pid]
    piece = arr[r0:r1] if arr.ndim else arr
    return {
        "param_id": pid, "shard_index": u,
        "shape": list(arr.shape),
        "row_start": r0, "row_end": r1,
        "data": piece.tobytes(),
    }


def save_checkpoint(
    path: str,
    tree,
    n_writers: int = 4,
    row_block_bytes: int = 4 * 1024 * 1024,
    options: Optional[WriteOptions] = None,
    metadata: Optional[Dict] = None,
) -> Dict:
    """Parallel single-file save.

    ``n_writers`` simulates hosts: work (leaf row-blocks) is partitioned
    round-robin; each writer thread owns a fill context and commits its
    clusters through the shared reserve+metadata critical section.  In a
    real multi-host deployment each jax process runs one writer over its
    addressable shards and the critical section is the coordinator's
    extent ledger (DESIGN.md §3.2).
    """
    # journal=False: checkpoint durability comes from the temp-file +
    # atomic-rename commit protocol (a torn save is discarded wholesale,
    # never salvaged), so the per-cluster recovery framing would only add
    # bytes that no reader CRC covers — without it, every byte of a
    # committed checkpoint is checksummed and a flip is always detected
    options = options or WriteOptions(
        codec="zlib", level=1, cluster_bytes=32 * 1024 * 1024, journal=False
    )
    leaves, treedef = _flatten_with_names(tree)
    manifest = _build_manifest(leaves, metadata)
    arrays = _host_arrays(leaves)
    units = _work_units(arrays, row_block_bytes)

    writer = ParallelWriter(CKPT_SCHEMA, path, options)

    # manifest entry (param_id = -1) goes in first
    mctx = writer.create_fill_context()
    mctx.fill_batch(_entry_batch([_manifest_entry(manifest)]))
    mctx.flush_cluster()

    def worker(widx: int):
        ctx = writer.create_fill_context()
        batch: List[Dict] = []
        for u, unit in enumerate(units):
            if u % n_writers != widx:
                continue
            batch.append(_unit_entry(arrays, u, unit))
            if sum(len(e["data"]) for e in batch) >= row_block_bytes:
                ctx.fill_batch(_entry_batch(batch))
                batch = []
        if batch:
            ctx.fill_batch(_entry_batch(batch))
        ctx.close()

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    writer.close()
    return writer.stats.as_dict()


def save_checkpoint_mp(
    path: str,
    tree,
    n_processes: int = 2,
    row_block_bytes: int = 4 * 1024 * 1024,
    options: Optional[WriteOptions] = None,
    metadata: Optional[Dict] = None,
    mp_context: str = "spawn",
    crash_worker: Optional[int] = None,
    crash_after_units: int = 1,
) -> Dict:
    """N-**process** sharded save into ONE container file.

    The real-deployment shape of :func:`save_checkpoint`: each writer is
    a separate OS process joining the shared file through the side-car
    extent log (DESIGN.md §8.6) instead of a thread sharing the in-process
    reserve lock.  The parent acts as coordinator — it writes the manifest
    cluster through an in-process participant, hands each child its
    round-robin share of work units (pickled host arrays), then runs the
    footer-assembly rendezvous.

    A worker killed mid-save (or ``crash_worker=i`` for tests: worker *i*
    hard-exits after ``crash_after_units`` entries) is fenced at lease
    expiry and the seal degrades gracefully: every fully journaled cluster
    is kept, the crash is recorded in ``footer.extra["mpw"]``, and the
    returned report has ``degraded=True`` so callers (CheckpointManager)
    can refuse to commit.  ``load_checkpoint(strict=False)`` restores the
    surviving parameters from such a file.

    Unlike the thread path, mp saves keep ``journal=True`` — the journal
    framing is what makes per-writer clusters independently salvageable.
    """
    options = options or WriteOptions(
        codec="zlib", level=1, cluster_bytes=32 * 1024 * 1024,
        lease_interval=2.0,
    )
    if not (options.buffered and options.journal):
        options = dataclasses.replace(options, buffered=True, journal=True)

    leaves, treedef = _flatten_with_names(tree)
    manifest = _build_manifest(leaves, metadata)
    arrays = _host_arrays(leaves)
    units = _work_units(arrays, row_block_bytes)

    # Round-robin shards, materialized as picklable entry dicts.  In a
    # real multi-host job each process owns its addressable shards and no
    # bytes cross processes; here the parent holds the whole tree, so the
    # hand-off is the pickle through the spawn pipe.
    shards: List[List[Dict]] = [[] for _ in range(n_processes)]
    for u, unit in enumerate(units):
        shards[u % n_processes].append(_unit_entry(arrays, u, unit))

    # the with-block skips the rendezvous when the body raises, so a
    # parent-side failure doesn't stall on the straggler timeout
    with MultiWriterCoordinator(CKPT_SCHEMA, path, options) as coord:
        mw = coord.participant()
        mctx = mw.create_fill_context()
        mctx.fill_batch(_entry_batch([_manifest_entry(manifest)]))
        mctx.flush_cluster()
        mw.close()

        ctx = multiprocessing.get_context(mp_context)
        procs = []
        for i in range(n_processes):
            crash = crash_after_units if crash_worker == i else None
            p = ctx.Process(
                target=run_save_worker,
                args=(path, shards[i], row_block_bytes, options, crash),
            )
            p.start()
            procs.append(p)
        for p in procs:
            p.join()
        exitcodes = [p.exitcode for p in procs]

        report = coord.seal(expect_writers=1 + n_processes)

    report["worker_exitcodes"] = exitcodes
    report["degraded"] = bool(
        report["fenced"] or report["salvaged"] or report["abandoned"]
        or any(c != 0 for c in exitcodes)
    )
    return report


def load_checkpoint(path: str, target_tree=None, shardings=None,
                    strict: bool = True):
    """-> (tree, metadata).  Reassembles from any cluster layout.

    Entries that arrive before the manifest are buffered, not rejected —
    a salvaged multi-writer file's cluster order is the global reservation
    order, which can interleave worker data ahead of the manifest.

    ``strict=False`` tolerates an *incomplete* checkpoint (a degraded
    multi-writer seal after a worker crash): parameters with missing
    shards come back zero-filled and their names are listed under
    ``metadata["restore_missing"]``.  With ``strict=True`` (default) any
    gap raises ``IOError``.
    """
    reader = RNTJReader(path)
    manifest = None
    buffers: Dict[int, np.ndarray] = {}
    covered: Dict[int, int] = {}
    pending: List[Tuple[int, tuple, int, int, np.ndarray]] = []

    def _apply(pid, shape, r0, r1, data):
        npdt = _np_dtype(manifest["dtypes"][pid])
        if pid not in buffers:
            # zeros (not empty) when gaps are tolerated: uncovered rows
            # must read as a defined value, not heap garbage
            alloc = np.empty if strict else np.zeros
            buffers[pid] = alloc(shape, npdt)
        piece = data.view(npdt)
        if buffers[pid].ndim:
            buffers[pid][r0:r1] = piece.reshape((r1 - r0,) + shape[1:])
            covered[pid] = covered.get(pid, 0) + (r1 - r0)
        else:
            buffers[pid] = piece.reshape(()).copy()
            covered[pid] = 1

    # column-at-a-time: each entry's payload is a zero-copy slice of the
    # cluster's decoded data column (no per-byte entry recomposition)
    col = [reader.schema.column_of_path[p] for p in (
        "param_id", "shape", "shape._0", "row_start", "row_end",
        "data", "data._0")]
    for ci in range(reader.n_clusters):
        c = reader.read_cluster(ci, col)
        pids, s_end, s_val, r0s, r1s, d_end, d_val = (c[i] for i in col)
        s_beg = np.concatenate([[0], s_end[:-1]])
        d_beg = np.concatenate([[0], d_end[:-1]])
        for k in range(len(pids)):
            pid = int(pids[k])
            data = d_val[d_beg[k]:d_end[k]]
            if pid == -1:
                manifest = json.loads(data.tobytes())
                for args in pending:
                    _apply(*args)
                pending = []
                continue
            shape = tuple(s_val[s_beg[k]:s_end[k]].tolist())
            r0, r1 = int(r0s[k]), int(r1s[k])
            if manifest is None:
                pending.append((pid, shape, r0, r1, data))
            else:
                _apply(pid, shape, r0, r1, data)
    reader.close()
    if manifest is None:
        raise IOError("checkpoint has no manifest entry")

    missing: List[str] = []
    leaves = []
    for pid, name in enumerate(manifest["names"]):
        shape = tuple(int(s) for s in manifest["shapes"][pid])
        need = shape[0] if shape else 1
        if covered.get(pid, 0) < need:
            missing.append(name)
            if pid not in buffers:
                buffers[pid] = np.zeros(shape, _np_dtype(manifest["dtypes"][pid]))
        leaves.append(buffers[pid])
    if missing and strict:
        raise IOError(
            f"checkpoint incomplete: missing or partial params {missing}"
        )

    tree = _unflatten_by_names(manifest["names"], leaves, target_tree)
    if shardings is not None:
        tree = jax.tree_util.tree_map(jax.device_put, tree, shardings)
    meta = dict(manifest["metadata"])
    if missing:
        meta["restore_missing"] = missing
    return tree, meta


def _unflatten_by_names(names: List[str], leaves, target_tree=None):
    if target_tree is not None:
        flat, treedef = jax.tree_util.tree_flatten_with_path(target_tree)
        by_name = dict(zip(names, leaves))
        ordered = [by_name[jax.tree_util.keystr(p)] for p, _ in flat]
        return jax.tree_util.tree_unflatten(treedef, ordered)
    # build nested dicts from keystr names like "['a']['b']"
    import re
    root: Dict = {}
    for name, leaf in zip(names, leaves):
        keys = re.findall(r"\['([^']*)'\]|\[(\d+)\]|\.([A-Za-z_]\w*)", name)
        keys = [k or i or a for k, i, a in keys]
        cur = root
        for k in keys[:-1]:
            cur = cur.setdefault(k, {})
        cur[keys[-1]] = leaf
    return root
