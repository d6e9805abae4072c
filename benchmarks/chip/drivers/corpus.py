"""A tokenized corpus on disk, and the token stream a packing loader
must produce from it: the set-up that the train and loader cells share.

Every seed gives a file of the same shapes: the document lengths come
from the traffic's ``doc_len_seed``, the seed draws only the tokens, and
each producer ingests a fixed share of the documents.  The program's
loader compiles its decode and packing per cluster shape, so only a
checkout's first run compiles them; the rest find them in the cache.
"""

from __future__ import annotations

import threading
import time
from pathlib import Path

import numpy as np

from generators import Corpus, synth_corpus


def make(config: dict, traffic: dict, seed: int, path: Path) -> dict:
    """Generate the corpus from the seed and ingest it with the program's
    parallel writer (see :func:`ingest`).  Returns the corpus, the
    document ids in file order, and the reference token stream."""
    from repro.core import encoding

    data = config["data"]
    t0 = time.perf_counter()
    corpus = synth_corpus(seed, traffic["corpus_tokens"], config["vocab_size"],
                          mean_len=traffic["doc_len_median"],
                          sigma=traffic["doc_len_sigma"],
                          min_len=traffic["doc_len_min"],
                          n_phrases=traffic["phrases"],
                          length_seed=traffic["doc_len_seed"])
    t1 = time.perf_counter()
    calls = (encoding.BYTESHUFFLE.calls, encoding.OFFSETS_SCAN.calls)
    ingest(corpus, path, data)
    t2 = time.perf_counter()
    order = file_order(path)
    stream = reference_stream(corpus, order, data["eos_id"])
    print(f"[setup] corpus {corpus.n_docs} docs, {int(corpus.lengths.sum())} "
          f"tokens, generated in {t1 - t0:.3f} s, ingested by "
          f"{data['producers']} producers in {t2 - t1:.3f} s; write-side "
          f"kernel calls: byteshuffle "
          f"{encoding.BYTESHUFFLE.calls - calls[0]}, offsets_scan "
          f"{encoding.OFFSETS_SCAN.calls - calls[1]}", flush=True)
    return {"corpus": corpus, "order": order, "stream": stream}


def ingest(corpus: Corpus, path: Path, data: dict) -> None:
    """``ingest_corpus`` with a fixed share per producer.

    As the program's ``ingest_corpus``: ``producers`` threads, each with a
    fill context of one ``ParallelWriter``, fill ``batch_docs`` documents
    at a time, with its write options.  It differs in one thing: batch
    ``j`` goes to producer ``j % producers`` instead of whichever producer
    pulls first, so that which documents share a cluster, and hence each
    cluster's shape, does not depend on thread timing.  Only the order of
    the clusters in the file still does."""
    from repro.core import ParallelWriter, WriteOptions
    from repro.pipeline import DOC_SCHEMA, docs_to_batch

    n_p, per = data["producers"], data["batch_docs"]
    writer = ParallelWriter(DOC_SCHEMA, str(path), WriteOptions(
        codec=data["codec"], level=data["level"],
        cluster_bytes=data["cluster_bytes"]))
    errors = []

    def producer(p: int) -> None:
        try:
            ctx = writer.create_fill_context()
            for j0 in range(p * per, corpus.n_docs, n_p * per):
                ids = np.arange(j0, min(j0 + per, corpus.n_docs), dtype=np.int64)
                ctx.fill_batch(docs_to_batch(ids, [corpus.doc(i) for i in ids]))
            ctx.close()
        except BaseException as exc:   # re-raised on the main thread
            errors.append(exc)

    threads = [threading.Thread(target=producer, args=(p,)) for p in range(n_p)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    writer.close()


def file_order(path: Path) -> np.ndarray:
    """Document ids in the order the file holds them.  The parallel writer
    commits clusters in whatever order its producers finish (the paper's
    protocol), so the order is read back from the ``doc_id`` column; the
    tokens themselves are never taken from the file."""
    from repro.core import RNTJReader

    r = RNTJReader(str(path))
    try:
        c_id = r.schema.column_of_path["doc_id"]
        return np.concatenate([r.read_cluster(ci, [c_id])[c_id]
                               for ci in range(r.n_clusters)])
    finally:
        r.close()


def reference_stream(corpus: Corpus, order: np.ndarray, eos: int) -> np.ndarray:
    """Documents in file order, each followed by ``eos``: one epoch of the
    packed stream."""
    if not np.array_equal(np.sort(order), np.arange(corpus.n_docs)):
        raise RuntimeError("the file does not hold each document exactly once")
    lens = corpus.lengths[order]
    n_tok = int(lens.sum())
    out = np.full(n_tok + len(order), eos, np.int32)
    is_tok = np.ones(len(out), bool)
    is_tok[np.cumsum(lens + 1) - 1] = False
    within = np.arange(n_tok) - np.repeat(np.cumsum(lens) - lens, lens)
    out[is_tok] = corpus.tokens[np.repeat(corpus.starts[order], lens) + within]
    return out


def batch_grid(stream: np.ndarray, k: int, batch: int, seq: int) -> np.ndarray:
    """Batch ``k`` of the packed stream (epochs wrap): a (batch, seq + 1)
    grid whose rows give tokens ``[:, :-1]`` and labels ``[:, 1:]``."""
    need = batch * (seq + 1)
    idx = (np.arange(need, dtype=np.int64) + k * need) % len(stream)
    return stream[idx].reshape(batch, seq + 1)
