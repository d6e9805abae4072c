"""Seeded input generators, vectorised copies of the program's own.

* :func:`synth_corpus` — tokenized documents with log-normal lengths
  (median ``mean_len``, sigma ``sigma``, at least ``min_len`` tokens), each
  a Zipf-weighted concatenation of a fixed phrase inventory (phrases of
  8..31 tokens drawn uniformly from ``[0, vocab)``), cut to its length.
  Same distributions as ``repro.pipeline.ingest.synth_corpus``.
* :func:`synth_events` — the paper's synthetic events (arXiv:2410.14239
  §6): an int64 id and a Poisson(5) collection of uniform [0, 100)
  float32 values.  Same distributions as ``benchmarks/_harness.synth_batch``.

Both return flat arrays (values plus per-row sizes), never Python lists of
rows, so that set-up stays short at millions of tokens.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Corpus:
    """Documents ``0..n-1`` as one flat token array plus their lengths."""

    tokens: np.ndarray   # int32, all documents back to back
    lengths: np.ndarray  # int64, one per document

    @property
    def n_docs(self) -> int:
        return len(self.lengths)

    @property
    def starts(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.lengths)[:-1]]).astype(np.int64)

    def doc(self, i: int) -> np.ndarray:
        s = int(self.starts[i])
        return self.tokens[s:s + int(self.lengths[i])]


def synth_corpus(seed: int, min_tokens: int, vocab: int, mean_len: int = 512,
                 sigma: float = 0.6, min_len: int = 8, n_phrases: int = 512,
                 phrase_min: int = 8, phrase_max: int = 32,
                 length_seed: int | None = None) -> Corpus:
    """Documents until their tokens reach ``min_tokens``.

    With ``length_seed`` the document lengths come from a stream of their
    own, the same for every ``seed``, and ``seed`` draws only the tokens:
    every seed then gives files of the same shapes."""
    rng = np.random.default_rng(seed)
    len_rng = rng if length_seed is None else np.random.default_rng(length_seed)
    plen = rng.integers(phrase_min, phrase_max, n_phrases)
    pstart = np.concatenate([[0], np.cumsum(plen)[:-1]])
    ptoks = rng.integers(0, vocab, int(plen.sum())).astype(np.int32)
    zipf = 1.0 / np.arange(1, n_phrases + 1)
    zipf /= zipf.sum()

    # document lengths: enough for min_tokens, then trimmed to the first
    # prefix that reaches it
    n_est = int(min_tokens / (mean_len * np.exp(sigma ** 2 / 2)) * 1.2) + 16
    lengths = np.empty(0, np.int64)
    while lengths.sum() < min_tokens:
        more = np.maximum(min_len, len_rng.lognormal(np.log(mean_len), sigma, n_est)
                          .astype(np.int64))
        lengths = np.concatenate([lengths, more])
    lengths = lengths[: int(np.searchsorted(np.cumsum(lengths), min_tokens)) + 1]

    # one stream of phrase picks; document i takes whole phrases from where
    # document i-1 stopped until it has its length, then is cut to it
    mean_plen = float(plen @ zipf)
    n_picks = int(lengths.sum() / mean_plen * 1.1 + 2 * len(lengths) + 64)
    picks = rng.choice(n_phrases, n_picks, p=zipf)
    ends = np.cumsum(plen[picks])
    first = np.empty(len(lengths), np.int64)
    pos, base = 0, 0
    for i, n in enumerate(lengths.tolist()):
        while True:
            last = int(np.searchsorted(ends, base + n, side="left"))
            if last < len(picks):
                break
            extra = rng.choice(n_phrases, n_picks // 4 + 64, p=zipf)
            picks = np.concatenate([picks, extra])
            ends = np.cumsum(plen[picks])
        first[i] = pos
        pos = last + 1
        base = int(ends[last])
    used = picks[:pos]
    # token position of each used pick's first token in the pick stream
    pick_start = np.concatenate([[0], ends[:pos - 1]])
    stream_idx = (np.repeat(pstart[used] - pick_start, plen[used])
                  + np.arange(int(ends[pos - 1])))
    stream = ptoks[stream_idx]
    doc_begin = pick_start[first]
    take = (np.repeat(doc_begin - np.concatenate([[0], np.cumsum(lengths)[:-1]]),
                      lengths) + np.arange(int(lengths.sum())))
    return Corpus(tokens=stream[take], lengths=lengths)


@dataclass
class Events:
    """``n`` synthetic events: ids, collection sizes and the flat values."""

    ids: np.ndarray     # int64
    sizes: np.ndarray   # int64
    values: np.ndarray  # float32


def synth_events(rng: np.random.Generator, n: int, id0: int = 0,
                 mean_size: float = 5.0, low: float = 0.0,
                 high: float = 100.0) -> Events:
    sizes = rng.poisson(mean_size, n).astype(np.int64)
    values = rng.uniform(low, high, int(sizes.sum())).astype(np.float32)
    return Events(ids=np.arange(id0, id0 + n, dtype=np.int64), sizes=sizes,
                  values=values)
