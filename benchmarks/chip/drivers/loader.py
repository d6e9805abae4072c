"""Loader cells: ``PackedLoader.batches()`` (device engine) drained into a
jitted checksum consumer, with no train step behind it.

Set-up generates and ingests the corpus, then draws one whole epoch (and
the first batch of the next) through the same loader and consumer that
the window uses, so that every cluster's decode and packing programs
exist before the window opens.  The window keeps drawing from that same
generator until the deadline; it ends when the consumer's running
checksum is ready on the device.

The consumer folds each batch into a 32-bit checksum that is sensitive to
every token and to the order of batches.  The check recomputes it on the
host from the reference packing of the documents in file order, over
every batch drawn since the loader started, the window's included.
"""

from __future__ import annotations

import time
from pathlib import Path

import numpy as np

import common
from drivers import corpus as corpus_mod

MULT = np.uint32(0x9E3779B1)


def weights(batch: int, seq: int) -> np.ndarray:
    """Odd per-position weights: a token changed by any amount below 2**32
    changes the checksum."""
    i = np.arange(2 * batch * seq, dtype=np.uint64)
    return ((i * np.uint64(2654435761) + np.uint64(12345)) | np.uint64(1)).astype(
        np.uint32).reshape(2, batch, seq)


def fold(acc, tokens, labels, w):
    """Device consumer: ``acc * MULT + sum(w * [tokens, labels])`` mod 2**32."""
    import jax.numpy as jnp

    h = (jnp.sum(tokens.astype(jnp.uint32) * w[0], dtype=jnp.uint32)
         + jnp.sum(labels.astype(jnp.uint32) * w[1], dtype=jnp.uint32))
    return acc * jnp.uint32(MULT) + h


def host_checksum(stream: np.ndarray, n_batches: int, batch: int, seq: int,
                  w: np.ndarray, chunk: int = 256) -> int:
    """The consumer's checksum after ``n_batches`` batches of ``stream``."""
    need = batch * (seq + 1)
    w64 = w.astype(np.uint64)
    acc, mult, mask = 0, int(MULT), 0xFFFFFFFF
    for k0 in range(0, n_batches, chunk):
        k1 = min(n_batches, k0 + chunk)
        idx = (np.arange(k0 * need, k1 * need, dtype=np.int64)) % len(stream)
        g = stream[idx].reshape(k1 - k0, batch, seq + 1).astype(np.uint64)
        h = (np.sum(g[:, :, :-1] * w64[0], axis=(1, 2))
             + np.sum(g[:, :, 1:] * w64[1], axis=(1, 2)))
        for x in h.tolist():
            acc = (acc * mult + (x & mask)) & mask
    return acc


class Run:
    def __init__(self, config, traffic, seed, devices, scratch: Path):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.scratch = devices, scratch
        self.spans = common.Spans()
        self.loader = None

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.pipeline import PackedLoader

        cfg, tr = self.config, self.traffic
        b, s = tr["batch"], tr["seq_len"]
        path = self.scratch / "corpus.rntj"
        self.stream = corpus_mod.make(cfg, tr, self.seed, path)["stream"]
        self.loader = PackedLoader(str(path), batch=b, seq_len=s,
                                   eos_id=cfg["data"]["eos_id"], device="device")
        self.w_host = weights(b, s)
        self.w = jax.device_put(self.w_host, self.devices[0])
        self.fold = jax.jit(fold)
        self.acc = jax.device_put(np.uint32(0), self.devices[0])
        self.gen = self.loader.batches()
        self.drawn = 0
        t0 = time.perf_counter()
        for _ in range(len(self.stream) // (b * (s + 1)) + 2):
            self._consume(next(self.gen))
        self.acc.block_until_ready()
        print(f"[setup] warm epoch: {self.drawn} batches in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)

    def _consume(self, batch) -> None:
        self.acc = self.fold(self.acc, batch["tokens"], batch["labels"], self.w)
        self.drawn += 1

    def window(self, seconds: float, tracer) -> dict:
        tr = self.traffic
        st = self.loader.reader.stats
        before = (st.io_ns, st.decompress_ns, st.h2d_ns, st.device_clusters)
        drawn0 = self.drawn
        t0 = time.perf_counter()
        deadline = t0 + seconds
        trace_at = t0 + max(0.0, seconds - tr["trace_seconds"])
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            if tracer is not None and tracer.t0 is None and now >= trace_at:
                tracer.start()
            with self.spans.span("loader_next"):
                batch = next(self.gen)
            with self.spans.span("consume"):
                self._consume(batch)
        self.acc.block_until_ready()
        t1 = time.perf_counter()
        if tracer is not None:
            tracer.stop()
        after = (st.io_ns, st.decompress_ns, st.h2d_ns, st.device_clusters)
        d = [a - b_ for a, b_ in zip(after, before)]
        n = self.drawn - drawn0
        window_s = t1 - t0
        t0n, t1n = int(t0 * 1e9), int(t1 * 1e9)
        return {
            "loader_tokens_per_s": n * tr["batch"] * tr["seq_len"] / window_s,
            "attempted": n, "failed": 0, "window_s": window_s, "batches": n,
            "reader_io_ns": d[0], "reader_decompress_ns": d[1],
            "reader_h2d_ns": d[2], "device_clusters": d[3],
            "loader_next_s": self.spans.total_ns("loader_next", t0n, t1n) / 1e9,
            "slowest_loader_next_s":
                self.spans.longest_ns("loader_next", t0n, t1n) / 1e9,
        }

    def release(self) -> None:
        self.acc_value = int(np.asarray(self.acc))
        self.loader.close()
        self.loader = None
        self.gen = None

    def check(self) -> list:
        tr = self.traffic
        want = host_checksum(self.stream, self.drawn, tr["batch"],
                             tr["seq_len"], self.w_host)
        return [common.Check("checksum_mismatch", int(want != self.acc_value),
                             self.traffic["limits"]["checksum_mismatch"])]

    def close(self) -> None:
        if self.loader is not None:
            self.loader.close()
