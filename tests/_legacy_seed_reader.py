"""The seed reader's hot path, vendored verbatim as a test reference.

This is the read path as it stood before the read-engine rebuild: one
``pread`` per page, serial per-page decompress+decode (``read_page``
allocates per page), ``np.concatenate`` per column, no coalescing, no
decode pool, no prefetch.  ``test_seed_compat.py`` checks that files
written by every commit mode and codec still decode through it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.container import FileSink, Sink
from repro.core.metadata import (
    ANCHOR_SIZE,
    ClusterMeta,
    parse_anchor,
    parse_footer,
    parse_header,
    parse_pagelist,
)
from repro.core.pages import read_page


class SeedRNTJReader:
    def __init__(self, sink_or_path, verify_checksums: bool = True):
        if isinstance(sink_or_path, str):
            self.sink: Sink = FileSink(sink_or_path, create=False)
        else:
            self.sink = sink_or_path
        if not self.sink.readable():
            raise IOError("sink is not readable")
        self.verify = verify_checksums
        size = self.sink.size
        anchor = parse_anchor(self.sink.pread(size - ANCHOR_SIZE, ANCHOR_SIZE))
        hoff, hsize = anchor["header"]
        foff, fsize = anchor["footer"]
        self.schema, self.options = parse_header(self.sink.pread(hoff, hsize))
        footer = parse_footer(self.sink.pread(foff, fsize))
        pl_off, pl_size = footer["pagelist"]
        self.clusters: List[ClusterMeta] = parse_pagelist(
            self.sink.pread(pl_off, pl_size)
        )
        self.n_entries = int(footer["n_entries"])

    @property
    def n_clusters(self) -> int:
        return len(self.clusters)

    def read_cluster(
        self, cluster_index: int, columns: Optional[Sequence[int]] = None
    ) -> Dict[int, np.ndarray]:
        cm = self.clusters[cluster_index]
        want = set(columns) if columns is not None else None
        parts: Dict[int, List[np.ndarray]] = {}
        for desc in cm.pages:
            if want is not None and desc.column not in want:
                continue
            col = self.schema.columns[desc.column]
            buf = self.sink.pread(desc.offset, desc.size)
            parts.setdefault(desc.column, []).append(
                read_page(buf, desc, col, self.verify)
            )
        out: Dict[int, np.ndarray] = {}
        targets = want if want is not None else range(self.schema.n_columns)
        for ci in targets:
            col = self.schema.columns[ci]
            chunks = parts.get(ci, [])
            if chunks:
                out[ci] = np.concatenate(chunks) if len(chunks) > 1 else chunks[0]
            else:
                out[ci] = np.empty(0, dtype=col.dtype)
        return out

    def close(self) -> None:
        self.sink.close()
