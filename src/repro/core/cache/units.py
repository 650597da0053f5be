"""Graph-aware cache units (paper §5.1).

Both units wrap one encoded column chunk and expose *value readers* that
retrieve attribute values by row index.  They differ in decode strategy,
matching the paper exactly:

- ``VertexCacheUnit`` — irregular (random) access pattern.  A decoded value
  array is pre-allocated for the whole chunk and populated **as a contiguous
  prefix**: a request for row 300 when only 100 rows are decoded extends the
  prefix through row 300.  Point lookups after that are plain array indexing.
  The invariant "decoded entries form a contiguous prefix" keeps status
  management a single integer (``_decoded_upto``) — the paper's rationale.

- ``EdgeCacheUnit`` — scan-oriented access with row-level evaluation for
  cross-entity predicates.  A sliding window buffer decodes values in batches
  around the requested index; re-requests inside the window are free; a
  request past the window advances it.  No full decoded array is kept because
  edges are too numerous (paper §7.6.2 shows the decoded-array design is not
  worth it for edges).

Decode-cost accounting (``decode_ops``) lets benchmarks reproduce Fig. 16
(graph-aware units vs naive re-decoding).

**Concurrency contract (DESIGN.md §5).**  Every unit carries its own
``lock``; callers that may run concurrently (the pipelined read path, the
prefetcher's I/O threads, concurrent serving queries) hold it around
``read``/``read_all`` so decode state mutates under exactly one thread.
A unit-lock holder may block on the manager's global lock (``on_growth``
fires mid-decode), but the manager never *blocks* on a unit lock while
holding its global lock — its eviction probe is non-blocking — so blocking
edges only point unit-lock → global-lock and cannot cycle.
``accounted_nbytes`` is the manager's
incremental byte-accounting watermark: the last ``nbytes()`` the manager has
charged against its memory budget.  Units report decoded-growth deltas
upward through the ``on_growth`` callback (installed at admission, wired to
``CacheManager.note_growth``) the moment their decoded state changes size —
the manager never re-sums live units to learn their footprint.
"""

from __future__ import annotations

import dataclasses
import threading

import numpy as np

from repro.lakehouse.encoding import decode_column
from repro.tracing import span


@dataclasses.dataclass(frozen=True)
class ChunkRef:
    """Identity of one column chunk: (table file, column, row group)."""

    file_key: str
    column: str
    row_group: int

    def cache_key(self) -> str:
        return f"{self.file_key}::{self.column}::{self.row_group}"


class VertexCacheUnit:
    """Decoded value array with a contiguous decoded prefix."""

    kind = "vertex"
    # sweep-clock priority (paper §5.2): vertex units are favored for retention
    priority = 3

    def __init__(self, ref: ChunkRef, raw_chunk: bytes, n_rows: int):
        self.ref = ref
        self._raw = raw_chunk
        self.n_rows = n_rows
        self._values: np.ndarray | None = None  # allocated lazily on first touch
        self._decoded_upto = 0
        self.decode_ops = 0
        self.pinned = 0
        self.lock = threading.Lock()
        self.accounted_nbytes = 0
        self.on_growth = None

    # -- decoded-state management ------------------------------------------------

    def _ensure_prefix(self, upto: int) -> None:
        """Extend the contiguous decoded prefix through row ``upto`` (exclusive)."""
        upto = min(int(upto), self.n_rows)
        if upto <= self._decoded_upto:
            return
        # the substrate decoder decodes prefixes natively (see encoding.py), so
        # extending the prefix costs only the *new* rows' decode work but one
        # pass over the stream; we count decoded rows as the work unit.
        with span("lake.decode", rows=upto):
            decoded = decode_column(self._raw, row_limit=upto)
        if self._values is None:
            # pre-allocate full capacity once: avoids resize/copy churn (§5.1)
            if decoded.dtype == object:
                self._values = np.empty(self.n_rows, dtype=object)
            else:
                self._values = np.empty(self.n_rows, dtype=decoded.dtype)
        self._values[self._decoded_upto: upto] = decoded[self._decoded_upto: upto]
        self.decode_ops += upto - self._decoded_upto
        self._decoded_upto = upto
        if self.on_growth is not None:
            self.on_growth(self)

    @property
    def decoded_prefix(self) -> int:
        return self._decoded_upto

    # -- value reader -------------------------------------------------------------

    def read(self, row_indices: np.ndarray) -> np.ndarray:
        """Point lookups by row index (vectorized)."""
        rows = np.asarray(row_indices, dtype=np.int64)
        if len(rows) == 0:
            dtype = self._values.dtype if self._values is not None else np.float64
            return np.empty(0, dtype=dtype)
        self._ensure_prefix(int(rows.max()) + 1)
        return self._values[rows]

    def read_all(self) -> np.ndarray:
        self._ensure_prefix(self.n_rows)
        return self._values

    # -- spill / restore (two-tier cache, §5.2) -----------------------------------

    def export_decoded(self) -> tuple[np.ndarray | None, int]:
        """Decoded state to flush to disk on eviction (vertex units only)."""
        return self._values, self._decoded_upto

    def import_decoded(self, values: np.ndarray, upto: int) -> None:
        self._values = values
        self._decoded_upto = upto

    def nbytes(self) -> int:
        n = len(self._raw)
        if self._values is not None and self._values.dtype != object:
            n += self._values.nbytes
        elif self._values is not None:
            n += sum(len(str(v)) for v in self._values[: self._decoded_upto])
        return n


class EdgeCacheUnit:
    """Sliding-window batch decoder for scan-oriented edge attributes."""

    kind = "edge"
    priority = 1

    def __init__(self, ref: ChunkRef, raw_chunk: bytes, n_rows: int, window: int = 4096):
        self.ref = ref
        self._raw = raw_chunk
        self.n_rows = n_rows
        self.window = window
        self._buf: np.ndarray | None = None
        self._buf_start = 0
        self.decode_ops = 0
        self.pinned = 0
        self.lock = threading.Lock()
        self.accounted_nbytes = 0
        self.on_growth = None

    def _advance(self, start: int, stop: int) -> None:
        stop = min(max(stop, start + self.window), self.n_rows)
        # the encoded stream decodes prefixes; a window [start, stop) costs a
        # prefix decode to `stop` (streams are not backward-seekable), but we
        # only *retain* the window — bounded memory, amortized batch decode.
        with span("lake.decode", rows=stop):
            decoded = decode_column(self._raw, row_limit=stop)
        self._buf = decoded[start:stop]
        self._buf_start = start
        self.decode_ops += stop - start
        if self.on_growth is not None:
            self.on_growth(self)

    def read(self, row_indices: np.ndarray) -> np.ndarray:
        """Batch row-level reads; indices are typically ascending during scans."""
        rows = np.asarray(row_indices, dtype=np.int64)
        if len(rows) == 0:
            dtype = self._buf.dtype if self._buf is not None else np.float64
            return np.empty(0, dtype=dtype)
        lo, hi = int(rows.min()), int(rows.max())
        if self._buf is None or lo < self._buf_start or hi >= self._buf_start + len(self._buf):
            # widen to cover the whole batch (scans hand us ascending batches)
            self._advance(lo, hi + 1)
        return self._buf[rows - self._buf_start]

    def read_all(self) -> np.ndarray:
        self._advance(0, self.n_rows)
        return self._buf

    def nbytes(self) -> int:
        n = len(self._raw)
        if self._buf is not None and self._buf.dtype != object:
            n += self._buf.nbytes
        return n


class NaiveChunkReader:
    """Baseline for Fig. 16: re-decodes the chunk on every batch request."""

    kind = "naive"
    priority = 1

    def __init__(self, ref: ChunkRef, raw_chunk: bytes, n_rows: int):
        self.ref = ref
        self._raw = raw_chunk
        self.n_rows = n_rows
        self.decode_ops = 0
        self.pinned = 0
        self.lock = threading.Lock()
        self.accounted_nbytes = 0
        self.on_growth = None  # naive readers retain nothing: never fires

    def read(self, row_indices: np.ndarray) -> np.ndarray:
        rows = np.asarray(row_indices, dtype=np.int64)
        if len(rows) == 0:
            return np.empty(0, dtype=np.float64)
        decoded = decode_column(self._raw, row_limit=int(rows.max()) + 1)
        self.decode_ops += int(rows.max()) + 1
        return decoded[rows]

    def read_all(self) -> np.ndarray:
        self.decode_ops += self.n_rows
        return decode_column(self._raw)

    def nbytes(self) -> int:
        return len(self._raw)
