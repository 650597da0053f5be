"""Two-tier cache with priority sweep-clock replacement (paper §5.2).

Memory tier holds live cache units; the disk tier holds (a) raw encoded
chunks and (b) decoded vertex value arrays flushed on eviction.  Eviction
policy is the paper's priority-aware sweep clock (PostgreSQL-style):

- on access, a unit's usage count resets to its priority (vertex 3, edge 1),
- the clock hand decrements counts and evicts the first unpinned unit at 0,
- evicted **edge** units are discarded (raw chunk persists on disk),
- evicted **vertex** units flush their decoded arrays to the disk tier so a
  later re-admission skips re-decoding,
- disk-tier entries are deleted outright when the disk budget is exceeded
  (never written back to the data lake — §5.2).

**Concurrency (DESIGN.md §5).**  The manager is the shared hot path of the
pipelined read pipeline and of concurrent serving queries, so its internals
are built for parallel callers:

- the hit path is O(1) under one short critical section (dict probe + clock
  count reset);
- chunk loading is **single-flight**: a miss registers a per-key loading
  event and performs the lake fetch *outside* the global lock, concurrent
  requests for the same chunk wait on the event instead of fetching again —
  the structural "never fetch the same chunk twice" guarantee the per-gather
  dedup in ``core/read_pipeline.py`` builds on;
- byte accounting is **incremental**: admission charges ``unit.nbytes()``
  once, decoded growth is reported as deltas through :meth:`note_growth`
  (units track their ``accounted_nbytes`` watermark), and the eviction sweep
  consults the O(1) ``_mem_bytes`` counter instead of re-summing every unit
  per iteration (the old sweep was O(n²));
- the clock ring and the disk-tier order are ordered dicts (rotate =
  ``popitem(last=False)`` + reinsert; arbitrary removal = ``del``) — no
  ``list.remove`` O(n) scans;
- decode happens under **per-unit locks**, never under the global lock.
  Deadlock-freedom argument: a unit-lock holder *may* block on the global
  lock (``on_growth`` fires mid-decode and ``note_growth`` takes it), but a
  global-lock holder never blocks on a unit lock — the eviction sweep's
  unit-lock probe is strictly non-blocking (``acquire(blocking=False)``,
  skipping units mid-decode).  Blocking edges therefore only ever point
  unit-lock → global-lock; a one-directional blocking order cannot cycle.
  Never add a blocking ``unit.lock.acquire()`` anywhere the global lock is
  held — that creates the cycle this design rules out.
"""

from __future__ import annotations

import dataclasses
import os
import pickle
import threading
from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

from repro.core.cache.units import ChunkRef, EdgeCacheUnit, NaiveChunkReader, VertexCacheUnit
from repro.lakehouse.columnfile import ColumnFileMeta
from repro.lakehouse.objectstore import ObjectStore
from repro.lakehouse.retry import lake_get
from repro.tracing import span


@dataclasses.dataclass
class CacheConfig:
    memory_budget_bytes: int = 256 * 1024 * 1024
    disk_budget_bytes: int = 2 * 1024 * 1024 * 1024
    disk_dir: Optional[str] = None          # None -> memory-backed "disk" dict
    edge_window: int = 4096
    naive_mode: bool = False                # Fig. 16 baseline: no decoded caching


class CacheManager:
    def __init__(self, store: ObjectStore, config: Optional[CacheConfig] = None):
        self.store = store
        self.config = config or CacheConfig()
        self._units: dict[str, object] = {}       # cache key -> unit (memory tier)
        # clock ring: key -> usage count, rotated FIFO (second-chance clock)
        self._clock: OrderedDict[str, int] = OrderedDict()
        self._mem_bytes = 0
        self._lock = threading.RLock()
        self._loading: dict[str, threading.Event] = {}  # single-flight admissions
        # disk tier: raw chunks and spilled decoded arrays
        self._disk_raw: dict[str, bytes] = {}
        self._disk_decoded: dict[str, tuple[np.ndarray, int, int]] = {}
        self._disk_bytes = 0
        self._disk_order: OrderedDict[str, None] = OrderedDict()
        if self.config.disk_dir:
            os.makedirs(self.config.disk_dir, exist_ok=True)
        self.stats = {
            "hits": 0, "misses": 0, "evictions": 0,
            "vertex_flushes": 0, "disk_hits": 0, "lake_fetches": 0,
            "load_waits": 0, "sweep_steps": 0, "invalidated_units": 0,
        }

    # ------------------------------------------------------------------ fetch

    def get_unit(
        self,
        ref: ChunkRef,
        meta: ColumnFileMeta,
        kind: str,
        pin: bool = False,
    ):
        """Return the cache unit for a chunk, admitting it if necessary.

        Hits resolve in one O(1) critical section.  Misses are single-flight:
        the winning thread fetches and decodes-restores *outside* the global
        lock while racing threads wait on the per-key loading event — the
        modeled ~30 ms lake latency is never paid under the lock and never
        paid twice for one chunk.
        """
        key = ref.cache_key()
        while True:
            with self._lock:
                unit = self._units.get(key)
                if unit is not None:
                    self.stats["hits"] += 1
                    self._clock[key] = unit.priority
                    if pin:
                        unit.pinned += 1
                    return unit
                event = self._loading.get(key)
                if event is None:
                    event = threading.Event()
                    self._loading[key] = event
                    self.stats["misses"] += 1
                    break
                self.stats["load_waits"] += 1
            event.wait()  # another thread is admitting this chunk

        try:
            raw = self._load_raw(ref, meta)
            chunk_meta = meta.chunk(ref.column, ref.row_group)
            if self.config.naive_mode:
                unit = NaiveChunkReader(ref, raw, chunk_meta.n_rows)
            elif kind == "vertex":
                unit = VertexCacheUnit(ref, raw, chunk_meta.n_rows)
                with self._lock:
                    spilled = self._disk_decoded.pop(key, None)
                    if spilled is not None:
                        values, upto, nbytes = spilled
                        # reclaim the disk-tier budget the spilled entry held;
                        # leaving the bytes/order entry behind makes
                        # _disk_bytes drift upward across evict/re-admit
                        # cycles and triggers premature trims
                        self._disk_bytes -= nbytes
                        self._disk_order.pop("D:" + key, None)
                        self.stats["disk_hits"] += 1
                if spilled is not None:
                    unit.import_decoded(values, upto)
            else:
                unit = EdgeCacheUnit(ref, raw, chunk_meta.n_rows,
                                     window=self.config.edge_window)
            with self._lock:
                self._admit(key, unit)
                if pin:
                    unit.pinned += 1
            return unit
        finally:
            with self._lock:
                self._loading.pop(key, None)
            event.set()

    def get_units_batch(
        self,
        requests: Sequence[tuple[ChunkRef, ColumnFileMeta, str]],
        pool=None,
    ) -> dict[str, object]:
        """Admit a batch of chunks, in parallel when a pool is given.

        Returns ``cache key -> unit`` with duplicate refs deduplicated —
        the synchronous bulk-admission entry (poolless prefetching, warm-up
        loads, tests).  The read pipeline's executor streams per-chunk jobs
        instead, to overlap each chunk's decode with later fetches; both
        paths meet in single-flight ``get_unit`` admission, so batches
        racing the pipeline (or each other) still fetch each chunk once.
        Call it from a caller thread, not from a pool worker — with
        ``pool`` given it blocks on futures of that same bounded pool.
        """
        dedup: dict[str, tuple[ChunkRef, ColumnFileMeta, str]] = {}
        for ref, meta, kind in requests:
            dedup.setdefault(ref.cache_key(), (ref, meta, kind))
        if pool is None:
            return {k: self.get_unit(*req) for k, req in dedup.items()}
        futures = {k: pool.submit(self.get_unit, *req) for k, req in dedup.items()}
        return {k: f.result() for k, f in futures.items()}

    def read_unit(self, unit, rows: np.ndarray) -> tuple[np.ndarray, int]:
        """Decode-safe read: per-unit lock around ``read``.  Growth is
        accounted by the unit's ``on_growth`` callback the moment the decode
        happens.  Returns ``(values, decode_ops delta)``."""
        with unit.lock:
            before = unit.decode_ops
            vals = unit.read(rows)
            delta = unit.decode_ops - before
        return vals, delta

    def unpin(self, unit) -> None:
        with self._lock:
            unit.pinned = max(0, unit.pinned - 1)

    def _load_raw(self, ref: ChunkRef, meta: ColumnFileMeta) -> bytes:
        key = ref.cache_key()
        with self._lock:
            raw = self._disk_raw.get(key)
            if raw is not None:
                self.stats["disk_hits"] += 1
                return raw
        chunk = meta.chunk(ref.column, ref.row_group)
        # lake_get retries transient faults and rejects short (torn) reads
        # against the chunk length, so truncated bytes never enter the cache
        with span("lake.fetch", bytes=chunk.length):
            raw = lake_get(self.store, meta.key,
                           offset=chunk.offset, length=chunk.length)
        with self._lock:
            self.stats["lake_fetches"] += 1
            self._disk_put_raw(key, raw)
        return raw

    # ----------------------------------------------------------------- memory tier

    def _admit(self, key: str, unit) -> None:
        # caller holds self._lock
        unit.accounted_nbytes = unit.nbytes()
        unit.on_growth = self.note_growth
        self._units[key] = unit
        # new admissions enter at the ring's front — the next sweep position —
        # so a fresh low-priority unit is inspected before long-resident ones
        # whose counts earlier sweeps already ground down (hand continuation,
        # same placement the list-based clock converged to)
        self._clock[key] = unit.priority
        self._clock.move_to_end(key, last=False)
        self._mem_bytes += unit.accounted_nbytes
        self._maybe_evict()

    def note_growth(self, unit) -> None:
        """Charge a unit's decoded-state growth against the memory budget.

        Units report growth as deltas against their ``accounted_nbytes``
        watermark — the sweep never re-sums live units.  Growth on a unit
        that was already evicted (its holder keeps reading the object) is
        not charged: it left the tier with its watermark's worth of bytes.
        """
        with self._lock:
            nbytes = unit.nbytes()
            delta = nbytes - unit.accounted_nbytes
            if delta == 0:
                return
            unit.accounted_nbytes = nbytes
            if self._units.get(unit.ref.cache_key()) is unit:
                self._mem_bytes += delta
                self._maybe_evict()

    def _maybe_evict(self) -> None:
        # caller holds self._lock; _mem_bytes is maintained incrementally so
        # each sweep step is O(1) — no per-iteration re-sum of unit sizes
        budget = self.config.memory_budget_bytes
        if self._mem_bytes <= budget:
            return
        sweeps = 0
        max_sweeps = 8 * max(1, len(self._clock))
        while self._mem_bytes > budget and self._clock and sweeps < max_sweeps:
            sweeps += 1
            self.stats["sweep_steps"] += 1
            key, count = self._clock.popitem(last=False)
            unit = self._units[key]
            if unit.pinned > 0:
                self._clock[key] = count        # second chance, hand advances
                continue
            if count > 0:
                self._clock[key] = count - 1
                continue
            if not unit.lock.acquire(blocking=False):
                self._clock[key] = count        # mid-decode: skip this round
                continue
            try:
                self._evict(key, unit)
            finally:
                unit.lock.release()

    def _evict(self, key: str, unit) -> None:
        # caller holds self._lock and unit.lock (clock entry already popped)
        self._units.pop(key)
        self._mem_bytes -= unit.accounted_nbytes
        self.stats["evictions"] += 1
        if unit.kind == "vertex":
            values, upto = unit.export_decoded()
            if values is not None and upto > 0:
                self._disk_put_decoded(key, values, upto)
                self.stats["vertex_flushes"] += 1
        # edge units: discard (raw chunk already lives on the disk tier)

    def mem_bytes(self) -> int:
        """Accounted memory-tier bytes — O(1), maintained incrementally."""
        return self._mem_bytes

    def mem_bytes_recomputed(self) -> int:
        """Ground truth: re-sum every live unit (tests assert it matches the
        incremental counter after concurrent storms)."""
        with self._lock:
            return sum(u.nbytes() for u in self._units.values())

    # ----------------------------------------------------------------- disk tier

    def _disk_put_raw(self, key: str, raw: bytes) -> None:
        if key in self._disk_raw:
            return
        self._disk_raw[key] = raw
        self._disk_bytes += len(raw)
        self._disk_order[key] = None
        self._disk_trim()

    def _disk_put_decoded(self, key: str, values: np.ndarray, upto: int) -> None:
        old = self._disk_decoded.pop(key, None)
        if old is not None:
            # duplicate admission (evict raced with a stale entry): replace
            # the entry instead of double counting its bytes
            self._disk_bytes -= old[2]
            self._disk_order.pop("D:" + key, None)
        nbytes = values.nbytes if values.dtype != object else len(pickle.dumps(values[:upto]))
        self._disk_decoded[key] = (values, upto, nbytes)
        self._disk_bytes += nbytes
        self._disk_order["D:" + key] = None
        self._disk_trim()

    def _disk_trim(self) -> None:
        while self._disk_bytes > self.config.disk_budget_bytes and self._disk_order:
            victim, _ = self._disk_order.popitem(last=False)
            if victim.startswith("D:"):
                entry = self._disk_decoded.pop(victim[2:], None)
                if entry is not None:
                    self._disk_bytes -= entry[2]
            else:
                raw = self._disk_raw.pop(victim, b"")
                self._disk_bytes -= len(raw)

    # ------------------------------------------------------- file invalidation

    def invalidate_file(self, file_key: str) -> int:
        """Evict exactly the ``(file, row-group)`` units of one data file —
        every tier: memory units, disk raw chunks, disk decoded spills.

        The epoch manager calls this when a lake commit removes or replaces
        a data file (DESIGN.md §7): nothing else is touched, so the rest of
        the working set stays warm.  Cache keys are
        ``"{file_key}::{column}::{row_group}"``, so prefix matching is
        exact per file.  Readers still holding an affected unit object keep
        a valid self-contained handle (units own their raw bytes), and old
        epochs re-reading a logically deleted file fall through to the lake,
        where the immutable physical object still exists.  Returns the
        number of memory-tier units evicted.
        """
        prefix = file_key + "::"
        n = 0
        with self._lock:
            for key in [k for k in self._units if k.startswith(prefix)]:
                unit = self._units.pop(key)
                self._clock.pop(key, None)
                self._mem_bytes -= unit.accounted_nbytes
                n += 1
            for key in [k for k in self._disk_raw if k.startswith(prefix)]:
                raw = self._disk_raw.pop(key)
                self._disk_bytes -= len(raw)
                self._disk_order.pop(key, None)
            for key in [k for k in self._disk_decoded if k.startswith(prefix)]:
                entry = self._disk_decoded.pop(key)
                self._disk_bytes -= entry[2]
                self._disk_order.pop("D:" + key, None)
            self.stats["invalidated_units"] += n
        return n

    # ----------------------------------------------------------------- misc

    def drop_memory(self) -> None:
        """Simulate a cold restart: clear the memory tier, keep disk tier."""
        with self._lock:
            self._units.clear()
            self._clock.clear()
            self._mem_bytes = 0

    def drop_all(self) -> None:
        with self._lock:
            self.drop_memory()
            self._disk_raw.clear()
            self._disk_decoded.clear()
            self._disk_bytes = 0
            self._disk_order.clear()

    def resident_keys(self) -> list[str]:
        with self._lock:
            return list(self._units.keys())
