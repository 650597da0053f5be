"""Lakehouse-optimized parallel primitives: VertexMap and EdgeScan (paper §6.1).

Both primitives materialize rows through graph-aware cache units and run
vectorized UDFs.  The paper's per-thread loops become block-vectorized numpy
over (file x row-group) tasks — the TPU-idiomatic masking formulation of the
same computation (see DESIGN.md §2).

``EdgeScan`` consumes the topology through the **topology plane**
(DESIGN.md §3): per scan it resolves a physical representation — the
edge-centric per-file edge lists (sequential scan, Min-Max portion pruning)
or the vertex-centric CSR index (adjacency-range gather) — via an adaptive
selectivity dispatch.  Either way the gather returns (u, v, global-edge-id)
in canonical order and row-level alignment with edge-attribute chunks is
kept through the global edge ids.

Two materialization paths exist past the gather (DESIGN.md §4):

- the **legacy full-materialization path** (``edge_filter`` callable): every
  requested column is materialized for every gathered row, then the filter
  runs once over the complete frame — the only path that supports opaque
  cross-entity UDF filters;
- the **staged pushdown path** (``plan``: a :class:`~repro.core.plan.ScanPlan`
  from the query planner): per-prefix conjuncts evaluate stage by stage on a
  shrinking row set (edge columns -> frontier-side vertex columns -> far-side
  vertex columns), each stage's reads consult per-chunk Min/Max statistics to
  skip chunks that cannot satisfy the conjunct (zone-map pruning), and
  ACCUM-only columns materialize last, for final survivors only.  Both paths
  produce bit-identical ``EdgeFrame``s.

Every reader accepts an optional ``pool`` (the engine's shared ``IOPool``):
surviving chunks then fetch and decode through the **parallel chunk
pipeline** (``core/read_pipeline.py``, DESIGN.md §5) instead of one at a
time on the caller thread.  The staged path threads one
:class:`~repro.core.read_pipeline.ReadContext` through all of its stages so
E/U/V/ACCUM never fetch the same chunk twice.  ``pool=None`` (or the
``pipe`` flag off) is the sequential parity path — bit-identical output.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import numpy as np

from repro.core.cache.manager import CacheManager
from repro.core.plan import check_deadline, union_bounds_maps
from repro.core.read_pipeline import (
    ReadContext,
    execute_plan,
    plan_edge_read,
    plan_edge_read_multi,
    plan_vertex_read,
    plan_vertex_read_multi,
)
from repro.core.types import VSet
from repro.tracing import span


def _finalize(out: dict, n: int) -> dict[str, np.ndarray]:
    for c, arr in out.items():
        if arr is None:
            out[c] = np.zeros(n, dtype=np.float64)
    return out


def read_vertex_columns_pruned(
    topology, cache: CacheManager, vertex_type: str, dense_ids: np.ndarray,
    columns: Sequence[str], bounds: Optional[dict] = None, counters: Optional[dict] = None,
    pool=None, ctx: Optional[ReadContext] = None,
) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """Materialize vertex columns for arbitrary dense IDs (point lookups).

    Groups the request by (file, row group) into a
    :class:`~repro.core.read_pipeline.ChunkFetchPlan` and reads each
    surviving chunk through its VertexCacheUnit — batched through ``pool``
    when given — scattering results back into request order.  When
    ``bounds`` (column -> ``ColumnBounds``) is given, row groups whose chunk
    Min/Max statistics cannot satisfy a bound are skipped outright — no
    column of the group is fetched/decoded — and their rows are flagged in
    the returned reject mask (they definitively fail the conjunct; their
    output values are filler and must not be consulted).
    """
    plan = plan_vertex_read(topology, vertex_type, dense_ids, columns,
                            bounds=bounds, counters=counters)
    out = execute_plan(plan, cache, counters=counters, pool=pool, ctx=ctx)
    return _finalize(out, plan.n), plan.reject


def read_vertex_values(
    topology, cache: CacheManager, vertex_type: str, dense_ids: np.ndarray, column: str
) -> np.ndarray:
    """Single-column, no-pruning convenience over
    :func:`read_vertex_columns_pruned` (the pre-pushdown API)."""
    cols, _ = read_vertex_columns_pruned(topology, cache, vertex_type, dense_ids, [column])
    return cols[column]


def read_edge_columns_pruned(
    topology, cache: CacheManager, edge_type: str, eids: np.ndarray,
    columns: Sequence[str], bounds: Optional[dict] = None, counters: Optional[dict] = None,
    pool=None, ctx: Optional[ReadContext] = None,
) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """Materialize edge columns for *global* edge ids of an edge type.

    Global edge ids address rows across the edge type's files (lists in
    registration order, rows in file order) — the addressing every
    ``TopologyView.gather`` returns.  The per-list/per-row-group grouping
    depends only on the eids, so it is computed once and shared by all
    requested columns.  ``bounds``/``counters``/``pool`` behave exactly as
    in :func:`read_vertex_columns_pruned`: zone-map-rejected row groups are
    never fetched or decoded and their rows come back reject-flagged.
    """
    plan = plan_edge_read(topology, edge_type, eids, columns,
                          bounds=bounds, counters=counters)
    out = execute_plan(plan, cache, counters=counters, pool=pool, ctx=ctx)
    return _finalize(out, plan.n), plan.reject


def read_vertex_columns_multi(
    topology, cache: CacheManager, vertex_type: str, dense_ids: np.ndarray,
    columns: Sequence[str], bounds_list: Sequence[Optional[dict]],
    counters: Optional[dict] = None, pool=None,
    ctx: Optional[ReadContext] = None,
) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """Shared-scan vertex read: one fetch pass, R riders (DESIGN.md §9).

    Identical to :func:`read_vertex_columns_pruned` except pruning takes one
    bounds map *per rider*: a chunk is skipped only when every rider rejects
    it, and the returned ``(R, n)`` reject matrix carries each rider's own
    definitive verdicts (rider *r* must not consult values its row flags)."""
    plan, rejects = plan_vertex_read_multi(
        topology, vertex_type, dense_ids, columns, bounds_list,
        counters=counters)
    out = execute_plan(plan, cache, counters=counters, pool=pool, ctx=ctx)
    return _finalize(out, plan.n), rejects


def read_edge_columns_multi(
    topology, cache: CacheManager, edge_type: str, eids: np.ndarray,
    columns: Sequence[str], bounds_list: Sequence[Optional[dict]],
    counters: Optional[dict] = None, pool=None,
    ctx: Optional[ReadContext] = None,
) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """Shared-scan edge read — :func:`read_vertex_columns_multi` for global
    edge ids."""
    plan, rejects = plan_edge_read_multi(
        topology, edge_type, eids, columns, bounds_list, counters=counters)
    out = execute_plan(plan, cache, counters=counters, pool=pool, ctx=ctx)
    return _finalize(out, plan.n), rejects


def read_edge_columns_by_eid(
    topology, cache: CacheManager, edge_type: str, eids: np.ndarray,
    columns: Sequence[str], pool=None,
) -> dict[str, np.ndarray]:
    """No-pruning convenience over :func:`read_edge_columns_pruned`."""
    return read_edge_columns_pruned(topology, cache, edge_type, eids, columns,
                                    pool=pool)[0]


def read_edge_values_by_eid(
    topology, cache: CacheManager, edge_type: str, eids: np.ndarray, column: str
) -> np.ndarray:
    """Single-column convenience over :func:`read_edge_columns_by_eid`."""
    return read_edge_columns_by_eid(topology, cache, edge_type, eids, [column])[column]


# ---------------------------------------------------------------------------
# VertexMap
# ---------------------------------------------------------------------------

def vertex_map(
    topology,
    cache: CacheManager,
    vset: VSet,
    columns: Sequence[str] = (),
    filter_fn: Optional[Callable[[dict], np.ndarray]] = None,
    map_fn: Optional[Callable[[dict], np.ndarray]] = None,
    prefetcher=None,
    bounds: Optional[dict] = None,
    counters: Optional[dict] = None,
    pool=None,
    deadline: Optional[float] = None,
):
    """Apply a UDF over an active vertex set (paper §6.1).

    Returns ``(VSet, values)``: the filtered subset (if ``filter_fn``) and the
    per-active-vertex ``map_fn`` output (if given).  The UDF receives a dict
    ``{"id": dense ids, <col>: values...}`` — fully materialized vertex rows.

    ``bounds`` (column -> ``ColumnBounds``, only sensible with ``filter_fn``)
    enables zone-map chunk pruning on the column reads: definitively rejected
    rows are dropped from the output without the UDF seeing real values.
    ``deadline`` (monotonic seconds) enforces ``ExecOptions.timeout_s`` at
    the read boundary.
    """
    check_deadline(deadline)
    if prefetcher is not None:
        prefetcher.prefetch_vertices(vset, columns, bounds=bounds, topo=topology)
    ids = vset.ids()
    frame = {"id": ids}
    with span("read.seed", rows=len(ids), columns=";".join(columns)):
        cols, reject = read_vertex_columns_pruned(
            topology, cache, vset.vertex_type, ids, list(columns),
            bounds=bounds, counters=counters, pool=pool,
        )
    frame.update(cols)
    out_vals = map_fn(frame) if map_fn is not None else None
    if filter_fn is not None:
        keep = np.asarray(filter_fn(frame), dtype=bool) & ~reject
        new = VSet.from_dense_ids(vset.vertex_type, len(vset.mask), ids[keep])
        if out_vals is not None:
            out_vals = out_vals[keep]
        return new, out_vals
    return vset, out_vals


# ---------------------------------------------------------------------------
# EdgeScan
# ---------------------------------------------------------------------------

def _gather(topology, edge_type, strategy, frontier, direction):
    """(u, v, global eid) of the edges incident to ``frontier``, through
    the representation the topology plane picks for this scan."""
    with span("scan.gather") as s:
        view = topology.plane.view(
            edge_type, strategy, frontier=frontier, direction=direction
        )
        u, v, eid = view.gather(frontier, direction=direction)
        s.set_metadata(rows=len(u))
    return u, v, eid


@dataclasses.dataclass
class EdgeFrame:
    """Materialized, filtered edge rows from one EdgeScan."""

    u: np.ndarray                 # frontier-side dense endpoint IDs
    v: np.ndarray                 # far-side dense endpoint IDs
    u_type: str
    v_type: str
    columns: dict[str, np.ndarray]  # "e.X" / "u.X" / "v.X"
    eid: Optional[np.ndarray] = None  # global edge ids, aligned with u/v

    def __len__(self) -> int:
        return len(self.u)

    def v_set(self, n: int) -> VSet:
        return VSet.from_dense_ids(self.v_type, n, np.unique(self.v))

    def u_set(self, n: int) -> VSet:
        return VSet.from_dense_ids(self.u_type, n, np.unique(self.u))


def edge_scan(
    topology,
    cache: CacheManager,
    frontier: VSet,
    edge_type: str,
    direction: str = "out",
    edge_columns: Sequence[str] = (),
    u_columns: Sequence[str] = (),
    v_columns: Sequence[str] = (),
    edge_filter: Optional[Callable[[dict], np.ndarray]] = None,
    prefetcher=None,
    read_v_values: Optional[Callable[[str, np.ndarray, str], np.ndarray]] = None,
    strategy: str = "auto",
    plan=None,
    counters: Optional[dict] = None,
    pool=None,
    deadline: Optional[float] = None,
) -> EdgeFrame:
    """Scan the edges incident to ``frontier`` (paper §6.1).

    The physical plan is chosen per scan by the topology plane
    (DESIGN.md §3): ``strategy="edgelist"`` forces the edge-centric
    sequential scan with Min-Max portion pruning, ``strategy="csr"`` forces
    the vertex-centric adjacency-range gather, and ``strategy="auto"``
    (default) picks by frontier selectivity — CSR below the calibrated
    crossover threshold, edge lists above it.  Both produce bit-identical
    output (global edge-id order).

    ``direction="out"`` treats stored (first, second) IDs as (u=src, v=dst);
    ``direction="in"`` swaps roles — bidirectional traversal without storing
    reverse edges (edge lists swap endpoint roles; CSR uses its reverse
    index).  ``edge_filter`` sees the full materialized frame and returns a
    keep-mask (cross-entity predicates welcome).

    ``plan`` (a :class:`~repro.core.plan.ScanPlan`, mutually exclusive with
    ``edge_filter``/column args) switches to the staged pushdown path
    (DESIGN.md §4): per-prefix conjuncts evaluate on a shrinking row set with
    zone-map chunk pruning, and far-side/ACCUM columns materialize late.

    ``read_v_values`` overrides far-side attribute reads — the distributed
    engine injects the two-pass remote fetch here (paper §6.2).  ``pool``
    selects the parallel chunk pipeline for every attribute read.
    ``deadline`` (monotonic seconds) enforces ``ExecOptions.timeout_s`` at
    every stage boundary — a timed-out scan stops before its next batch of
    lake reads.
    """
    check_deadline(deadline)
    et = topology.schema.edge_types[edge_type]
    if direction == "out":
        u_type, v_type = et.src_type, et.dst_type
    else:
        u_type, v_type = et.dst_type, et.src_type

    if plan is not None:
        return _edge_scan_staged(
            topology, cache, frontier, edge_type, direction, plan,
            prefetcher, read_v_values, strategy, counters, u_type, v_type, pool,
            deadline=deadline,
        )

    if prefetcher is not None:
        prefetcher.prefetch_edges(frontier, edge_type, edge_columns,
                                  direction=direction, topo=topology)
        prefetcher.prefetch_vertices(frontier, u_columns, topo=topology)

    u, v, eid = _gather(topology, edge_type, strategy, frontier, direction)
    ctx = ReadContext()
    by_col, _ = read_edge_columns_pruned(
        topology, cache, edge_type, eid, edge_columns, counters=counters,
        pool=pool, ctx=ctx,
    )
    columns = {f"e.{c}": by_col[c] for c in edge_columns}

    # endpoint materialization (vertex rows via graph-aware cache units)
    check_deadline(deadline)
    u_vals, _ = read_vertex_columns_pruned(
        topology, cache, u_type, u, list(u_columns), counters=counters,
        pool=pool, ctx=ctx,
    )
    for c in u_columns:
        columns[f"u.{c}"] = u_vals[c]
    if read_v_values is not None:
        for c in v_columns:
            columns[f"v.{c}"] = read_v_values(v_type, v, c)
    else:
        v_vals, _ = read_vertex_columns_pruned(
            topology, cache, v_type, v, list(v_columns), counters=counters,
            pool=pool, ctx=ctx,
        )
        for c in v_columns:
            columns[f"v.{c}"] = v_vals[c]

    frame = dict(columns)
    frame["u"] = u
    frame["v"] = v
    if edge_filter is not None and len(u):
        keep = np.asarray(edge_filter(frame), dtype=bool)
        u, v, eid = u[keep], v[keep], eid[keep]
        columns = {k: vals[keep] for k, vals in columns.items()}

    return EdgeFrame(u=u, v=v, u_type=u_type, v_type=v_type, columns=columns,
                     eid=eid)


# ---------------------------------------------------------------------------
# shared-scan batched EdgeScan (DESIGN.md §9)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchedScan:
    """One shared pass serving R rider queries.

    ``u``/``v``/``columns`` hold the *union* survivors — every row at least
    one rider kept — and ``alive`` is the (R, E) rider mask: row *j* belongs
    to rider *r*'s solo result iff ``alive[r, j]``.  Slicing the shared
    arrays by a rider's mask yields exactly that rider's solo
    :class:`EdgeFrame` (rows stay in canonical global-edge-id order, so the
    restriction preserves solo row order bit-for-bit).  The stacked
    accumulator path consumes the mask form directly — the masking
    formulation of DESIGN.md §2, lifted across queries.
    """

    u: np.ndarray
    v: np.ndarray
    u_type: str
    v_type: str
    columns: dict[str, np.ndarray]
    alive: np.ndarray               # (R, E) per-rider keep masks
    eid: Optional[np.ndarray] = None  # global edge ids, aligned with u/v

    @property
    def n_riders(self) -> int:
        return self.alive.shape[0]

    def frame(self, r: int) -> EdgeFrame:
        m = self.alive[r]
        return EdgeFrame(
            u=self.u[m], v=self.v[m], u_type=self.u_type, v_type=self.v_type,
            columns={k: vals[m] for k, vals in self.columns.items()},
            eid=self.eid[m] if self.eid is not None else None)


def _union_frontier(frontiers: Sequence[VSet]) -> VSet:
    mask = frontiers[0].mask.copy()
    for f in frontiers[1:]:
        mask |= f.mask
    return VSet(frontiers[0].vertex_type, mask)


def _union_cols(col_lists) -> tuple:
    # riders of one installed template request identical column sets; keep
    # first-seen order (the per-plan tuples are already sorted)
    return tuple(dict.fromkeys(c for cols in col_lists for c in cols))


def edge_scan_batched(
    topology,
    cache: CacheManager,
    frontiers: Sequence[VSet],
    edge_type: str,
    direction: str,
    plans: Sequence,
    prefetcher=None,
    strategy: str = "auto",
    counters: Optional[dict] = None,
    pool=None,
    deadline: Optional[float] = None,
) -> BatchedScan:
    """One EdgeScan pass shared by R rider queries (DESIGN.md §9).

    The staged pushdown scan (:func:`_edge_scan_staged`) generalized across
    queries: gather once over the *union* frontier, fetch/decode each stage's
    chunk union once (multi-rider zone maps — a chunk is skipped only when
    every rider's bounds reject it), then evaluate each rider's conjunct
    vectorized over the shared rows and AND it into that rider's ``alive``
    mask together with the rider's own definitive reject row.  Rows dead for
    *every* rider compress away between stages, so each stage's reads cover
    exactly the union of the rows the solo scans would read.

    Parity with R solo scans is structural, not numeric: gathers return rows
    in canonical global-edge-id order, predicates are row-local (the GSQL
    subset guarantees it — no cross-row UDFs reach this path), and rejects
    are per-rider conservative, so restricting the shared pass to one
    rider's mask commutes with running that rider alone.
    """
    check_deadline(deadline)
    union = _union_frontier(frontiers)
    e_cols = _union_cols([p.edge_columns for p in plans])
    u_cols = _union_cols([p.u_columns for p in plans])
    v_cols = _union_cols([p.v_columns for p in plans])
    if prefetcher is not None:
        prefetcher.prefetch_edges(
            union, edge_type,
            e_cols + _union_cols([p.accum_edge_columns for p in plans]),
            direction=direction,
            bounds=union_bounds_maps([p.edge_bounds for p in plans]),
            topo=topology,
        )
        prefetcher.prefetch_vertices(
            union, u_cols + _union_cols([p.accum_u_columns for p in plans]),
            bounds=union_bounds_maps([p.u_bounds for p in plans]),
            topo=topology,
        )

    et = topology.schema.edge_types[edge_type]
    if direction == "out":
        u_type, v_type = et.src_type, et.dst_type
    else:
        u_type, v_type = et.dst_type, et.src_type

    u, v, eid = _gather(topology, edge_type, strategy, union, direction)
    alive = np.stack([f.mask[u] for f in frontiers]) if len(u) \
        else np.zeros((len(frontiers), 0), dtype=bool)
    ctx = ReadContext()
    columns: dict[str, np.ndarray] = {}

    def _evaluate(preds, prefix, prefix_cols, rejects):
        """AND each rider's verdict into its alive row, then drop rows no
        rider keeps."""
        nonlocal u, v, eid, alive, columns
        columns.update(prefix_cols)
        with span(f"predicate.{prefix.upper()}", rows_in=len(u)) as s:
            if len(u):
                frame = dict(columns)
                frame["u"] = u
                frame["v"] = v
                for r, pred in enumerate(preds):
                    if pred is None:
                        continue
                    keep = np.asarray(pred.evaluate(frame, prefix), dtype=bool)
                    alive[r] &= keep & ~rejects[r]
            keep_any = alive.any(axis=0)
            s.set_metadata(rows_out=int(np.count_nonzero(keep_any)))
        if keep_any.all():
            return
        u, v, eid = u[keep_any], v[keep_any], eid[keep_any]
        alive = alive[:, keep_any]
        columns = {k: vals[keep_any] for k, vals in columns.items()}

    if e_cols:
        check_deadline(deadline)
        with span("read.E", rows=len(eid), columns=";".join(e_cols)):
            cols, rejects = read_edge_columns_multi(
                topology, cache, edge_type, eid, e_cols,
                [p.edge_bounds for p in plans], counters=counters, pool=pool,
                ctx=ctx,
            )
        _evaluate([p.edge_pred for p in plans], "e",
                  {f"e.{c}": a for c, a in cols.items()}, rejects)

    if u_cols:
        check_deadline(deadline)
        with span("read.U", rows=len(u), columns=";".join(u_cols)):
            cols, rejects = read_vertex_columns_multi(
                topology, cache, u_type, u, u_cols,
                [p.u_bounds for p in plans], counters=counters, pool=pool,
                ctx=ctx,
            )
        _evaluate([p.source_pred for p in plans], "u",
                  {f"u.{c}": a for c, a in cols.items()}, rejects)

    if v_cols:
        check_deadline(deadline)
        with span("read.V", rows=len(v), columns=";".join(v_cols)):
            cols, rejects = read_vertex_columns_multi(
                topology, cache, v_type, v, v_cols,
                [p.v_bounds for p in plans], counters=counters, pool=pool,
                ctx=ctx,
            )
        _evaluate([p.target_pred for p in plans], "v",
                  {f"v.{c}": a for c, a in cols.items()}, rejects)

    # ACCUM-only columns: union of final survivors (each rider's slice only
    # ever consults rows its own mask kept)
    acc_e = _union_cols([p.accum_edge_columns for p in plans])
    acc_u = _union_cols([p.accum_u_columns for p in plans])
    acc_v = _union_cols([p.accum_v_columns for p in plans])
    if acc_e or acc_u or acc_v:
        check_deadline(deadline)
    if acc_e:
        with span("read.accum", rows=len(eid), columns=";".join(acc_e)):
            cols, _ = read_edge_columns_multi(
                topology, cache, edge_type, eid, acc_e, [{}], counters=counters,
                pool=pool, ctx=ctx,
            )
        columns.update({f"e.{c}": a for c, a in cols.items()})
    if acc_u:
        with span("read.accum", rows=len(u), columns=";".join(acc_u)):
            cols, _ = read_vertex_columns_multi(
                topology, cache, u_type, u, acc_u, [{}], counters=counters,
                pool=pool, ctx=ctx,
            )
        columns.update({f"u.{c}": a for c, a in cols.items()})
    if acc_v:
        with span("read.accum", rows=len(v), columns=";".join(acc_v)):
            cols, _ = read_vertex_columns_multi(
                topology, cache, v_type, v, acc_v, [{}], counters=counters,
                pool=pool, ctx=ctx,
            )
        columns.update({f"v.{c}": a for c, a in cols.items()})

    return BatchedScan(u=u, v=v, u_type=u_type, v_type=v_type,
                       columns=columns, alive=alive, eid=eid)


def _edge_scan_staged(
    topology, cache, frontier, edge_type, direction, plan,
    prefetcher, read_v_values, strategy, counters, u_type, v_type, pool=None,
    deadline=None,
) -> EdgeFrame:
    """Staged late-materialization EdgeScan (DESIGN.md §4).

    Stage order E -> U -> V: each predicate stage materializes only its own
    prefix's columns, for only the rows still alive, with zone-map chunk
    pruning folded into the reads (a pruned chunk's rows carry a definitive
    reject, so filler values never reach a predicate's verdict).  Far-side
    (``v.``) reads — the expensive random point lookups — therefore see only
    rows that survived the cheaper stages, and ACCUM-only columns are read
    last, for final survivors.

    All stages share one :class:`ReadContext`, so a chunk materialized by an
    earlier stage (self-loop edge types, predicate columns re-used by ACCUM
    reads) is never fetched or pool-dispatched twice within the gather.
    """
    if prefetcher is not None:
        prefetcher.prefetch_edges(
            frontier, edge_type,
            tuple(plan.edge_columns) + tuple(plan.accum_edge_columns),
            direction=direction, bounds=plan.edge_bounds, topo=topology,
        )
        prefetcher.prefetch_vertices(
            frontier, tuple(plan.u_columns) + tuple(plan.accum_u_columns),
            bounds=plan.u_bounds, topo=topology,
        )

    u, v, eid = _gather(topology, edge_type, strategy, frontier, direction)
    ctx = ReadContext()
    columns: dict[str, np.ndarray] = {}

    def _evaluate(pred, prefix, prefix_cols, reject):
        """Shrink (u, v, eid, columns) to the conjunct's survivors."""
        nonlocal u, v, eid, columns
        columns.update(prefix_cols)
        if pred is None or not len(u):
            return
        frame = dict(columns)
        frame["u"] = u
        frame["v"] = v
        with span(f"predicate.{prefix.upper()}", rows_in=len(u)) as s:
            keep = np.asarray(pred.evaluate(frame, prefix), dtype=bool) & ~reject
            s.set_metadata(rows_out=int(np.count_nonzero(keep)))
        u, v, eid = u[keep], v[keep], eid[keep]
        columns = {k: vals[keep] for k, vals in columns.items()}

    if plan.edge_columns:
        check_deadline(deadline)
        with span("read.E", rows=len(eid), columns=";".join(plan.edge_columns)):
            e_cols, rej = read_edge_columns_pruned(
                topology, cache, edge_type, eid, plan.edge_columns,
                bounds=plan.edge_bounds, counters=counters, pool=pool, ctx=ctx,
            )
        _evaluate(plan.edge_pred, "e", {f"e.{c}": a for c, a in e_cols.items()}, rej)

    if plan.u_columns:
        check_deadline(deadline)
        with span("read.U", rows=len(u), columns=";".join(plan.u_columns)):
            u_cols, rej = read_vertex_columns_pruned(
                topology, cache, u_type, u, plan.u_columns,
                bounds=plan.u_bounds, counters=counters, pool=pool, ctx=ctx,
            )
        _evaluate(plan.source_pred, "u", {f"u.{c}": a for c, a in u_cols.items()}, rej)

    if plan.v_columns:
        check_deadline(deadline)
        with span("read.V", rows=len(v), columns=";".join(plan.v_columns)):
            if read_v_values is not None:
                v_cols = {c: read_v_values(v_type, v, c) for c in plan.v_columns}
                rej = np.zeros(len(v), dtype=bool)
            else:
                v_cols, rej = read_vertex_columns_pruned(
                    topology, cache, v_type, v, plan.v_columns,
                    bounds=plan.v_bounds, counters=counters, pool=pool, ctx=ctx,
                )
        _evaluate(plan.target_pred, "v", {f"v.{c}": a for c, a in v_cols.items()}, rej)

    # ACCUM-only columns: needed by no predicate -> final survivors only
    if plan.accum_edge_columns or plan.accum_u_columns or plan.accum_v_columns:
        check_deadline(deadline)
    if plan.accum_edge_columns:
        with span("read.accum", rows=len(eid),
                  columns=";".join(plan.accum_edge_columns)):
            e_cols, _ = read_edge_columns_pruned(
                topology, cache, edge_type, eid, plan.accum_edge_columns,
                counters=counters, pool=pool, ctx=ctx,
            )
        columns.update({f"e.{c}": a for c, a in e_cols.items()})
    if plan.accum_u_columns:
        with span("read.accum", rows=len(u),
                  columns=";".join(plan.accum_u_columns)):
            u_cols, _ = read_vertex_columns_pruned(
                topology, cache, u_type, u, plan.accum_u_columns,
                counters=counters, pool=pool, ctx=ctx,
            )
        columns.update({f"u.{c}": a for c, a in u_cols.items()})
    if plan.accum_v_columns:
        with span("read.accum", rows=len(v),
                  columns=";".join(plan.accum_v_columns)):
            if read_v_values is not None:
                columns.update({f"v.{c}": read_v_values(v_type, v, c)
                                for c in plan.accum_v_columns})
            else:
                v_cols, _ = read_vertex_columns_pruned(
                    topology, cache, v_type, v, plan.accum_v_columns,
                    counters=counters, pool=pool, ctx=ctx,
                )
                columns.update({f"v.{c}": a for c, a in v_cols.items()})

    return EdgeFrame(u=u, v=v, u_type=u_type, v_type=v_type, columns=columns,
                     eid=eid)
