"""Declarative multi-hop pattern queries — the execution core behind both
query front ends (paper §6).

Two front ends construct the same :class:`~repro.gsql.ir.LogicalQuery` IR
and compile to the same execution blocks (DESIGN.md §8):

- **GSQL text** (the paper's headline interface), via
  ``repro.gsql``::

      session = repro.connect(store, schema)
      session.query('''
          SELECT p FROM Tag:t -(HasTag:e1)- Comment:c -(HasCreator:e2)- Person:p
          WHERE t.name == $tag AND e2.creationDate > $date
            AND p.gender == "Female"
          ACCUM p.@cnt += 1
      ''', tag="Music", date=20100101)

- the **fluent builder** (this module), a thin constructor over the same
  blocks::

      q = (Query(engine)
           .vertices("Tag", where=eq("name", "Music"))
           .hop("HasTag", direction="in")
           .hop("HasCreator", direction="out",
                edge_where=gt("creationDate", d), target_where=eq("gender", "Female"),
                accum=accum_sum("cnt", 1.0)))
      result = q.run()

Either way execution flows through :func:`execute_compiled` over
``_SeedBlock`` / ``_HopBlock`` sequences — one execution path, two front
ends — so text queries are bit-identical to their builder equivalents.

Predicates compose with ``&`` / ``|``; they compile to vectorized masks over
materialized frames.  The standard comparison builders additionally carry a
declarative ``spec`` so builder chains can round-trip through the IR
(``Query.to_ir()`` -> ``LogicalQuery.render()`` -> ``parse()``).

**Predicate pushdown (DESIGN.md §4).**  Every hop is planned before it
executes: the WHERE conjuncts are already split by prefix (``e.`` / ``u.`` /
``v.``), so the planner's job is staging — pred columns vs ACCUM-only
columns per prefix — plus compiling each boundable conjunct to
:class:`~repro.core.plan.ColumnBounds` via ``Predicate.bounds()``.
``eq``/``gt``/``ge``/``lt``/``le``/``isin`` and their ``&``-compositions
produce usable bounds; ``|``-compositions, ``ne`` and opaque UDF predicates
degrade safely to no-prune (empty bounds).  The staged plan drives
``edge_scan``'s late materialization and the zone-map chunk skipping in the
read/prefetch path; ``ExecOptions(pushdown=False)`` forces the legacy
full-materialization path (the parity baseline).

**Execution knobs** live in :class:`ExecOptions` (per-session defaults on
:class:`~repro.gsql.session.GraphSession`, overridable per call) — the one
place they travel; ``Query.run`` takes an ``ExecOptions``, nothing else.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np

from repro.core.accumulators import AccumSpec
from repro.core.plan import (
    ColumnBounds,
    ScanPlan,
    check_deadline,
    merge_bounds,
    new_pruning_counters,
    union_bounds_maps,
)
from repro.core.types import VSet
from repro.tracing import span


# ---------------------------------------------------------------------------
# predicates
# ---------------------------------------------------------------------------

class Predicate:
    """Vectorized predicate over a named column of a materialized frame."""

    def __init__(
        self,
        fn: Callable[[dict, str], np.ndarray],
        columns: tuple[str, ...],
        bounds: Optional[dict] = None,
        spec=None,
    ):
        self._fn = fn
        self.columns = columns  # bare column names this predicate touches
        self._bounds = dict(bounds) if bounds else {}
        # declarative shape for IR round-tripping: ("cmp", col, op, value) |
        # ("in", col, values) | ("and"|"or", left, right); None for opaque
        # UDFs — those execute fine but cannot render as GSQL text
        self.spec = spec

    def bounds(self) -> dict[str, ColumnBounds]:
        """Column -> zone-map bounds implied by this predicate.

        Conservative protocol: every returned bound is a *necessary*
        condition of the whole predicate, so chunk pruning against it can
        only drop rows that would fail anyway.  Unboundable predicates
        (``|``-composition, ``ne``, raw UDFs) return ``{}`` — no pruning.
        """
        return dict(self._bounds)

    def evaluate(self, frame: dict, prefix: str) -> np.ndarray:
        return self._fn(frame, prefix)

    def _compose_spec(self, kind: str, other: "Predicate"):
        if self.spec is None or other.spec is None:
            return None
        return (kind, self.spec, other.spec)

    def __and__(self, other: "Predicate") -> "Predicate":
        # AND is at least as restrictive as each side: bounds intersect, and
        # a one-sided bound stays usable even if the other side is opaque.
        return Predicate(
            lambda f, p: self.evaluate(f, p) & other.evaluate(f, p),
            self.columns + other.columns,
            bounds=merge_bounds(self._bounds, other.bounds()),
            spec=self._compose_spec("and", other),
        )

    def __or__(self, other: "Predicate") -> "Predicate":
        # OR weakens both sides; degrade to no-prune rather than widen.
        return Predicate(
            lambda f, p: self.evaluate(f, p) | other.evaluate(f, p),
            self.columns + other.columns,
            spec=self._compose_spec("or", other),
        )


def _col(frame: dict, prefix: str, column: str) -> np.ndarray:
    key = f"{prefix}.{column}" if prefix else column
    if key in frame:
        return frame[key]
    return frame[column]


def _cmp(column: str, op: Callable, op_text: str,
         bounds_of: Optional[Callable] = None) -> Callable[..., Predicate]:
    def make(value) -> Predicate:
        def fn(frame, prefix):
            col = _col(frame, prefix, column)
            if col.dtype == object:
                col = np.asarray([str(x) for x in col])
                return op(col, str(value))
            return op(col, value)
        b = {column: bounds_of(value)} if bounds_of is not None else None
        return Predicate(fn, (column,), bounds=b,
                         spec=("cmp", column, op_text, value))
    return make


def eq(column: str, value) -> Predicate:
    return _cmp(column, np.equal, "==",
                lambda v: ColumnBounds(values=frozenset([v])))(value)


def ne(column: str, value) -> Predicate:
    return _cmp(column, np.not_equal, "!=")(value)


def gt(column: str, value) -> Predicate:
    return _cmp(column, np.greater, ">",
                lambda v: ColumnBounds(lo=v, lo_strict=True))(value)


def ge(column: str, value) -> Predicate:
    return _cmp(column, np.greater_equal, ">=", lambda v: ColumnBounds(lo=v))(value)


def lt(column: str, value) -> Predicate:
    return _cmp(column, np.less, "<",
                lambda v: ColumnBounds(hi=v, hi_strict=True))(value)


def le(column: str, value) -> Predicate:
    return _cmp(column, np.less_equal, "<=", lambda v: ColumnBounds(hi=v))(value)


def isin(column: str, values) -> Predicate:
    values = set(values)
    test = np.asarray(sorted(values, key=repr))

    def fn(frame, prefix):
        col = _col(frame, prefix, column)
        if col.dtype != object and test.dtype.kind in "biuf":
            # vectorized membership — only when the candidates are uniformly
            # numeric (a mixed list coerces to strings and would mismatch)
            return np.isin(col, test)
        return np.asarray([x in values for x in col.tolist()], dtype=bool)

    return Predicate(fn, (column,),
                     bounds={column: ColumnBounds(values=frozenset(values))},
                     spec=("in", column, tuple(sorted(values, key=repr))))


# ---------------------------------------------------------------------------
# accumulate specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AccumUpdate:
    name: str
    op: str                     # sum | max | min | or
    value: object               # constant, or "e.col"/"u.col"/"v.col" reference
    target: str = "v"           # which endpoint receives the update ("u"|"v")
    dtype: str = "float64"


def accum_sum(name: str, value=1.0, target: str = "v") -> AccumUpdate:
    return AccumUpdate(name=name, op="sum", value=value, target=target)


def accum_max(name: str, value, target: str = "v") -> AccumUpdate:
    return AccumUpdate(name=name, op="max", value=value, target=target)


def accum_min(name: str, value, target: str = "v") -> AccumUpdate:
    return AccumUpdate(name=name, op="min", value=value, target=target)


# ---------------------------------------------------------------------------
# execution blocks — what the GSQL compiler and the fluent builder both
# lower to (the IR's execution targets, DESIGN.md §8)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _SeedBlock:
    vertex_type: str
    where: Optional[Predicate]
    raw_ids: Optional[np.ndarray]
    # accumulator conjuncts (name, cmp-op text, value): filter the seed set
    # against runtime @accum state without touching the lake (BI5's
    # "high-degree persons" stage)
    accum_where: Optional[list] = None


@dataclasses.dataclass
class _HopBlock:
    edge_type: str
    direction: str
    edge_where: Optional[Predicate]
    source_where: Optional[Predicate]
    target_where: Optional[Predicate]
    accum: Optional[AccumUpdate]


@dataclasses.dataclass
class _PostAccumBlock:
    """POST-ACCUM: one aggregation hop seeded from an already-matched alias
    (vertex position ``source`` of the statement's path) — it updates
    accumulators and appends its frame, but never moves the result set."""

    source: int
    hop: _HopBlock
    target_alias: Optional[str] = None


@dataclasses.dataclass
class CompiledStatement:
    """One SELECT statement lowered to execution blocks."""

    seed: _SeedBlock
    hops: list[_HopBlock] = dataclasses.field(default_factory=list)
    # vertex position (0 = seed) whose forward-matched set becomes the
    # statement's result vset; -1 = last position (builder default)
    select: int = -1
    # alias name per vertex position (None = unnamed, builder chains)
    vertex_aliases: list = dataclasses.field(default_factory=list)
    post: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class CompiledQuery:
    """A full query: statements sharing one accumulator space."""

    statements: list
    # (vertex_type, accum name) pairs the query writes — what a session
    # resets before running so repeated queries are deterministic
    accum_targets: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ExecOptions:
    """Per-execution knobs, owned by the session (DESIGN.md §8).

    ``pushdown=False`` forces the legacy full-materialization scan path (no
    staging, no zone-map pruning) — the pushdown parity baseline.
    ``pipeline`` pins the parallel chunk-pipelined read path on/off
    (``None`` defers to the ``pipe`` perf flag; ``False`` is the pipelining
    parity baseline, DESIGN.md §5).  All paths return bit-identical
    results.  ``timeout_s`` bounds wall time: exceeded deadlines raise
    :class:`~repro.core.plan.QueryTimeoutError` at the next stage boundary
    (E/U/V/ACCUM stage reads in ``edge_scan``, hop and statement edges in
    the executor)."""

    pushdown: bool = True
    pipeline: Optional[bool] = None
    timeout_s: Optional[float] = None

    def deadline(self) -> Optional[float]:
        if self.timeout_s is None:
            return None
        return time.monotonic() + self.timeout_s


@dataclasses.dataclass
class QueryResult:
    vset: VSet
    accumulators: dict[str, np.ndarray]
    n_edges_scanned: int
    frames: list
    # zone-map pruning counters accumulated over every read the query issued
    # (seed VertexMap + all hops); see plan.new_pruning_counters for keys
    pruning: dict = dataclasses.field(default_factory=new_pruning_counters)
    # which snapshot-pinned epoch served the query and how stale its view of
    # the lake was when the query finished (core/epochs.py); -1 = no epoch
    # subsystem (query ran straight against the mutable topology)
    epoch_id: int = -1
    staleness_s: float = 0.0
    # named vertex aliases -> vertex sets (GSQL front end): the seed alias
    # maps to the filtered seed set, every other alias to the set that
    # reached it (its hop's surviving far side)
    alias_sets: dict = dataclasses.field(default_factory=dict)
    # which execution path produced this result ("full" engine vs the
    # plan-cached "lookup" fast path) and the template's traffic-light tier
    # at install time ("green"/"yellow"/"red", "" = ad-hoc). Observability
    # stamps only — result contents are bit-identical across routes.
    route: str = "full"
    tier: str = ""
    # True when the serving layer's refresh breaker was open and the result
    # was served from the last good pinned epoch (staleness_s stays honest —
    # it keeps growing while degraded); DESIGN.md §11
    degraded: bool = False


def plan_hop(hop: "_HopBlock") -> ScanPlan:
    """Compile one hop block into a staged :class:`ScanPlan`.

    The WHERE is already split per prefix at the front end; planning stages
    the columns (predicate columns materialize in their stage, ACCUM-only
    columns for final survivors) and compiles each conjunct's zone-map
    bounds.
    """
    e_cols = list(dict.fromkeys(hop.edge_where.columns)) if hop.edge_where else []
    u_cols = list(dict.fromkeys(hop.source_where.columns)) if hop.source_where else []
    v_cols = list(dict.fromkeys(hop.target_where.columns)) if hop.target_where else []
    acc: dict[str, list[str]] = {"e": [], "u": [], "v": []}
    if hop.accum is not None and isinstance(hop.accum.value, str):
        pfx, col = hop.accum.value.split(".", 1)
        if col not in {"e": e_cols, "u": u_cols, "v": v_cols}[pfx]:
            acc[pfx].append(col)
    return ScanPlan(
        edge_pred=hop.edge_where,
        source_pred=hop.source_where,
        target_pred=hop.target_where,
        edge_columns=tuple(sorted(e_cols)),
        u_columns=tuple(sorted(u_cols)),
        v_columns=tuple(sorted(v_cols)),
        accum_edge_columns=tuple(acc["e"]),
        accum_u_columns=tuple(acc["u"]),
        accum_v_columns=tuple(acc["v"]),
        edge_bounds=hop.edge_where.bounds() if hop.edge_where else {},
        u_bounds=hop.source_where.bounds() if hop.source_where else {},
        v_bounds=hop.target_where.bounds() if hop.target_where else {},
    )


# ---------------------------------------------------------------------------
# the executor — one path under both front ends
# ---------------------------------------------------------------------------

_ACC_CMP = {
    "==": np.equal, "!=": np.not_equal, ">": np.greater, ">=": np.greater_equal,
    "<": np.less, "<=": np.less_equal,
}


def execute_compiled(engine, compiled: CompiledQuery,
                     options: Optional[ExecOptions] = None,
                     epoch=None, private_accums: bool = False) -> QueryResult:
    """Run a compiled query against the engine.

    Every run executes against one snapshot-pinned epoch (DESIGN.md §7): by
    default the engine's current epoch is acquired for the whole run —
    covering *all* statements of a multi-statement query — and released
    afterwards, so commits (and ``advance()``) landing mid-query can never
    tear the result.  Pass ``epoch`` (an explicitly acquired
    :class:`~repro.core.epochs.GraphEpoch`) to time-travel onto an older
    pinned view; the caller then owns its release.

    ``private_accums=True`` (the session path) runs the query against a
    fresh accumulator store sized to the pinned epoch: results are a pure
    function of (query, params, epoch), concurrent queries can never
    observe each other's partial accumulator state, and the returned arrays
    are never mutated by later queries.  The default shares the engine's
    store — the legacy builder semantics (cumulative across runs), which
    ``engine.register_accum`` consumers rely on.  Either store is captured
    *once* here: a full-rebuild ``advance()`` swapping ``engine.accums``
    mid-query cannot hand later hops a renumbered dense space.
    """
    options = options or ExecOptions()
    deadline = options.deadline()
    counters = new_pruning_counters()
    mgr = getattr(engine, "epochs", None)
    acquired = None
    if epoch is None and mgr is not None:
        epoch = acquired = mgr.acquire()
    try:
        from repro.core.accumulators import Accumulators

        accums = Accumulators(epoch if epoch is not None else engine.topology) \
            if private_accums else engine.accums
        accum_out: dict[str, np.ndarray] = {}
        frames: list = []
        alias_sets: dict = {}
        n_scanned = 0
        vset = None
        for stmt in compiled.statements:
            check_deadline(deadline)
            vset, n = _run_statement(
                engine, stmt, accums, counters, options, epoch, deadline,
                accum_out, frames, alias_sets,
            )
            n_scanned += n
        return QueryResult(
            vset=vset, accumulators=accum_out, n_edges_scanned=n_scanned,
            frames=frames, pruning=counters,
            epoch_id=epoch.epoch_id if epoch is not None else -1,
            staleness_s=epoch.staleness_s() if epoch is not None else 0.0,
            alias_sets=alias_sets,
        )
    finally:
        if acquired is not None:
            mgr.release(acquired)


def _run_statement(eng, stmt: CompiledStatement, accums, counters, options,
                   epoch, deadline, accum_out, frames, alias_sets):
    # ``accums`` is the store execute_compiled pinned for the whole query: a
    # full-rebuild advance() swaps eng.accums (renumbered dense space), and
    # this query's dense ids only mean anything in the store that matches
    # its pinned epoch
    seed = stmt.seed
    topo = epoch if epoch is not None else eng.topology
    pushdown, pipeline = options.pushdown, options.pipeline

    with span("query.seed", vertex_type=seed.vertex_type):
        if seed.raw_ids is not None:
            vset = eng.vset_from_raw_ids(seed.vertex_type, seed.raw_ids,
                                         epoch=epoch)
        else:
            vset = eng.all_vertices(seed.vertex_type, epoch=epoch)
        if seed.where is not None:
            vset, _ = eng.vertex_map(
                vset,
                columns=list(dict.fromkeys(seed.where.columns)),
                filter_fn=lambda fr: _seed_verdict(seed.where, fr),
                bounds=seed.where.bounds() if pushdown else None,
                counters=counters, pipeline=pipeline, epoch=epoch,
                deadline=deadline,
            )
        if seed.accum_where:
            vset = VSet(seed.vertex_type,
                        vset.mask & _accum_seed_mask(accums, topo, seed))
    seed_set = vset

    aliases = stmt.vertex_aliases or [None] * (len(stmt.hops) + 1)
    if aliases[0] is not None:
        alias_sets[aliases[0]] = seed_set

    # forward-matched set per vertex position: position i>0 is the set its
    # hop reached; position 0 (computed lazily — it costs a np.unique) is
    # the seed vertices with at least one edge surviving hop 1
    matched: list = [None] * (len(stmt.hops) + 1)
    matched[0] = seed_set
    n_scanned = 0
    first_frame = None
    for hop_i, hop in enumerate(stmt.hops):
        check_deadline(deadline)
        frame, v_type = _run_hop(eng, vset, hop, accums, topo, counters,
                                 options, epoch, deadline, accum_out)
        if hop_i == 0:
            first_frame = frame
        n_scanned += len(frame)
        frames.append(frame)
        n_v = topo.n_vertices(v_type)
        vset = frame.v_set(n_v)
        matched[hop_i + 1] = vset
        if aliases[hop_i + 1] is not None:
            alias_sets[aliases[hop_i + 1]] = vset

    def matched_set(pos: int) -> VSet:
        if pos == 0 and stmt.hops:
            # lazily refine: seed vertices that kept an edge through hop 1
            return first_frame.u_set(topo.n_vertices(seed.vertex_type))
        return matched[pos]

    for pb in stmt.post:
        check_deadline(deadline)
        src = matched_set(pb.source)
        frame, v_type = _run_hop(eng, src, pb.hop, accums, topo, counters,
                                 options, epoch, deadline, accum_out)
        n_scanned += len(frame)
        frames.append(frame)
        if pb.target_alias is not None:
            alias_sets[pb.target_alias] = frame.v_set(topo.n_vertices(v_type))

    select = stmt.select if stmt.select >= 0 else len(stmt.hops)
    return matched_set(select), n_scanned


def _seed_verdict(where: Predicate, frame: dict) -> np.ndarray:
    """The seed's WHERE over its VertexMap frame."""
    with span("predicate.seed", rows_in=len(frame["id"])) as s:
        keep = np.asarray(where.evaluate(frame, ""), dtype=bool)
        s.set_metadata(rows_out=int(np.count_nonzero(keep)))
    return keep


def _accum_seed_mask(accums, topo, seed: _SeedBlock) -> np.ndarray:
    """The seed's accumulator conjuncts against runtime @accum state."""
    n = topo.n_vertices(seed.vertex_type)
    mask = np.ones(n, dtype=bool)
    for name, op, value in seed.accum_where:
        if accums.has(seed.vertex_type, name):
            arr = accums.ensure_capacity(seed.vertex_type, name, n)[:n]
        else:  # never written -> every slot sits at the sum identity
            arr = np.zeros(n)
        mask &= _ACC_CMP[op](arr, value)
    return mask


def _hop_span(hops: list, frontiers: list):
    """``query.hop`` over one hop: its edge type and direction, and the
    frontier size (summed over riders for a shared scan)."""
    return span("query.hop", edge_type=hops[0].edge_type,
                direction=hops[0].direction, riders=len(hops),
                rows_in=sum(int(np.count_nonzero(f.mask)) for f in frontiers))


def _run_hop(eng, vset, hop: _HopBlock, accums, topo, counters, options,
             epoch, deadline, accum_out):
    """One hop and its ACCUM; returns the frame and the far-side type."""
    with _hop_span([hop], [vset]) as s:
        frame, u_type, v_type = _exec_hop(
            eng, vset, hop, counters, options, epoch, deadline)
        if hop.accum is not None:
            with span("accum", rows=len(frame)):
                _apply_accum(accums, topo, hop, frame, u_type, v_type,
                             accum_out)
        s.set_metadata(rows_out=len(frame))
    return frame, v_type


def _exec_hop(eng, vset, hop: _HopBlock, counters, options, epoch, deadline):
    """One EdgeScan hop: staged pushdown plan, or the legacy
    full-materialization path when ``options.pushdown`` is off."""
    et = eng.schema.edge_types[hop.edge_type]
    u_type = et.src_type if hop.direction == "out" else et.dst_type
    v_type = et.dst_type if hop.direction == "out" else et.src_type

    if options.pushdown:
        frame = eng.edge_scan(
            vset, hop.edge_type, hop.direction,
            plan=plan_hop(hop), counters=counters, pipeline=options.pipeline,
            epoch=epoch, deadline=deadline,
        )
        return frame, u_type, v_type

    edge_cols, u_cols, v_cols = set(), set(), set()
    if hop.edge_where is not None:
        edge_cols.update(hop.edge_where.columns)
    if hop.source_where is not None:
        u_cols.update(hop.source_where.columns)
    if hop.target_where is not None:
        v_cols.update(hop.target_where.columns)
    if hop.accum is not None and isinstance(hop.accum.value, str):
        pfx, col = hop.accum.value.split(".", 1)
        {"e": edge_cols, "u": u_cols, "v": v_cols}[pfx].add(col)

    def _filter(frame, hop=hop):
        n = len(frame["u"])
        keep = np.ones(n, dtype=bool)
        if hop.edge_where is not None:
            keep &= hop.edge_where.evaluate(frame, "e")
        if hop.source_where is not None:
            keep &= hop.source_where.evaluate(frame, "u")
        if hop.target_where is not None:
            keep &= hop.target_where.evaluate(frame, "v")
        return keep

    frame = eng.edge_scan(
        vset, hop.edge_type, hop.direction,
        edge_columns=sorted(edge_cols),
        u_columns=sorted(u_cols),
        v_columns=sorted(v_cols),
        edge_filter=_filter,
        counters=counters, pipeline=options.pipeline,
        epoch=epoch, deadline=deadline,
    )
    return frame, u_type, v_type


def _apply_accum(accums, topo, hop: _HopBlock, frame, u_type, v_type, accum_out):
    a = hop.accum
    if a.target == "v":
        tgt_type, tgt_ids = v_type, frame.v
    else:
        tgt_type, tgt_ids = u_type, frame.u
    if not accums.has(tgt_type, a.name):
        accums.register(AccumSpec(tgt_type, a.name, op=a.op, dtype=a.dtype))
    if isinstance(a.value, str):
        pfx, col = a.value.split(".", 1)
        vals = frame.columns[f"{pfx}.{col}"]
    else:
        vals = a.value
    accums.update(tgt_type, a.name, tgt_ids, vals)
    # the result view is sized to *this* epoch's dense space, so it always
    # aligns with the result vset's mask even when a later epoch has
    # already grown the shared array
    n_tgt = topo.n_vertices(tgt_type)
    accums.ensure_capacity(tgt_type, a.name, n_tgt)
    accum_out[a.name] = accums.array(tgt_type, a.name)[:n_tgt]


# ---------------------------------------------------------------------------
# the shared-scan batched executor (DESIGN.md §9)
# ---------------------------------------------------------------------------

def _batch_shape(cq: CompiledQuery) -> tuple:
    """The structural skeleton riders must share to execute as one pass:
    everything about a compiled query *except* its bound parameter values."""
    def hop_shape(h: _HopBlock):
        a = h.accum
        return (h.edge_type, h.direction,
                h.edge_where is not None, h.source_where is not None,
                h.target_where is not None,
                None if a is None else
                (a.name, a.op, a.target, a.dtype,
                 a.value if isinstance(a.value, str) else "<const>"))

    def stmt_shape(s: CompiledStatement):
        return (s.seed.vertex_type, s.seed.where is not None,
                s.seed.raw_ids is not None,
                tuple((n, op) for n, op, _ in (s.seed.accum_where or ())),
                tuple(hop_shape(h) for h in s.hops), s.select,
                tuple(s.vertex_aliases),
                tuple((p.source, p.target_alias, hop_shape(p.hop))
                      for p in s.post))

    return tuple(stmt_shape(s) for s in cq.statements)


def _assert_batchable(compiled_list: list) -> None:
    ref = _batch_shape(compiled_list[0])
    for i, cq in enumerate(compiled_list[1:], start=1):
        if _batch_shape(cq) != ref:
            raise ValueError(
                "shared-scan batch requires riders compiled from one query "
                f"template (rider {i} differs structurally from rider 0); "
                "riders may only differ in bound parameter values")
    for cq in compiled_list:
        for s in cq.statements:
            if s.seed.raw_ids is not None:
                raise ValueError(
                    "raw_ids seeds cannot ride a shared-scan batch")


def execute_compiled_batch(engine, compiled_list: list,
                           options: Optional[ExecOptions] = None,
                           epoch=None) -> list[QueryResult]:
    """Run R compiled riders of one query template as a single shared pass
    (DESIGN.md §9).

    All riders pin the *same* epoch — acquired once here — and each gets a
    private accumulator store, so per-rider results match
    ``session.query()`` run solo on that epoch bit-for-bit: one gather over
    the union frontier, one chunk fetch/decode pass per stage (a chunk is
    skipped only when every rider's zone-map bounds reject it), per-rider
    masks over the shared decoded columns, and a stacked accumulator update.

    Riders must share the template's structure (:func:`_assert_batchable`);
    only bound parameter values may differ.  A single rider, or
    ``pushdown=False`` (the batched path is staged-scan-only), degenerates
    to sequential solo execution on one pinned epoch.  Pruning counters are
    the *batch's* — each rider's ``QueryResult.pruning`` is a copy of the
    shared pass's counters, which is exactly what "one pass served N
    riders" looks like (the serving benchmark asserts on it).
    """
    options = options or ExecOptions()
    if not compiled_list:
        return []
    mgr = getattr(engine, "epochs", None)
    acquired = None
    if epoch is None and mgr is not None:
        epoch = acquired = mgr.acquire()
    try:
        if len(compiled_list) == 1 or not options.pushdown:
            return [execute_compiled(engine, cq, options=options, epoch=epoch,
                                     private_accums=True)
                    for cq in compiled_list]
        _assert_batchable(compiled_list)
        from repro.core.accumulators import Accumulators

        deadline = options.deadline()
        counters = new_pruning_counters()
        n_riders = len(compiled_list)
        accums_list = [Accumulators(epoch if epoch is not None
                                    else engine.topology)
                       for _ in range(n_riders)]
        accum_outs: list[dict] = [{} for _ in range(n_riders)]
        frames_list: list[list] = [[] for _ in range(n_riders)]
        alias_sets_list: list[dict] = [{} for _ in range(n_riders)]
        n_scanned = [0] * n_riders
        vsets: list = [None] * n_riders
        for si in range(len(compiled_list[0].statements)):
            check_deadline(deadline)
            stmts = [cq.statements[si] for cq in compiled_list]
            vsets = _run_statement_batched(
                engine, stmts, accums_list, counters, options, epoch,
                deadline, accum_outs, frames_list, alias_sets_list, n_scanned,
            )
        return [
            QueryResult(
                vset=vsets[r], accumulators=accum_outs[r],
                n_edges_scanned=n_scanned[r], frames=frames_list[r],
                pruning=dict(counters),
                epoch_id=epoch.epoch_id if epoch is not None else -1,
                staleness_s=epoch.staleness_s() if epoch is not None else 0.0,
                alias_sets=alias_sets_list[r],
            )
            for r in range(n_riders)
        ]
    finally:
        if acquired is not None:
            mgr.release(acquired)


def _run_statement_batched(eng, stmts, accums_list, counters, options, epoch,
                           deadline, accum_outs, frames_list, alias_sets_list,
                           n_scanned):
    """Lockstep batched :func:`_run_statement`: riders advance hop by hop
    through one shared scan per hop, each tracking its own frontier,
    matched sets, aliases and accumulators."""
    n_riders = len(stmts)
    topo = epoch if epoch is not None else eng.topology
    pool = eng._query_pool(options.pipeline)
    seed0 = stmts[0].seed
    with span("query.seed", vertex_type=seed0.vertex_type, riders=n_riders):
        vsets = _seed_batched(eng, stmts, accums_list, topo, counters, pool,
                              epoch, deadline)
    seed_sets = list(vsets)

    n_hops = len(stmts[0].hops)
    rider_aliases = [s.vertex_aliases or [None] * (n_hops + 1) for s in stmts]
    for r in range(n_riders):
        if rider_aliases[r][0] is not None:
            alias_sets_list[r][rider_aliases[r][0]] = seed_sets[r]

    matched = [[None] * (n_hops + 1) for _ in range(n_riders)]
    first_frames: list = [None] * n_riders
    for r in range(n_riders):
        matched[r][0] = seed_sets[r]

    for hop_i in range(n_hops):
        check_deadline(deadline)
        hops = [s.hops[hop_i] for s in stmts]
        scan = _run_hop_batched(eng, vsets, hops, accums_list, topo, counters,
                                pool, epoch, deadline, accum_outs)
        rider_frames = [scan.frame(r) for r in range(n_riders)]
        n_v = topo.n_vertices(scan.v_type)
        for r in range(n_riders):
            if hop_i == 0:
                first_frames[r] = rider_frames[r]
            frames_list[r].append(rider_frames[r])
            n_scanned[r] += len(rider_frames[r])
            vsets[r] = rider_frames[r].v_set(n_v)
            matched[r][hop_i + 1] = vsets[r]
            if rider_aliases[r][hop_i + 1] is not None:
                alias_sets_list[r][rider_aliases[r][hop_i + 1]] = vsets[r]

    def matched_set(r: int, pos: int) -> VSet:
        if pos == 0 and n_hops:
            # lazily refine: seed vertices that kept an edge through hop 1
            return first_frames[r].u_set(topo.n_vertices(seed0.vertex_type))
        return matched[r][pos]

    for pb_i in range(len(stmts[0].post)):
        check_deadline(deadline)
        pbs = [s.post[pb_i] for s in stmts]
        hops = [pb.hop for pb in pbs]
        srcs = [matched_set(r, pbs[r].source) for r in range(n_riders)]
        scan = _run_hop_batched(eng, srcs, hops, accums_list, topo, counters,
                                pool, epoch, deadline, accum_outs)
        rider_frames = [scan.frame(r) for r in range(n_riders)]
        n_v = topo.n_vertices(scan.v_type)
        for r in range(n_riders):
            frames_list[r].append(rider_frames[r])
            n_scanned[r] += len(rider_frames[r])
            if pbs[r].target_alias is not None:
                alias_sets_list[r][pbs[r].target_alias] = \
                    rider_frames[r].v_set(n_v)

    sel = stmts[0].select if stmts[0].select >= 0 else n_hops
    return [matched_set(r, sel) for r in range(n_riders)]


def _seed_batched(eng, stmts, accums_list, topo, counters, pool, epoch,
                  deadline) -> list:
    """Each rider's seed set: one shared column read over the base set,
    per-rider evaluation — vertex_map's filter path lifted across riders."""
    from repro.core.primitives import read_vertex_columns_multi

    seed0 = stmts[0].seed
    base = eng.all_vertices(seed0.vertex_type, epoch=epoch)
    wheres = [s.seed.where for s in stmts]
    if any(w is not None for w in wheres):
        check_deadline(deadline)
        columns = list(dict.fromkeys(
            c for w in wheres if w is not None for c in w.columns))
        bounds_list = [w.bounds() if w is not None else {} for w in wheres]
        if eng.prefetcher is not None:
            eng.prefetcher.prefetch_vertices(
                base, columns, bounds=union_bounds_maps(bounds_list),
                topo=eng._topo(epoch))
        ids = base.ids()
        with span("read.seed", rows=len(ids), columns=";".join(columns)):
            cols, rejects = read_vertex_columns_multi(
                eng._topo(epoch), eng.cache, seed0.vertex_type, ids, columns,
                bounds_list, counters=counters, pool=pool,
            )
        frame = {"id": ids, **cols}
        vsets = []
        kept = np.zeros(len(ids), dtype=bool)    # rows some rider keeps
        with span("predicate.seed", rows_in=len(ids)) as s:
            for r, w in enumerate(wheres):
                if w is None:
                    vsets.append(base)
                    kept[:] = True
                    continue
                keep = np.asarray(w.evaluate(frame, ""), dtype=bool) & ~rejects[r]
                kept |= keep
                vsets.append(VSet.from_dense_ids(
                    seed0.vertex_type, len(base.mask), ids[keep]))
            s.set_metadata(rows_out=int(np.count_nonzero(kept)))
    else:
        vsets = [base] * len(stmts)
    for r, s in enumerate(stmts):
        if s.seed.accum_where:
            vsets[r] = VSet(s.seed.vertex_type, vsets[r].mask
                            & _accum_seed_mask(accums_list[r], topo, s.seed))
    return vsets


def _run_hop_batched(eng, frontiers, hops, accums_list, topo, counters, pool,
                     epoch, deadline, accum_outs):
    """One shared-scan hop and its stacked ACCUM."""
    from repro.core.primitives import edge_scan_batched

    with _hop_span(hops, frontiers) as s:
        scan = edge_scan_batched(
            eng._topo(epoch), eng.cache, frontiers, hops[0].edge_type,
            hops[0].direction, [plan_hop(h) for h in hops],
            prefetcher=eng.prefetcher, counters=counters, pool=pool,
            deadline=deadline,
        )
        if hops[0].accum is not None:
            with span("accum", rows=len(scan.u)):
                _apply_accum_batched(accums_list, topo, hops, scan, accum_outs)
        s.set_metadata(rows_out=len(scan.u))
    return scan


def _apply_accum_batched(accums_list, topo, hops, scan, accum_outs):
    """Stacked accumulator update over one shared scan.

    ``sum`` riders update through a single flattened bincount — the numpy
    mirror of ``kernels.ops.stacked_segment_sum`` (rider r's segments live
    at offset ``r * cap``), with dead rows contributing the identity instead
    of being sliced away (the masking formulation, DESIGN.md §2/§9).  The
    ordered-traversal ops (max/min/or) update per rider on their masked
    slice — same ``np.<op>.at`` path as solo.
    """
    a0 = hops[0].accum
    if a0.target == "v":
        tgt_type, tgt_ids = scan.v_type, scan.v
    else:
        tgt_type, tgt_ids = scan.u_type, scan.u
    n_riders, n_rows = scan.alive.shape
    for accums in accums_list:
        if not accums.has(tgt_type, a0.name):
            accums.register(AccumSpec(tgt_type, a0.name, op=a0.op,
                                      dtype=a0.dtype))

    def rider_values(r: int):
        a = hops[r].accum
        if isinstance(a.value, str):
            pfx, col = a.value.split(".", 1)
            return scan.columns[f"{pfx}.{col}"]
        return a.value

    if n_rows:
        if a0.op == "sum":
            vals = np.stack([
                np.broadcast_to(np.asarray(rider_values(r), dtype=np.float64),
                                (n_rows,))
                for r in range(n_riders)
            ])
            contrib = np.where(scan.alive, vals, 0.0)
            cap = int(tgt_ids.max()) + 1
            seg = tgt_ids[None, :] + (np.arange(n_riders) * cap)[:, None]
            stacked = np.bincount(
                seg.ravel(), weights=contrib.ravel(),
                minlength=n_riders * cap).reshape(n_riders, cap)
            for r, accums in enumerate(accums_list):
                arr = accums.ensure_capacity(tgt_type, a0.name, cap)
                arr[:cap] += stacked[r].astype(arr.dtype, copy=False)
        else:
            for r, accums in enumerate(accums_list):
                m = scan.alive[r]
                vals = rider_values(r)
                if isinstance(vals, np.ndarray):
                    vals = vals[m]
                accums.update(tgt_type, a0.name, tgt_ids[m], vals)

    # result views sized to this epoch's dense space (see _apply_accum)
    n_tgt = topo.n_vertices(tgt_type)
    for r, accums in enumerate(accums_list):
        accums.ensure_capacity(tgt_type, a0.name, n_tgt)
        accum_outs[r][a0.name] = accums.array(tgt_type, a0.name)[:n_tgt]


# ---------------------------------------------------------------------------
# the fluent builder front end
# ---------------------------------------------------------------------------

class Query:
    def __init__(self, engine):
        self.engine = engine
        self._seed: Optional[_SeedBlock] = None
        self._hops: list[_HopBlock] = []

    # -- builders ---------------------------------------------------------------

    def vertices(self, vertex_type: str, where: Optional[Predicate] = None,
                 raw_ids=None) -> "Query":
        self._seed = _SeedBlock(vertex_type, where,
                                None if raw_ids is None else np.asarray(raw_ids))
        return self

    def hop(
        self,
        edge_type: str,
        direction: str = "out",
        edge_where: Optional[Predicate] = None,
        source_where: Optional[Predicate] = None,
        target_where: Optional[Predicate] = None,
        accum: Optional[AccumUpdate] = None,
    ) -> "Query":
        self._hops.append(
            _HopBlock(edge_type, direction, edge_where, source_where, target_where, accum)
        )
        return self

    # -- lowering ---------------------------------------------------------------

    def compiled(self) -> CompiledQuery:
        """This chain as a single-statement :class:`CompiledQuery` — the
        exact blocks the GSQL compiler would emit for the equivalent text."""
        if self._seed is None:
            raise ValueError("query has no seed block")
        return CompiledQuery(
            statements=[CompiledStatement(seed=self._seed, hops=list(self._hops))],
        )

    def to_ir(self):
        """This chain as a :class:`~repro.gsql.ir.LogicalQuery`.

        Only declarative chains convert: opaque UDF predicates (no
        ``spec``) and ``raw_ids`` seeds raise ``ValueError``.  The result
        renders to GSQL text that parses back to an equal IR — the
        round-trip property the GSQL tests fuzz.
        """
        from repro.gsql import ir

        if self._seed is None:
            raise ValueError("query has no seed block")
        if self._seed.raw_ids is not None:
            raise ValueError("raw_ids seeds are not representable in GSQL text")

        schema = self.engine.schema
        v_aliases = ["s"] + [f"v{i + 1}" for i in range(len(self._hops))]
        vtypes = [self._seed.vertex_type]
        hop_pats = []
        conds: list = []
        accums: list = []

        def add_pred(pred: Optional[Predicate], alias: str):
            if pred is None:
                return
            conds.extend(_spec_to_conds(pred.spec, alias))

        add_pred(self._seed.where, "s")
        if self._seed.accum_where:
            for name, op, value in self._seed.accum_where:
                conds.append(ir.Cmp(ref=ir.ColRef("s", name, is_accum=True),
                                    op=op, value=value))

        for i, hop in enumerate(self._hops):
            et = schema.edge_types[hop.edge_type]
            if hop.direction not in ("out", "in"):
                raise ValueError(f"direction {hop.direction!r} is not renderable")
            v_type = et.dst_type if hop.direction == "out" else et.src_type
            u_type = et.src_type if hop.direction == "out" else et.dst_type
            if u_type != vtypes[-1]:
                raise ValueError(
                    f"hop {i + 1} ({hop.edge_type}, {hop.direction}) expects a "
                    f"{u_type} frontier, got {vtypes[-1]}")
            vtypes.append(v_type)
            e_alias = f"e{i + 1}"
            hop_pats.append(ir.HopPat(edge_type=hop.edge_type, alias=e_alias,
                                      direction=hop.direction))
            add_pred(hop.edge_where, e_alias)
            add_pred(hop.source_where, v_aliases[i])
            add_pred(hop.target_where, v_aliases[i + 1])
            if hop.accum is not None:
                a = hop.accum
                if a.op not in ir.ACCUM_OPS:
                    raise ValueError(f"accumulator op {a.op!r} is not renderable")
                tgt_alias = v_aliases[i + 1] if a.target == "v" else v_aliases[i]
                if isinstance(a.value, str):
                    pfx, col = a.value.split(".", 1)
                    value = ir.ColRef(
                        {"u": v_aliases[i], "v": v_aliases[i + 1], "e": e_alias}[pfx],
                        col)
                else:
                    value = a.value
                accums.append(ir.AccumStmt(
                    target=ir.ColRef(tgt_alias, a.name, is_accum=True),
                    op=a.op, value=value))

        stmt = ir.StatementIR(
            select_alias=v_aliases[-1],
            vertices=tuple(ir.VertexPat(vtype=t, alias=a)
                           for t, a in zip(vtypes, v_aliases)),
            hops=tuple(hop_pats),
            where=tuple(conds),
            accums=tuple(accums),
        )
        return ir.LogicalQuery(statements=(stmt,))

    # -- execution ----------------------------------------------------------------

    def run(self, options: Optional[ExecOptions] = None, *,
            epoch=None) -> QueryResult:
        """Execute the query via :func:`execute_compiled`.

        Execution knobs travel in :class:`ExecOptions` (or as session
        defaults via ``repro.connect()``).  ``epoch`` time-travels onto an
        explicitly acquired pinned view (the caller owns its release)."""
        return execute_compiled(self.engine, self.compiled(),
                                options=options, epoch=epoch)


def _spec_to_conds(spec, alias: str) -> list:
    """A Predicate's declarative ``spec`` -> IR conjuncts for one alias."""
    from repro.gsql import ir

    if spec is None:
        raise ValueError("opaque (UDF) predicates are not representable in GSQL")
    kind = spec[0]
    if kind == "cmp":
        _, col, op, value = spec
        return [ir.Cmp(ref=ir.ColRef(alias, col), op=op, value=value)]
    if kind == "in":
        _, col, values = spec
        return [ir.InSet(ref=ir.ColRef(alias, col), values=tuple(values))]
    if kind == "and":
        return _spec_to_conds(spec[1], alias) + _spec_to_conds(spec[2], alias)
    if kind == "or":
        items = []
        for side in (spec[1], spec[2]):
            cs = _spec_to_conds(side, alias)
            if len(cs) != 1:
                # (a & b) | c has no GSQL spelling in the subset — the
                # grammar's OR joins simple comparisons only
                raise ValueError("OR over an AND-composition is not "
                                 "representable in GSQL")
            if isinstance(cs[0], ir.OrCond):
                items.extend(cs[0].items)
            else:
                items.append(cs[0])
        return [ir.OrCond(items=tuple(items))]
    raise ValueError(f"unknown predicate spec {spec!r}")
