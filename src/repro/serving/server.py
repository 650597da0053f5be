"""Batched query serving over GraphLake (the paper's wrk2-driven evaluation,
§7.5, as an in-process server).

Clients submit named queries with parameters; a scheduler thread groups
them, worker threads execute against a shared engine (the engine's cache
manager is thread-safe, so concurrent queries share warmed cache units
exactly like the paper's multi-connection evaluation).  Latency percentiles
and throughput are recorded for the scalability benchmark.

**Shared-scan batching (DESIGN.md §9).**  Requests for the *same installed
template* that arrive within a short window coalesce into one *shared-scan
batch*: the scheduler holds a template's first request for
``batch_window_ms``, collects riders, and dispatches the group as a single
``session.query_batch()`` — one gather per hop over the union frontier, one
chunk fetch/decode pass per stage, per-rider masks, one pinned epoch for
the whole group (the (template, epoch) grouping is implicit: a batch
acquires its epoch at execution, so all riders see the same snapshot).
Each rider's result is bit-identical to a solo ``session.query()`` on that
epoch.  The window comes from ``ServerConfig.batch_window_ms`` or, when
unset, the ``batch`` perf flag (``batch=<window_ms>``, default 2 ms);
``<= 0`` or the flag off restores the per-request path.

**Point-lookup routing (DESIGN.md §10).**  Requests for installed
green/yellow templates — point lookups and single-hop reads classified at
``install()`` time — route *around* the batching scheduler: they dispatch
immediately (never waiting out ``batch_window_ms``) and execute through
``session.lookup()``'s plan-cached fast path (IDM probe + CSR slice against
the pinned epoch, no compile, no staged scan).  ``stats["lookup_requests"]``
/ ``stats["route_green"]`` / ``stats["route_yellow"]`` count them; results
are bit-identical to the full engine, stamped ``route="lookup"``.

**Priority lanes + tenant quotas.**  Requests carry a ``priority`` lane
(0 = high, larger = later; batches never mix lanes) and a ``tenant`` label:
with ``ServerConfig.tenant_quota`` set, a tenant may only hold that many
requests in flight — the excess is shed with :class:`TenantQuotaExceededError`
(a :class:`ServerOverloadedError`), so one hot tenant cannot starve the
queue for everyone else.

Concurrent queries also share the engine's query-time ``IOPool``
(DESIGN.md §5): each scan issues its chunk-fetch batches through the one
pool, so the modeled object-store parallel-stream budget is a per-engine
resource.  The cache manager's single-flight admission guarantees that two
workers racing over the same cold chunk pay its lake fetch once.

**Freshness (DESIGN.md §7).**  A background refresher thread periodically
calls the engine's ``advance()``: the epoch manager diffs the lake, applies
incremental deltas and atomically publishes a new epoch, while queries
already in flight keep draining on the epoch they pinned at start.  The
interval comes from ``ServerConfig.refresh_interval_s`` or, when unset, the
``refresh`` perf flag (``refresh=<seconds>``).

**Degrade-to-stale (DESIGN.md §11).**  The refresher carries a circuit
breaker: failed advances back off exponentially and record ``last_error``;
``breaker_threshold`` *consecutive* failures open the breaker.  Open means
the server stops paying for doomed refresh attempts and keeps serving the
last good pinned epoch — results stay bit-correct for that snapshot, with
``QueryResult.staleness_s`` honestly growing and ``degraded=True`` stamped
on both the serving envelope and the engine result.  After
``breaker_cooldown_s`` the refresher goes *half-open*: one probe advance;
success closes the breaker (degraded stamping stops), failure re-opens it.
``health()`` snapshots the whole picture: breaker state, last advance
error, refresh/retry/hedge counters, epoch freshness, queue depth.

**Installed queries (DESIGN.md §8).**  The server fronts a
:class:`~repro.gsql.session.GraphSession`: any query *installed* on the
session is servable by name with bound parameters —
``submit("bi1", tag="Music", date=20100101)``.  Plain callables
(``query_fns``) remain for result-shaping wrappers; they receive the engine
and always execute solo (opaque callables cannot ride a shared scan).

**Admission control + timeouts.**  ``submit()`` never blocks the client: a
full bounded queue raises :class:`ServerOverloadedError` (typed, so callers
can shed load / retry with backoff).  ``ServerConfig.timeout_s`` bounds
each installed query's execution; ``ServerConfig.total_timeout_s`` is the
*queue-time-aware* budget — a request whose queue wait already exhausted it
fails as a ``QueryTimeoutError`` result **without executing**, and an
admitted request runs with only its remaining budget.  A shared-scan batch
runs on the most patient rider's remaining budget (already-expired riders
were failed out before dispatch, so batching never extends anyone's wait
past what admission allowed).

**Results.**  ``result(rid)`` parks on a per-request ``threading.Event`` —
completion wakes the waiter immediately; queue-time/service-time accounting
is measured at dispatch, not collection.  Completed results a caller never
collects are evicted after ``ServerConfig.result_ttl_s`` (counted in
``server.stats["evicted_results"]``) so an abandoning client cannot leak
the results dict.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Optional

from repro import perf_flags
from repro.core.query import ExecOptions
# the server's typed errors now live in repro.errors (the consolidated
# typed-error surface, common ReproError base); re-exported here for one
# release
from repro.errors import (  # noqa: F401
    QueryTimeoutError,
    ServerOverloadedError,
    TenantQuotaExceededError,
)
from repro.gsql.session import GraphSession
from repro.tracing import name_os_thread, span

# a scheduler wake-up later than this past its timeout counts as a stall:
# something (as a rule the interpreter lock) kept the server from running
STALL_S = 0.010


@dataclasses.dataclass
class ServerConfig:
    n_workers: int = 2
    max_queue: int = 256
    # background epoch-refresh interval; None defers to the ``refresh`` perf
    # flag (its numeric value, default 30 s), <= 0 disables outright
    refresh_interval_s: Optional[float] = None
    # per-query execution timeout for installed queries (None = no bound);
    # overrides the session's ExecOptions.timeout_s while serving
    timeout_s: Optional[float] = None
    # queue-time-aware total budget per request (None = no bound): queue
    # wait counts against it, an expired request fails without executing,
    # and an admitted one runs with the remaining budget only
    total_timeout_s: Optional[float] = None
    # shared-scan batching window (DESIGN.md §9); None defers to the
    # ``batch`` perf flag (``batch=<window_ms>``, default 2 ms), <= 0 (or
    # the flag off) disables batching — the per-request parity path
    batch_window_ms: Optional[float] = None
    # riders per shared-scan batch cap (a flush happens at whichever of
    # window expiry / max_batch_riders comes first)
    max_batch_riders: int = 64
    # max in-flight requests per tenant (None = unlimited)
    tenant_quota: Optional[int] = None
    # completed-but-uncollected results are evicted after this many seconds
    result_ttl_s: float = 60.0
    # refresh circuit breaker (DESIGN.md §11): this many *consecutive*
    # failed advances open it ...
    breaker_threshold: int = 3
    # ... and after this long open, one half-open probe decides whether it
    # closes (success) or re-opens (failure)
    breaker_cooldown_s: float = 5.0


@dataclasses.dataclass
class QueryResult:
    request_id: int
    ok: bool
    value: object
    error: Optional[str]
    queued_s: float
    service_s: float
    # True when the refresh breaker was non-closed at execution: the result
    # was served from the last good pinned epoch (stale but bit-correct for
    # that snapshot); the engine-level value carries the same stamp
    degraded: bool = False


@dataclasses.dataclass
class _Request:
    rid: int
    name: str
    params: dict
    tenant: str
    priority: int
    t_submit: float             # perf_counter at submit (queue accounting)
    t_mono: float               # monotonic at submit (total-budget clock)


class QueryServer:
    """Serves a session's installed GSQL queries by name, plus optional
    result-shaping callables (``query_fns``: name -> fn(engine, **params)).
    ``backend`` is a :class:`GraphSession` or a bare engine (a cached
    session is created for it); installed names resolve through
    ``session.query()`` / ``session.query_batch()``, callables win on a
    name clash."""

    def __init__(self, backend, query_fns: Optional[dict[str, Callable]] = None,
                 config: Optional[ServerConfig] = None):
        if isinstance(backend, GraphSession):
            self.session = backend
        else:
            self.session = GraphSession.for_engine(backend)
        self.engine = self.session.engine
        self.query_fns = query_fns or {}
        self.config = config or ServerConfig()
        # serving-time execution defaults: the session's, capped by the
        # server's per-query timeout when one is configured
        self._exec_options: Optional[ExecOptions] = None
        if self.config.timeout_s is not None:
            self._exec_options = dataclasses.replace(
                self.session.options, timeout_s=self.config.timeout_s)
        window = self.config.batch_window_ms
        if window is None:
            window = (perf_flags.value("batch", 2.0)
                      if perf_flags.enabled("batch") else 0.0)
        self._window_s = max(0.0, float(window)) / 1000.0
        self._q: queue.Queue = queue.Queue(maxsize=self.config.max_queue)
        # scheduler -> workers: ((priority, seq), unit); unit is
        # (kind, payload, dispatched at) with ("lookup", req) | ("single",
        # req) | ("batch", [reqs]), or None (worker shutdown)
        self._exec_q: queue.PriorityQueue = queue.PriorityQueue()
        self._seq = 0
        self._results: dict[int, QueryResult] = {}
        self._done_at: dict[int, float] = {}
        self._waiters: dict[int, threading.Event] = {}
        self._tenant_inflight: dict[str, int] = {}
        self._lock = threading.Lock()
        self._next_id = 0
        self.stats = {
            "batches": 0,            # shared-scan groups dispatched
            "batched_requests": 0,   # requests served by a shared scan
            "solo_requests": 0,      # requests served per-request
            "max_batch_riders": 0,   # largest group so far
            "shed_queue_full": 0,    # ServerOverloadedError (queue)
            "shed_tenant_quota": 0,  # TenantQuotaExceededError
            "expired_in_queue": 0,   # total budget gone before dispatch
            "evicted_results": 0,    # TTL-evicted uncollected results
            "lookup_requests": 0,    # served by the point-lookup fast path
            "route_green": 0,        # ... of which needed no lake columns
            "route_yellow": 0,       # ... of which paid a column fetch path
            "stall_s": 0.0,          # scheduler lateness past STALL_S, summed
            "max_stall_s": 0.0,      # ... and the largest one
        }
        # wire-surface dispatch counters (handle()): per-route hits + errors,
        # surfaced by health() under "routes"
        self.route_stats = {"/vertex": 0, "/neighbors": 0, "/query": 0,
                            "/lookup": 0, "/health": 0, "errors": 0}
        self._scheduler = threading.Thread(target=self._schedule, daemon=True,
                                           name="serve-scheduler")
        self._scheduler.start()
        self._workers = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"serve-worker-{i}")
            for i in range(self.config.n_workers)
        ]
        for w in self._workers:
            w.start()
        # background epoch refresher (DESIGN.md §7) + circuit breaker (§11)
        self.refresh_stats = {"ticks": 0, "advanced": 0, "errors": 0,
                              "last_epoch": -1, "last_error": None,
                              "consecutive_failures": 0, "breaker_opens": 0,
                              "half_open_probes": 0, "breaker_closes": 0}
        self._breaker_state = "closed"   # "closed" | "open" | "half_open"
        self._refresh_stop = threading.Event()
        self._refresher: Optional[threading.Thread] = None
        interval = self.config.refresh_interval_s
        if interval is None and perf_flags.enabled("refresh"):
            interval = perf_flags.value("refresh", 30.0)
        if interval is not None and interval > 0 and hasattr(self.engine, "advance"):
            self._refresher = threading.Thread(
                target=self._refresh_loop, args=(float(interval),), daemon=True,
                name="serve-refresh",
            )
            self._refresher.start()

    # -- client API -------------------------------------------------------------

    def submit(self, query: str, *, tenant: str = "default",
               priority: int = 1, **params) -> int:
        """Enqueue one request; raises :class:`ServerOverloadedError` when
        the bounded queue is full and :class:`TenantQuotaExceededError` when
        ``tenant`` already holds its quota of in-flight requests (admission
        control — never blocks).  ``priority`` selects the dispatch lane
        (0 = high, larger = later; default 1)."""
        with self._lock:
            quota = self.config.tenant_quota
            held = self._tenant_inflight.get(tenant, 0)
            if quota is not None and held >= quota:
                self.stats["shed_tenant_quota"] += 1
                raise TenantQuotaExceededError(
                    f"tenant {tenant!r} holds {held} in-flight requests "
                    f"(quota {quota}); shed request ({query})")
            rid = self._next_id
            self._next_id += 1
            self._tenant_inflight[tenant] = held + 1
        req = _Request(rid=rid, name=query, params=params, tenant=tenant,
                       priority=priority, t_submit=time.perf_counter(),
                       t_mono=time.monotonic())
        try:
            self._q.put_nowait(req)
        except queue.Full:
            with self._lock:
                self._release_tenant(req.tenant)
                self.stats["shed_queue_full"] += 1
            raise ServerOverloadedError(
                f"request queue full ({self.config.max_queue} pending); "
                f"shed request {rid!r} ({query})") from None
        return rid

    def result(self, rid: int, timeout_s: float = 60.0) -> QueryResult:
        """Wait for one request's result (parks on the request's completion
        event — no polling; collection removes the entry)."""
        with self._lock:
            if rid in self._results:
                self._done_at.pop(rid, None)
                self._waiters.pop(rid, None)
                return self._results.pop(rid)
            ev = self._waiters.setdefault(rid, threading.Event())
        if not ev.wait(timeout_s):
            with self._lock:
                self._waiters.pop(rid, None)
            raise TimeoutError(f"request {rid}")
        with self._lock:
            self._done_at.pop(rid, None)
            self._waiters.pop(rid, None)
            res = self._results.pop(rid, None)
        if res is None:  # evicted between wake-up and collection
            raise TimeoutError(f"request {rid}")
        return res

    def run_batch(self, requests: list[tuple[str, dict]]) -> list[QueryResult]:
        """Submit a batch, wait for all, return results in order.

        A batch driver *chooses* to wait, so overload here backs off and
        retries instead of propagating :class:`ServerOverloadedError` —
        batches larger than the bounded queue drain through it; only direct
        ``submit()`` callers see admission rejections."""
        rids = []
        for q, p in requests:
            while True:
                try:
                    rids.append(self.submit(q, **p))
                    break
                except ServerOverloadedError:
                    time.sleep(0.001)
        return [self.result(r) for r in rids]

    def close(self) -> None:
        self._refresh_stop.set()
        self._q.put(None)           # scheduler: drain, flush, stop workers
        self._scheduler.join()
        for w in self._workers:
            w.join()
        if self._refresher is not None:
            self._refresher.join(timeout=10.0)

    # -- background refresher ------------------------------------------------------

    def _refresh_loop(self, interval_s: float) -> None:
        """Periodically advance the engine's epoch: in-flight queries drain
        on their pinned epoch, the next query picks up the new one.

        Failure handling (DESIGN.md §11): each failed tick records
        ``last_error`` and doubles the wait (exponential backoff, capped at
        ``breaker_cooldown_s``-or-32x) instead of hammering a broken lake at
        full cadence.  ``breaker_threshold`` consecutive failures open the
        circuit breaker: serving degrades to the last good pinned epoch
        (results stamped ``degraded``), and after ``breaker_cooldown_s``
        one half-open probe advance decides re-open vs close.
        """
        name_os_thread()
        cfg = self.config
        wait_s = interval_s
        while not self._refresh_stop.wait(wait_s):
            with self._lock:
                if self._breaker_state == "open":
                    # cooldown elapsed (wait_s was the cooldown): probe
                    self._breaker_state = "half_open"
                    self.refresh_stats["half_open_probes"] += 1
            try:
                with span("serve.refresh"):
                    report = self.engine.advance()
            except Exception as e:  # queries stay on the pinned epoch
                with self._lock:
                    self.refresh_stats["errors"] += 1
                    self.refresh_stats["last_error"] = f"{type(e).__name__}: {e}"
                    self.refresh_stats["consecutive_failures"] += 1
                    n = self.refresh_stats["consecutive_failures"]
                    if (self._breaker_state == "half_open"
                            or n >= cfg.breaker_threshold):
                        if self._breaker_state != "open":
                            if self._breaker_state == "closed":
                                self.refresh_stats["breaker_opens"] += 1
                            self._breaker_state = "open"
                        wait_s = cfg.breaker_cooldown_s
                    else:
                        wait_s = min(interval_s * (2 ** n),
                                     max(cfg.breaker_cooldown_s,
                                         interval_s * 32))
                continue
            with self._lock:
                self.refresh_stats["ticks"] += 1
                self.refresh_stats["last_epoch"] = report.to_epoch
                self.refresh_stats["consecutive_failures"] = 0
                if self._breaker_state != "closed":
                    self._breaker_state = "closed"
                    self.refresh_stats["breaker_closes"] += 1
                wait_s = interval_s
                if report.changed:   # last: pollers key off this counter
                    self.refresh_stats["advanced"] += 1

    def _stamp_degraded(self, value) -> bool:
        """True (and stamp ``value.degraded``) when the refresh breaker is
        non-closed: the result is served from the last good pinned epoch."""
        with self._lock:
            deg = self._breaker_state != "closed"
        if deg and value is not None and hasattr(value, "degraded"):
            value.degraded = True
        return deg

    def health(self) -> dict:
        """One self-describing snapshot of the server's resilience state:
        breaker + refresh history, epoch freshness, queue depth, shed/serve
        counters, and the lake-I/O retry / hedge / fault-injection counters
        (DESIGN.md §11)."""
        from repro.lakehouse.retry import retry_stats
        with self._lock:
            out = {
                "breaker": self._breaker_state,
                "refresh": dict(self.refresh_stats),
                "stats": dict(self.stats),
                "queue_depth": self._q.qsize(),
            }
        epochs = getattr(self.engine, "epochs", None)
        ep = epochs.current() if epochs is not None else None
        if ep is not None:
            out["epoch_id"] = ep.epoch_id
            out["staleness_s"] = ep.staleness_s()
        out["retry"] = retry_stats()
        pool = getattr(self.engine, "pool", None)
        if pool is not None:
            out["io_pool"] = dict(pool.stats)
        store = getattr(self.engine, "store", None)
        if store is not None and getattr(store, "faults", None) is not None:
            out["faults"] = store.faults.snapshot()
        ingest = getattr(self.engine, "ingest", None)
        if ingest is not None:
            out["ingest"] = ingest.stats()
        fabric = getattr(self.engine, "_shard_fabric", None)
        if fabric is not None:
            out["fabric"] = fabric.stats_snapshot()
        with self._lock:
            out["routes"] = dict(self.route_stats)
        return out

    # -- wire surface -------------------------------------------------------------

    def handle(self, method: str, path: str,
               params: Optional[dict] = None) -> dict:
        """HTTP-style request dispatch, mirroring the installed-query
        surface over a wire shape (the in-process stand-in for a listener):

        - ``GET /vertex/{vtype}/{pk}`` — point-read one vertex
          (``params["columns"]`` selects lake columns);
        - ``GET /neighbors/{etype}/{pk}`` — one CSR adjacency slice
          (``params``: ``direction`` =out|in, ``ids`` =raw|dense);
        - ``GET|POST /query/{name}`` — an installed query through the full
          scheduler (batching, lanes, budgets; params are the bindings);
        - ``GET /lookup/{name}`` — the point-lookup tier, synchronous;
        - ``GET /health`` — the resilience snapshot.

        Returns ``{"status": <code>, "value": ...}`` or ``{"status": ...,
        "error": "..."}`` — never raises; per-route hits and errors are
        counted in ``route_stats`` (see ``health()["routes"]``)."""
        params = dict(params or {})
        parts = [p for p in path.split("/") if p]
        route = "/" + parts[0] if parts else path
        try:
            status, value = self._route(method.upper(), route, parts, params)
        except KeyError as e:
            status, value = 404, f"{type(e).__name__}: {e}"
        except (TypeError, ValueError) as e:
            status, value = 400, f"{type(e).__name__}: {e}"
        except Exception as e:
            status, value = 500, f"{type(e).__name__}: {e}"
        with self._lock:
            if route in self.route_stats:
                self.route_stats[route] += 1
            if status >= 400:
                self.route_stats["errors"] += 1
        if status >= 400:
            return {"status": status, "error": value}
        return {"status": status, "value": value}

    def _route(self, method: str, route: str, parts: list,
               params: dict) -> tuple[int, object]:
        if route == "/health" and len(parts) == 1:
            if method != "GET":
                return 405, f"{method} not allowed on {route}"
            return 200, self.health()
        if route == "/vertex" and len(parts) == 3:
            if method != "GET":
                return 405, f"{method} not allowed on {route}"
            columns = tuple(params.pop("columns", ()))
            out = self.session.get_vertex(parts[1], _wire_id(parts[2]),
                                          columns=columns, **params)
            if out is None:
                return 404, f"no {parts[1]!r} vertex with id {parts[2]!r}"
            return 200, out
        if route == "/neighbors" and len(parts) == 3:
            if method != "GET":
                return 405, f"{method} not allowed on {route}"
            out = self.session.neighbors(parts[1], _wire_id(parts[2]),
                                         direction=params.pop("direction", "out"),
                                         ids=params.pop("ids", "raw"), **params)
            return 200, {"edge_type": parts[1], "vertex_id": _wire_id(parts[2]),
                         "n": int(len(out)), "neighbors": out}
        if route == "/query" and len(parts) == 2:
            if method not in ("GET", "POST"):
                return 405, f"{method} not allowed on {route}"
            rid = self.submit(parts[1], **params)
            res = self.result(rid)
            if not res.ok:
                return 500, res.error
            return 200, res
        if route == "/lookup" and len(parts) == 2:
            if method != "GET":
                return 405, f"{method} not allowed on {route}"
            value = self.session.lookup(
                parts[1], options=self._exec_options, **params)
            deg = self._stamp_degraded(value)
            with self._lock:
                self.stats["lookup_requests"] += 1
                if value is not None and value.tier in ("green", "yellow"):
                    self.stats[f"route_{value.tier}"] += 1
            return 200, QueryResult(request_id=-1, ok=True, value=value,
                                    error=None, queued_s=0.0, service_s=0.0,
                                    degraded=deg)
        return 404, f"no route for {method} {'/' + '/'.join(parts)}"

    # -- scheduler ----------------------------------------------------------------

    def _lookup_fast(self, req: _Request) -> bool:
        """True when the request serves through the point-lookup tier
        (DESIGN.md §10): an installed green/yellow template.  Lookups route
        *around* the batching scheduler — a sub-millisecond point read must
        never wait out ``batch_window_ms`` behind a scan it doesn't need."""
        if req.name in self.query_fns:
            return False
        iq = self.session.installed(req.name)
        return iq is not None and iq.lookup_plan is not None

    def _batchable(self, req: _Request) -> bool:
        return (self._window_s > 0
                and req.name not in self.query_fns
                and self.session.is_installed(req.name))

    def _dispatch(self, priority: int, kind: str, payload) -> None:
        with self._lock:
            seq = self._seq
            self._seq += 1
        self._exec_q.put(((priority, seq), (kind, payload, time.perf_counter())))

    def _note_stall(self, late_s: float) -> None:
        with self._lock:
            self.stats["stall_s"] += late_s
            self.stats["max_stall_s"] = max(self.stats["max_stall_s"], late_s)
        with span("serve.stall", late_s=late_s):
            pass

    def _schedule(self) -> None:
        """Drain submissions into dispatch units.

        Batchable requests (installed template, batching on) collect in a
        per-(template, lane) bucket flushed ``batch_window_ms`` after its
        first rider arrived — or immediately at ``max_batch_riders`` — so a
        burst of same-template requests becomes one shared scan while an
        isolated request pays at most one window of extra latency.
        Everything else dispatches immediately.  Buckets never cross
        priority lanes; a flushed unit keeps its lane's priority.
        """
        name_os_thread()
        buckets: dict[tuple, list[_Request]] = {}
        flush_at: dict[tuple, float] = {}
        last_sweep = time.monotonic()
        closing = False
        while True:
            now = time.monotonic()
            if buckets:
                wait = max(0.0, min(flush_at.values()) - now)
            elif closing:
                break
            else:
                wait = 0.05   # idle heartbeat: TTL sweeps keep running
            try:
                req = self._q.get(timeout=wait) if not closing else self._q.get_nowait()
            except queue.Empty:
                req = False   # timeout (None is the shutdown sentinel)
                late = time.monotonic() - (now + wait)
                if not closing and late > STALL_S:
                    self._note_stall(late)
            if req is None:
                closing = True
            elif req is not False:
                if self._lookup_fast(req):
                    self._dispatch(req.priority, "lookup", req)
                elif self._batchable(req):
                    key = (req.name, req.priority)
                    bucket = buckets.setdefault(key, [])
                    if not bucket:
                        flush_at[key] = time.monotonic() + self._window_s
                    bucket.append(req)
                    if len(bucket) >= self.config.max_batch_riders:
                        self._dispatch(req.priority, "batch", bucket)
                        del buckets[key], flush_at[key]
                else:
                    self._dispatch(req.priority, "single", req)
            now = time.monotonic()
            for key in [k for k, t in flush_at.items() if t <= now or closing]:
                self._dispatch(key[1], "batch", buckets.pop(key))
                del flush_at[key]
            if now - last_sweep >= 1.0:
                last_sweep = now
                self._evict_stale(now)
        for i in range(len(self._workers)):
            self._exec_q.put(((1 << 30, i), None))

    def _evict_stale(self, now: float) -> None:
        """Drop completed results nobody collected within ``result_ttl_s``
        (satellite of DESIGN.md §9: an abandoning client must not leak)."""
        ttl = self.config.result_ttl_s
        with self._lock:
            stale = [rid for rid, t in self._done_at.items()
                     if now - t > ttl]
            for rid in stale:
                self._done_at.pop(rid, None)
                self._results.pop(rid, None)
                self._waiters.pop(rid, None)
                self.stats["evicted_results"] += 1

    # -- worker -------------------------------------------------------------------

    def _release_tenant(self, tenant: str) -> None:
        # caller holds self._lock
        held = self._tenant_inflight.get(tenant, 0)
        if held <= 1:
            self._tenant_inflight.pop(tenant, None)
        else:
            self._tenant_inflight[tenant] = held - 1

    def _complete(self, req: _Request, ok: bool, value, err: Optional[str],
                  t_start: float, t_end: float,
                  degraded: bool = False) -> None:
        res = QueryResult(
            request_id=req.rid, ok=ok, value=value, error=err,
            queued_s=t_start - req.t_submit, service_s=t_end - t_start,
            degraded=degraded,
        )
        with self._lock:
            self._results[req.rid] = res
            self._done_at[req.rid] = time.monotonic()
            self._release_tenant(req.tenant)
            ev = self._waiters.get(req.rid)
        if ev is not None:
            ev.set()

    def _remaining_budget(self, req: _Request, now_mono: float) -> Optional[float]:
        total = self.config.total_timeout_s
        if total is None:
            return None
        return total - (now_mono - req.t_mono)

    def _split_expired(self, reqs: list[_Request], t_start: float
                       ) -> tuple[list[_Request], list[_Request]]:
        """Queue-time-aware admission at dispatch: riders whose total budget
        is already gone fail as ``QueryTimeoutError`` results *without
        executing* (their queue wait was the timeout)."""
        now = time.monotonic()
        live, expired = [], []
        for req in reqs:
            rem = self._remaining_budget(req, now)
            (expired if rem is not None and rem <= 0 else live).append(req)
        for req in expired:
            with self._lock:
                self.stats["expired_in_queue"] += 1
            self._complete(
                req, False, None,
                f"{QueryTimeoutError.__name__}: total budget "
                f"({self.config.total_timeout_s}s) exhausted in queue",
                t_start, t_start)
        return live, expired

    def _options_for(self, reqs: list[_Request]) -> Optional[ExecOptions]:
        """Execution options for one dispatch unit: the serving defaults,
        with ``timeout_s`` tightened to the remaining total budget.  A batch
        runs on its most patient rider's remaining budget — expired riders
        were already failed out, so nobody waits longer than admission
        allowed."""
        base = self._exec_options
        total = self.config.total_timeout_s
        if total is None:
            return base
        now = time.monotonic()
        remaining = max(self._remaining_budget(r, now) for r in reqs)
        current = base.timeout_s if base is not None else None
        if current is None or remaining < current:
            base = dataclasses.replace(base or self.session.options,
                                       timeout_s=remaining)
        return base

    def _run_single(self, req: _Request) -> None:
        t_start = time.perf_counter()
        live, _ = self._split_expired([req], t_start)
        if not live:
            return
        try:
            if req.name in self.query_fns:
                value = self.query_fns[req.name](self.engine, **req.params)
            elif self.session.is_installed(req.name):
                value = self.session.query(
                    req.name, options=self._options_for([req]), **req.params)
            else:
                raise KeyError(
                    f"no installed query or handler named {req.name!r}")
            ok, err = True, None
        except Exception as e:  # report (typed), don't kill the worker
            value, ok, err = None, False, f"{type(e).__name__}: {e}"
        deg = self._stamp_degraded(value if ok else None)
        with self._lock:
            self.stats["solo_requests"] += 1
        self._complete(req, ok, value, err, t_start, time.perf_counter(),
                       degraded=deg)

    def _run_lookup(self, req: _Request) -> None:
        """One point-lookup request: session fast path, no compile, no
        batch window, same completion/accounting protocol as solo."""
        t_start = time.perf_counter()
        live, _ = self._split_expired([req], t_start)
        if not live:
            return
        try:
            value = self.session.lookup(
                req.name, options=self._options_for([req]), **req.params)
            ok, err = True, None
        except Exception as e:  # report (typed), don't kill the worker
            value, ok, err = None, False, f"{type(e).__name__}: {e}"
        deg = self._stamp_degraded(value if ok else None)
        with self._lock:
            self.stats["lookup_requests"] += 1
            if ok and value is not None and value.tier in ("green", "yellow"):
                self.stats[f"route_{value.tier}"] += 1
        self._complete(req, ok, value, err, t_start, time.perf_counter(),
                       degraded=deg)

    def _run_shared(self, reqs: list[_Request]) -> None:
        """One shared-scan pass for a group of same-template riders."""
        t_start = time.perf_counter()
        live, _ = self._split_expired(reqs, t_start)
        if not live:
            return
        try:
            values = self.session.query_batch(
                live[0].name, [r.params for r in live],
                options=self._options_for(live))
            errs = [None] * len(live)
        except Exception as e:  # one failure fails the group, typed
            values = [None] * len(live)
            errs = [f"{type(e).__name__}: {e}"] * len(live)
        with self._lock:
            self.stats["batches"] += 1
            self.stats["batched_requests"] += len(live)
            self.stats["max_batch_riders"] = max(
                self.stats["max_batch_riders"], len(live))
        t_end = time.perf_counter()
        for req, value, err in zip(live, values, errs):
            deg = self._stamp_degraded(value if err is None else None)
            self._complete(req, err is None, value, err, t_start, t_end,
                           degraded=deg)

    def _worker(self) -> None:
        name_os_thread()
        while True:
            _, unit = self._exec_q.get()
            if unit is None:
                return
            kind, payload, t_dispatch = unit
            reqs = payload if kind == "batch" else [payload]
            if kind == "batch" and len(reqs) == 1:   # one-rider bucket: solo
                kind = "single"
            t_start = time.perf_counter()
            with span("serve.unit", kind=kind, template=reqs[0].name,
                      riders=len(reqs), rids=";".join(str(r.rid) for r in reqs),
                      rider_wait_s=len(reqs) * (t_start - t_dispatch),
                      batch_wait_s=t_dispatch - reqs[0].t_submit):
                if kind == "lookup":
                    self._run_lookup(reqs[0])
                elif kind == "single":
                    self._run_single(reqs[0])
                else:
                    self._run_shared(reqs)


def _wire_id(raw: str):
    """Path-segment vertex id -> lookup key (ids are int64 in this lake;
    a non-numeric segment passes through for string-keyed schemas)."""
    try:
        return int(raw)
    except (TypeError, ValueError):
        return raw


def latency_stats(results: list[QueryResult]) -> dict:
    """Service-latency percentiles over the successful results (plus mean
    queue wait — batching trades a bounded window of queueing for shared
    work, and the serving benchmark reports both sides)."""
    lats = sorted(r.service_s for r in results if r.ok)
    if not lats:
        return {"count": 0}
    queued = [r.queued_s for r in results if r.ok]
    pick = lambda q: lats[min(len(lats) - 1, int(q * len(lats)))]
    return {
        "count": len(lats),
        "mean_s": sum(lats) / len(lats),
        "p50_s": pick(0.50),
        "p95_s": pick(0.95),
        "p99_s": pick(0.99),
        "max_s": lats[-1],
        "mean_queued_s": sum(queued) / len(queued),
    }
