"""Open-loop serving through the program's ``QueryServer``.

Set-up: make the lake from the seed, start a fresh engine (``startup_s``),
install the traffic file's GSQL templates and analytics requests, start the
server with the configuration's settings, and warm every template until a
pass over all its parameters makes no lake fetch.

Window: requests are sent on a schedule, whatever the server's state, at
``rate_per_s`` (see ``schedule``).  Every seed sends the same multiset of
(template, params) at the same times, in its own order, so the work does not
change with the seed.  A request is timed from the moment it was due to the moment
its result reached the client; one that fails, is refused, or never comes
counts as infinitely late.  The window closes when the last request is
answered, or a minute after the last was due.

Check: every answer due in the window is compared with the plain reference
once the window has closed: a template's answer exactly with
``refs/<reference>.py``, the module the traffic file's ``reference`` key names
(see ``reference_module``), and ranks within the traffic file's limit of the
float64 power iteration.  Inside the window each answer is cut to what the
check needs (``Serving.kept``): the reference module decides that for its
templates, so an answer served by any route keeps what its check compares.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from pathlib import Path

import numpy as np

LATE_AFTER_CLOSE_S = 60.0
WARM_PASSES = 5
DEFAULT_REFERENCE = "ldbc_queries"
REFERENCE_API = ("reference", "compact", "program_answer", "same_answer")


def reference_module(chip_dir: Path, traffic: dict):
    """``refs/<name>.py`` for the traffic file's ``reference`` key
    (``DEFAULT_REFERENCE`` where it has none).  The module gives:

    - ``reference(tables, dtype=np.float64)``, whose ``.answer(name, params)``
      is a template's answer;
    - ``compact(result)``, what the check needs of one answer of an installed
      template, whichever route served it; the window keeps this until the
      check, and nothing else of the answer;
    - ``program_answer(kept, raw_of_dense, want)``, what ``compact`` kept, in
      the reference's form;
    - ``same_answer(a, b)``.

    Each is required: ``start`` fails before set-up, naming any that is
    missing."""
    from harness import load_module

    name = traffic.get("reference", DEFAULT_REFERENCE)
    return load_module(chip_dir / "refs" / f"{name}.py", f"chipbench_ref_{name}")


def param_combos(pool) -> list[dict]:
    """A pool is a list of parameter dicts, or a dict of value lists whose
    cross product is the list."""
    if isinstance(pool, list):
        return [dict(p) for p in pool]
    keys = list(pool)
    return [dict(zip(keys, vals)) for vals in itertools.product(*pool.values())]


def _deal(items: list, block: int, rng) -> list:
    """``items`` in an order where each run of ``block`` holds one item of
    each of ``block`` equal slices of ``items`` as given, in shuffled order."""
    nb = math.ceil(len(items) / block)
    blocks: list = [[] for _ in range(nb)]
    for s in range(0, len(items), nb):
        for item, b in zip(items[s:s + nb], rng.permutation(nb)):
            blocks[b].append(item)
    return [blk[i] for b in rng.permutation(nb) for blk in (blocks[b],)
            for i in rng.permutation(len(blk))]


def schedule(traffic: dict, seed: int, seconds: float) -> list[tuple]:
    """``(send_at_s, name, params)`` for one window, sorted by time.

    ``round(rate * seconds)`` requests, one every ``1/rate`` (as wrk2
    sends); templates by the mix's weights (largest remainder), each template
    cycling through its parameter combos in file order.  The seed orders the
    requests so that each run of ``sum(mix)`` requests holds the mix: every
    seed sends the same work, in its own order."""
    rate = float(traffic["rate_per_s"])
    n = max(1, round(rate * seconds))
    mix = traffic["mix"]
    block = sum(mix.values())
    exact = {k: n * w / block for k, w in mix.items()}
    counts = {k: int(v) for k, v in exact.items()}
    for k in sorted(exact, key=lambda k: counts[k] - exact[k])[:n - sum(counts.values())]:
        counts[k] += 1
    reqs = []
    for name in mix:
        combos = param_combos(traffic["params"][name])
        reqs += [(name, combos[i % len(combos)]) for i in range(counts[name])]
    rng = np.random.default_rng(seed)
    reqs = _deal(reqs, block, rng)
    return [((i + 1) / rate, name, params) for i, (name, params) in enumerate(reqs)]


def _analytics_fns(traffic: dict) -> dict:
    from repro.core import algorithms

    fns = {}
    for name, a in traffic.get("analytics", {}).items():
        algo = getattr(algorithms, a["algorithm"])

        def fn(engine, _algo=algo, _a=a, **_params):
            return _algo(engine, _a["edge_type"], max_iters=_a["supersteps"],
                         tol=0.0, damping=_a["damping"])
        fns[name] = fn
    return fns


def _warm(server, ctx, traffic: dict, engine) -> int:
    """Every template over all its combos, until a pass makes no lake fetch."""
    warm = [(name, p) for name in traffic["mix"]
            for p in param_combos(traffic["params"][name])]
    for i in range(WARM_PASSES):
        before = engine.cache.stats["lake_fetches"]
        for r in server.run_batch(warm):
            if not r.ok:
                raise RuntimeError(f"warm-up request failed: {r.error}")
        fetched = engine.cache.stats["lake_fetches"] - before
        ctx.log(f"warm pass {i}: {fetched} lake fetches")
        if fetched == 0:
            return i + 1
    return WARM_PASSES


def send(serving, ctx, plan: list) -> tuple[list, list]:
    """Send ``plan`` open loop; return one record per request and the
    generator's lateness per send."""
    from repro.errors import ServerOverloadedError

    server = serving.server
    records: list = [None] * len(plan)
    lateness = []
    waiters = []
    t0 = time.perf_counter()
    deadline = t0 + (plan[-1][0] if plan else 0.0) + LATE_AFTER_CLOSE_S

    def wait(i, rid, t_due, name):
        with ctx.span(f"request.{name}"):
            try:
                r = server.result(rid, timeout_s=max(0.0, deadline - time.perf_counter()))
            except TimeoutError:
                return                                   # never came
            t_done = time.perf_counter()
        records[i] = {"ok": r.ok, "error": r.error,
                      "latency_s": t_done - t_due if r.ok else math.inf,
                      "queued_s": r.queued_s, "service_s": r.service_s,
                      "answer": serving.kept(name, r.value) if r.ok else None}

    for i, (at, name, params) in enumerate(plan):
        t_due = t0 + at
        delay = t_due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        lateness.append(time.perf_counter() - t_due)
        try:
            rid = server.submit(name, **params)
        except ServerOverloadedError as e:
            records[i] = {"ok": False, "error": f"refused: {e}", "latency_s": math.inf}
            continue
        th = threading.Thread(target=wait, args=(i, rid, t_due, name), daemon=True)
        th.start()
        waiters.append(th)
    for th in waiters:
        th.join(max(0.0, deadline - time.perf_counter()) + 1.0)
    return records, lateness


def _verify(records: list, plan: list, tables: dict, schema, raw_of_dense: dict,
            traffic: dict, installed: set, ref_module) -> tuple[dict, list]:
    from refs.pagerank import max_rel_err, pagerank_tables

    ref = ref_module.reference(tables)
    answers: dict = {}
    wrong = []
    rank_err = 0.0
    for i, ((_, name, params), rec) in enumerate(zip(plan, records)):
        if rec is None or not rec["ok"]:
            wrong.append((i, name, params, rec and rec["error"]))
            continue
        key = (name, tuple(sorted(params.items())))
        if key not in answers:
            if name in installed:
                answers[key] = ref.answer(name, params)
            else:
                a = traffic["analytics"][name]
                et = schema.edge_types[a["edge_type"]]
                answers[key] = pagerank_tables(
                    tables, et.table, schema.vertex_types[et.src_type].table,
                    a["damping"], a["supersteps"])
        want = answers[key]
        if name in installed:
            got = ref_module.program_answer(rec["answer"], raw_of_dense, want)
            if not ref_module.same_answer(want, got):
                wrong.append((i, name, params, "differs from the reference"))
        else:
            vt = schema.edge_types[traffic["analytics"][name]["edge_type"]].src_type
            got = rec["answer"][np.argsort(raw_of_dense[vt])]
            rank_err = max(rank_err, max_rel_err(got, want))
    limits = traffic["limits"]
    checks = {"answers_wrong_or_missing": {"value": len(wrong),
                                           "limit": limits["answers_wrong_or_missing"]}}
    if traffic.get("analytics"):
        checks["rank_max_rel_err"] = {"value": rank_err,
                                      "limit": limits["rank_max_rel_err"]}
    return checks, wrong


@dataclasses.dataclass
class Serving:
    """A started engine and server over the cell's lake, warmed, and the
    traffic's reference module."""
    tables: dict
    schema: object
    engine: object
    server: object
    installed: set
    ref_module: object
    startup_s: float
    breakdown: dict
    warm_passes: int

    def kept(self, name: str, value):
        """What the check needs of one answer: the reference module's
        ``compact`` of an installed template's, an analytics request's ranks."""
        if name in self.installed:
            return self.ref_module.compact(value)
        return np.asarray(value)

    def close(self) -> None:
        try:
            self.server.close()
        finally:
            self.engine.close()


def start(ctx) -> Serving:
    """Set-up: lake from the seed, a fresh engine, the server, warm-up."""
    from harness import BenchError
    from repro.gsql.session import GraphSession
    from repro.lakehouse.objectstore import ObjectStore, StoreConfig
    from repro.serving.server import QueryServer, ServerConfig

    traffic, cfg = ctx.traffic, ctx.config
    ref_module = reference_module(ctx.cell.chip_dir, traffic)
    missing = [fn for fn in REFERENCE_API if not callable(getattr(ref_module, fn, None))]
    if missing:
        raise BenchError(f"{ref_module.__file__} lacks {', '.join(missing)}")
    gen = ctx.generator()
    with ctx.span("setup.generate"):
        tables = gen.generate(cfg, ctx.seed)
    store = ObjectStore(StoreConfig(root=str(ctx.lake_dir())))
    with ctx.span("setup.write_lake"):
        gen.write(tables, store, cfg)
    schema = gen.graph_schema()
    engine = ctx.make_engine(store, schema)
    t0 = time.perf_counter()
    with ctx.span("setup.startup"):
        breakdown = engine.startup()
    startup_s = time.perf_counter() - t0
    session = GraphSession.for_engine(engine)
    for name, text in traffic["queries"].items():
        session.install(name, text)
    srv = cfg["server"]
    server = QueryServer(session, query_fns=_analytics_fns(traffic),
                         config=ServerConfig(n_workers=srv["workers"],
                                             batch_window_ms=srv["batch_window_ms"],
                                             max_batch_riders=srv["max_batch_riders"]))
    serving = Serving(tables, schema, engine, server, set(traffic["queries"]),
                      ref_module, startup_s, breakdown, 0)
    try:
        with ctx.span("setup.warm"):
            serving.warm_passes = _warm(server, ctx, traffic, engine)
    except BaseException:
        serving.close()
        raise
    return serving


def latency_ms(records: list, q: float) -> float:
    """Nearest-rank percentile: one request's latency, infinitely late ones
    (failed, refused, never answered) included."""
    lat = np.array([r["latency_s"] if r else math.inf for r in records])
    return float(np.percentile(lat, q, method="inverted_cdf")) * 1e3


def run(ctx) -> dict:
    traffic = ctx.traffic
    s = start(ctx)
    try:
        plan = schedule(traffic, ctx.seed, ctx.seconds)
        cache0 = dict(s.engine.cache.stats)
        with ctx.window():
            records, lateness = send(s, ctx, plan)
        cache1 = dict(s.engine.cache.stats)
        ctx.read_memory()
        raw_of_dense = {vt: s.engine.read_vertex_column(
            vt, np.arange(s.engine.topology.n_vertices(vt)), "id")
            for vt in s.schema.vertex_types}
    finally:
        s.close()

    checks, wrong = _verify(records, plan, s.tables, s.schema, raw_of_dense, traffic,
                            s.installed, s.ref_module)
    for i, name, params, why in wrong[:5]:
        ctx.log(f"request {i} {name} {params}: {why}")
    ok = [r for r in records if r and r["ok"]]
    ctx.log(f"latency p50 {latency_ms(records, 50):.3f} ms, p95 "
            f"{latency_ms(records, 95):.3f} ms")
    ctx.log(f"{len(ok)}/{len(plan)} answered; generator lateness max "
            f"{max(lateness) * 1e3:.3f} ms, mean {np.mean(lateness) * 1e3:.3f} ms; "
            f"{s.warm_passes} warm passes")
    return {
        "attempted": len(plan),
        "failed": len(plan) - len(ok),
        "metrics": {"query_p50_ms": latency_ms(records, 50),
                    "startup_s": s.startup_s},
        "observations": {
            "startup": s.breakdown,
            "queued_s": [r["queued_s"] for r in ok],
            "service_s": [r["service_s"] for r in ok],
            "cache_delta": {k: cache1[k] - cache0[k] for k in cache0},
        },
        "checks": checks,
    }
