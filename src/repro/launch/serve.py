"""Serving driver: GraphLake engine + batched BI query serving.

Generates (or reuses) an LDBC-style lakehouse, starts the engine (first or
second connection), installs the BI suite (``core/bi_queries.BI_GSQL``) and
drives randomized requests for it through the QueryServer, reporting
startup time and latency percentiles — the in-process equivalent of the
paper's wrk2 evaluation (§7.5).  ``--verify`` re-runs every request solo with
``ExecOptions(pushdown=False)`` and requires an identical result.  The
process exits non-zero when any request fails or mismatches.

    PYTHONPATH=src python -m repro.launch.serve --sf 0.01 --requests 50
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import shutil
import time
from typing import Callable

import numpy as np

from repro.core.bi_queries import BI_GSQL, install_bi_queries
from repro.core.engine import GraphLakeEngine
from repro.core.query import ExecOptions, QueryResult
from repro.data.ldbc import generate_ldbc, ldbc_graph_schema
from repro.gsql.session import GraphSession
from repro.lakehouse.objectstore import ObjectStore, StoreConfig
from repro.serving.server import QueryServer, ServerConfig, latency_stats

# parameter pools per installed BI template (values inside the generator's
# ranges: dates 2008-2022, 15 base tag names, 50 cities)
_PARAMS: dict[str, Callable[[random.Random], dict]] = {
    "bi1": lambda r: {"tag": r.choice(["Music", "Sports", "Politics", "Tech"]),
                      "date": r.choice([20090101, 20120101, 20150101])},
    "bi2": lambda r: r.choice([{"lo": 20100101, "hi": 20121231},
                               {"lo": 20120101, "hi": 20151231},
                               {"lo": 20180101, "hi": 20221231}]),
    "bi3": lambda r: {"min_len": r.choice([500, 1000, 1900])},
    "bi4": lambda r: {"city": f"city_{r.randrange(50)}"},
    "bi5": lambda r: {"min_degree": r.choice([5, 10, 20]),
                      "date": r.choice([20120101, 20140101])},
}


def make_requests(n: int, seed: int = 0) -> list[tuple[str, dict]]:
    """``n`` (installed name, params) requests over the BI suite."""
    rng = random.Random(seed)
    names = sorted(BI_GSQL)
    out = []
    for _ in range(n):
        name = rng.choice(names)
        out.append((name, _PARAMS[name](rng)))
    return out


def results_identical(a: QueryResult, b: QueryResult) -> bool:
    """Same vertex set, alias sets, edge count and accumulators, bit for bit."""
    if (a.n_edges_scanned != b.n_edges_scanned
            or not np.array_equal(a.vset.ids(), b.vset.ids())
            or set(a.alias_sets) != set(b.alias_sets)
            or set(a.accumulators) != set(b.accumulators)):
        return False
    return (all(np.array_equal(a.alias_sets[k].ids(), b.alias_sets[k].ids())
                for k in a.alias_sets)
            and all(np.array_equal(a.accumulators[k], b.accumulators[k])
                    for k in a.accumulators))


@dataclasses.dataclass
class ServeReport:
    startup_mode: str
    startup_s: float
    breakdown: dict
    n_requests: int
    n_ok: int
    errors: list            # (request index, error text) of failed requests
    mismatched: list        # request indices whose result differs from solo
    wall_s: float
    latency: dict

    @property
    def ok(self) -> bool:
        return self.n_ok == self.n_requests and not self.mismatched


def serve(root: str, sf: float = 0.01, requests: int = 40, workers: int = 2,
          latency_scale: float = 0.0, fresh: bool = False, verify: bool = False,
          seed: int = 0, log: Callable[[str], None] = print) -> ServeReport:
    """Generate or reuse the lake at ``root``, start the engine, serve
    ``requests`` BI requests and (with ``verify``) check each against a solo
    ``pushdown=False`` run."""
    if fresh and os.path.exists(root):
        shutil.rmtree(root)
    store = ObjectStore(StoreConfig(root=root, latency_scale=latency_scale))
    if not os.path.exists(os.path.join(root, "tables")):
        t0 = time.perf_counter()
        ds = generate_ldbc(store, scale_factor=sf)
        log(f"generated LDBC SF={sf}: {ds.n_persons} persons, {ds.n_comments} "
            f"comments, {ds.n_edges} edges in {time.perf_counter() - t0:.3f}s")

    engine = GraphLakeEngine(store, ldbc_graph_schema())
    try:
        t0 = time.perf_counter()
        breakdown = engine.startup()
        startup_s = time.perf_counter() - t0
        log(f"startup ({engine.startup_mode}): {startup_s:.3f}s  "
            f"breakdown={json.dumps({k: round(v, 3) for k, v in breakdown.items()})}")

        session = GraphSession.for_engine(engine)
        install_bi_queries(session)
        reqs = make_requests(requests, seed)
        server = QueryServer(session, config=ServerConfig(n_workers=workers))
        try:
            t1 = time.perf_counter()
            results = server.run_batch(reqs)
            wall = time.perf_counter() - t1
        finally:
            server.close()

        errors = [(i, r.error) for i, r in enumerate(results) if not r.ok]
        mismatched = []
        if verify:
            solo_opts = ExecOptions(pushdown=False)
            for i, ((name, params), r) in enumerate(zip(reqs, results)):
                if r.ok and not results_identical(
                        r.value, session.query(name, options=solo_opts, **params)):
                    mismatched.append(i)
        report = ServeReport(
            startup_mode=engine.startup_mode, startup_s=startup_s,
            breakdown=dict(breakdown), n_requests=len(results),
            n_ok=len(results) - len(errors), errors=errors,
            mismatched=mismatched, wall_s=wall,
            latency=latency_stats(results))
    finally:
        engine.close()

    log(f"{report.n_ok}/{report.n_requests} ok, throughput "
        f"{report.n_ok / wall:.2f} q/s over {wall:.2f}s")
    log("latency: " + json.dumps({k: round(v, 4) for k, v in report.latency.items()}))
    if verify:
        log(f"pushdown=False solo parity: {report.n_ok - len(mismatched)}/"
            f"{report.n_ok} identical")
    for i, err in errors[:5]:
        log(f"request {i} {reqs[i]} failed: {err}")
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default="/tmp/graphlake_serve")
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--latency-scale", type=float, default=0.0,
                    help="1.0 simulates S3 latency on lake reads")
    ap.add_argument("--fresh", action="store_true", help="regenerate the lake")
    ap.add_argument("--verify", action="store_true",
                    help="check every result against a solo pushdown=False run")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    report = serve(args.root, sf=args.sf, requests=args.requests,
                   workers=args.workers, latency_scale=args.latency_scale,
                   fresh=args.fresh, verify=args.verify)
    if not report.ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
