"""Back-to-back analytics jobs through ``core.algorithms``.

Set-up: make the graph from the seed, write the lake, start a fresh engine
(``startup_s``), and run one job of ``warmup_supersteps`` supersteps, which
compiles the superstep and builds the CSR.

Window: jobs of ``supersteps`` supersteps (``tol=0``, so every job runs them
all) start one after another until ``--seconds`` have passed since the first
began; the job in flight then completes and counts.  ``superstep_ms`` is the
time from the first job's start to the last job's end over the supersteps
those jobs completed.

Check: every job's ranks against the float64 power iteration over the
generated edges (``refs/pagerank.py``), by the largest relative error.
"""

from __future__ import annotations

import time

import numpy as np


def run(ctx) -> dict:
    from repro.core import algorithms
    from repro.lakehouse.objectstore import ObjectStore, StoreConfig

    from refs.pagerank import max_rel_err, pagerank_tables

    traffic, cfg = ctx.traffic, ctx.config
    algo = getattr(algorithms, traffic["algorithm"])
    edge_type, k, damping = traffic["edge_type"], traffic["supersteps"], traffic["damping"]

    def job(supersteps: int) -> np.ndarray:
        return algo(engine, edge_type, max_iters=supersteps, tol=0.0, damping=damping)

    gen = ctx.generator()
    with ctx.span("setup.generate"):
        tables = gen.generate(cfg, ctx.seed)
    store = ObjectStore(StoreConfig(root=str(ctx.lake_dir())))
    with ctx.span("setup.write_lake"):
        gen.write(tables, store, cfg)
    schema = gen.graph_schema()
    et = schema.edge_types[edge_type]
    engine = ctx.make_engine(store, schema)
    try:
        t0 = time.perf_counter()
        with ctx.span("setup.startup"):
            breakdown = engine.startup()
        startup_s = time.perf_counter() - t0
        n = engine.topology.n_vertices(et.src_type)
        with ctx.span("setup.warm"):
            job(traffic["warmup_supersteps"])
        ctx.setup_done()
        outputs, spans = [], []
        with ctx.window():
            t_first = time.perf_counter()
            while not spans or time.perf_counter() - t_first < ctx.seconds:
                ts = time.perf_counter()
                with ctx.span(f"job.{traffic['algorithm']}"):
                    outputs.append(job(k))
                spans.append((ts, time.perf_counter()))
        ctx.read_memory()
        raw_of_dense = engine.read_vertex_column(et.src_type, np.arange(n), "id")
    finally:
        engine.close()
    del engine

    ref = pagerank_tables(tables, et.table, schema.vertex_types[et.src_type].table,
                          damping, k)
    order = np.argsort(raw_of_dense)
    err = max(max_rel_err(out[order], ref) for out in outputs)
    supersteps = k * len(outputs)
    ctx.log(f"{n} vertices, {len(tables[et.table]['src'])} arcs; "
            f"{len(outputs)} jobs of {k} supersteps in "
            f"{spans[-1][1] - spans[0][0]:.3f} s; job times "
            f"{[round(b - a, 3) for a, b in spans]}")
    return {
        "attempted": len(outputs),
        "failed": 0,
        "metrics": {"superstep_ms": (spans[-1][1] - spans[0][0]) / supersteps * 1e3,
                    "startup_s": startup_s},
        "observations": {"startup": breakdown, "supersteps": supersteps,
                         "graph": {"vertices": n, "edges": len(tables[et.table]["src"])}},
        "checks": {"rank_max_rel_err": {"value": err,
                                        "limit": traffic["limits"]["rank_max_rel_err"]}},
    }
