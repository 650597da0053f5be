"""Tests: program spans (``repro.tracing``) in a CPU profiler trace — their
attributes, how the executor's stages nest inside a hop, lake spans only on
a cache miss, the scheduler's stall counter, the server's thread names, and
PageRank's named scopes in the compiled program."""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms
from repro.core.engine import GraphLakeEngine
from repro.data.ldbc import generate_ldbc, ldbc_graph_schema
from repro.gsql.session import GraphSession
from repro.lakehouse.objectstore import ObjectStore, StoreConfig
from repro.serving.server import STALL_S, QueryServer, ServerConfig
from repro.tracing import PREFIX, span

BI1 = """
    SELECT p FROM Tag:t -(HasTag:e1)- Comment:c -(HasCreator:e2)- Person:p
    WHERE t.name == $tag AND e2.creationDate > $date AND p.gender == 'Female'
    ACCUM p.@cnt += 1
"""
HOP_STAGES = {"scan.gather", "read.E", "predicate.E", "read.V", "predicate.V", "accum"}


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    store = ObjectStore(StoreConfig(root=str(tmp_path_factory.mktemp("lake"))))
    generate_ldbc(store, scale_factor=0.004, n_files=3, row_group_rows=512)
    eng = GraphLakeEngine(store, ldbc_graph_schema())
    eng.startup()
    s = GraphSession.for_engine(eng)
    s.install("bi1", BI1)
    yield s
    eng.close()


def _record(path, fn) -> list[dict]:
    """Run ``fn`` under the profiler; the program spans it recorded, each
    with its attributes and the names of the spans around it on its thread."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(sorted(path.rglob("*.xplane.pb"))[-1]))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            events = sorted((e for e in line.events if e.name.startswith(PREFIX)),
                            key=lambda e: (e.start_ns, -e.end_ns))
            stack: list = []
            for e in events:
                while stack and stack[-1]["end"] < e.end_ns:
                    stack.pop()
                sp = {"name": e.name[len(PREFIX):], "attrs": dict(e.stats),
                      "end": e.end_ns, "parents": [p["name"] for p in stack]}
                out.append(sp)
                stack.append(sp)
    return out


def test_nested_spans_arrive_with_their_attributes(tmp_path):
    def work():
        with span("outer", rows=123, kind="batch", rids="7;8") as s:
            with span("inner", rows=5):
                pass
            s.set_metadata(rows_out=42)

    spans = {sp["name"]: sp for sp in _record(tmp_path, work)}
    assert spans["outer"]["attrs"] == {"rows": 123, "kind": "batch", "rids": "7;8",
                                       "rows_out": 42}
    assert spans["inner"]["attrs"] == {"rows": 5}
    assert spans["inner"]["parents"] == ["outer"]
    assert spans["outer"]["parents"] == []


def _check_stage_nesting(spans, riders):
    names = {sp["name"] for sp in spans}
    assert {"query.seed", "read.seed", "predicate.seed", "query.hop"} | HOP_STAGES <= names
    for sp in spans:
        if sp["name"] in HOP_STAGES:
            assert sp["parents"][-1] == "query.hop", sp
        if sp["name"] in ("read.seed", "predicate.seed"):
            assert sp["parents"][-1] == "query.seed", sp
    hops = [sp for sp in spans if sp["name"] == "query.hop"]
    assert [h["attrs"]["edge_type"] for h in hops] == ["HasTag", "HasCreator"]
    for h in hops:
        assert h["attrs"]["riders"] == riders
        assert h["attrs"]["rows_in"] > 0 and h["attrs"]["rows_out"] >= 0
    for sp in spans:
        if sp["name"].startswith("predicate."):
            assert 0 <= sp["attrs"]["rows_out"] <= sp["attrs"]["rows_in"]


def test_solo_query_spans_nest_by_stage(session, tmp_path):
    out = {}
    spans = _record(tmp_path, lambda: out.update(
        r=session.query("bi1", tag="Music", date=20100101)))
    _check_stage_nesting(spans, riders=1)
    last_hop = [sp for sp in spans if sp["name"] == "query.hop"][-1]
    assert last_hop["attrs"]["rows_out"] == len(out["r"].frames[-1])


def test_batched_query_spans_nest_by_stage(session, tmp_path):
    params = [{"tag": "Music", "date": 20090101}, {"tag": "Music", "date": 20110101}]
    spans = _record(tmp_path, lambda: session.query_batch("bi1", params))
    _check_stage_nesting(spans, riders=2)


def test_lake_spans_come_on_a_miss_only(session, tmp_path):
    eng = session.engine
    eng.cache.drop_all()
    cold = _record(tmp_path / "cold", lambda: session.query("bi1", tag="Music",
                                                             date=20100101))
    warm = _record(tmp_path / "warm", lambda: session.query("bi1", tag="Music",
                                                             date=20100101))
    fetches = [sp for sp in cold if sp["name"] == "lake.fetch"]
    assert fetches and all(sp["attrs"]["bytes"] > 0 for sp in fetches)
    assert any(sp["name"] == "lake.decode" for sp in cold)
    assert not any(sp["name"] == "lake.fetch" for sp in warm)


def test_server_units_carry_their_riders_and_waits(session, tmp_path):
    srv = QueryServer(session, config=ServerConfig(n_workers=1, batch_window_ms=30.0))
    try:
        def work():
            rids = [srv.submit("bi1", tag="Music", date=20090101 + i * 10000)
                    for i in range(3)]
            assert all(srv.result(r).ok for r in rids)
        spans = _record(tmp_path, work)
    finally:
        srv.close()
    units = [sp for sp in spans if sp["name"] == "serve.unit"]
    assert sum(u["attrs"]["riders"] for u in units) == 3
    for u in units:
        a = u["attrs"]
        assert a["template"] == "bi1" and a["kind"] in ("single", "batch")
        assert a["rider_wait_s"] >= 0 and a["batch_wait_s"] >= 0
        assert len(str(a["rids"]).split(";")) == a["riders"]
    assert any(sp["name"] == "query.seed" and "serve.unit" in sp["parents"]
               for sp in spans)


def test_scheduler_counts_a_stall_and_names_its_threads(session):
    def spin(engine):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.6:   # holds the interpreter lock
            pass
        return 0

    srv = QueryServer(session, query_fns={"spin": spin},
                      config=ServerConfig(n_workers=2, batch_window_ms=0.0))
    old = sys.getswitchinterval()
    try:
        time.sleep(0.1)                 # the scheduler idles on its heartbeat
        names = {t.name for t in threading.enumerate()}
        assert {"serve-scheduler", "serve-worker-0", "serve-worker-1"} <= names
        if sys.platform.startswith("linux"):
            tid = srv._scheduler.native_id
            with open(f"/proc/self/task/{tid}/comm") as f:
                assert f.read().strip() == "serve-scheduler"
        sys.setswitchinterval(0.25)
        assert srv.result(srv.submit("spin")).ok
    finally:
        sys.setswitchinterval(old)
        srv.close()
    stats = srv.health()["stats"]
    assert stats["max_stall_s"] > STALL_S
    assert stats["stall_s"] >= stats["max_stall_s"]


def test_pagerank_scopes_name_the_compiled_ops():
    n, e = 64, 512
    rng = np.random.default_rng(0)
    rev_src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    indptr = jnp.asarray(np.sort(np.r_[0, rng.integers(0, e, n - 1), e]), jnp.int32)
    deg = jnp.asarray(rng.integers(0, 4, n), jnp.float32)
    rank = jnp.full(n, 1.0 / n, jnp.float32)
    text = algorithms._pagerank_step_csr.lower(
        rank, rev_src, indptr, deg, n=n, damping=0.85).compile().as_text()
    for name in ("pagerank.gather", "pagerank.segment_sum", "pagerank.dangling"):
        assert name in text


def test_span_costs_microseconds_with_no_profiler():
    n = 20000
    costs = []
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n):
            with span("x", rows=i):
                pass
        costs.append((time.perf_counter() - t0) / n)
    # about 1 µs on a quiet core; the bound leaves room for a loaded host
    assert sorted(costs)[2] < 10e-6
