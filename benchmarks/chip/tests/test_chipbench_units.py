"""Unit tests of the chip benchmark's parts, on the CPU: the trace reduction,
the per-layer readers, the generators, the open-loop schedule and the
controls of the checks."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import trace_reduce  # noqa: E402
from harness import SPAN_PREFIX, BenchError, load_module  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _gen(name):
    return load_module(CHIP / "generators" / f"{name}.py", f"test_gen_{name}")


def _layer(name):
    return load_module(CHIP / "layers" / f"{name}.py",
                       "test_layer_" + name.replace(".", "_"))


# -- trace reduction -----------------------------------------------------------

def test_reduce_small_hand_made_trace():
    ev = {"devices": [[("fusion", 10, 30), ("gather", 25, 40), ("fusion", 70, 80),
                       ("outside", 200, 300)]],
          "spans": [("window", 0, 100), ("job.pagerank", 0, 60),
                    ("request.bi1", 50, 100)]}
    r = trace_reduce.reduce(ev, 1)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)             # [10, 40] + [70, 80]
    assert dict(r["device_ops"]) == pytest.approx({"fusion": 30e-9, "gather": 15e-9})
    # gap [0, 10] lies in the job only; the middle of [40, 70] lies in both
    # spans and goes to the one that began last; [80, 100] in the request
    assert dict(r["idle_gaps"]) == pytest.approx({"job.pagerank": 10e-9,
                                                  "request.bi1": 50e-9})


def test_reduce_recorded_chip_trace():
    """Events recorded from a traced PageRank run on one v5e."""
    ev = json.loads((DATA / "pagerank_trace_events.json").read_text())
    r = trace_reduce.reduce(ev, 1)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(v for _, v in r["device_ops"]) >= r["busy_s"] * 0.5
    idle = r["window_s"] - r["busy_s"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(idle, rel=1e-6, abs=1e-9) \
        or len(r["idle_gaps"]) == trace_reduce.TOP
    assert len(r["device_ops"]) <= trace_reduce.TOP


def test_load_reads_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + "window"):
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "job.x"):
            jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace_reduce.load(tmp_path, SPAN_PREFIX)
    names = {n for n, _, _ in ev["spans"]}
    assert {"window", "job.x"} <= names
    assert ev["devices"] == []                         # no TPU plane on a CPU
    r = trace_reduce.reduce(ev, 1)
    assert r["busy_s"] == 0.0 and r["window_s"] > 0


# -- per-layer readers ----------------------------------------------------------

def test_layer_readers():
    obs = {"queued_s": [0.001, 0.003], "service_s": [0.2, 0.1, 0.4],
           "cache_delta": {"hits": 99, "misses": 1},
           "startup": {"edge_list_build_s": 2.5},
           "trace": {"busy_s": 1.0, "window_s": 4.0},
           "device": {"kind": "TPU v5 lite"},
           "supersteps": 2, "graph": {"vertices": 1000, "edges": 16000}}
    assert _layer("queue_wait_ms").read(obs) == pytest.approx(2.0)
    assert _layer("service_p50_ms").read(obs) == pytest.approx(200.0)
    assert _layer("cache_hit_rate").read(obs) == pytest.approx(99.0)
    assert _layer("edge_list_build_s").read(obs) == 2.5
    assert _layer("device_idle.serve").read(obs) == pytest.approx(75.0)
    assert _layer("device_idle.analytics").read(obs) == pytest.approx(75.0)
    least = (4 * 16000 + 12 * 1000) / 819e9
    assert _layer("superstep_hbm_roofline").read(obs) == pytest.approx(100 * least / 0.5)


@pytest.mark.parametrize("name", ["queue_wait_ms", "service_p50_ms",
                                  "cache_hit_rate", "edge_list_build_s", "device_idle.serve",
                                  "device_idle.analytics", "superstep_hbm_roofline"])
def test_layer_reader_with_nothing_to_read_returns_none(name):
    empty = {"cache_delta": {"hits": 0, "misses": 0}, "trace": None,
             "device": {"kind": "TPU v5 lite"}}
    assert _layer(name).read(empty) is None


def test_roofline_reader_refuses_an_unknown_chip():
    obs = {"trace": {"busy_s": 1.0, "window_s": 2.0}, "device": {"kind": "cpu"},
           "supersteps": 1, "graph": {"vertices": 1, "edges": 1}}
    with pytest.raises(KeyError):
        _layer("superstep_hbm_roofline").read(obs)


# -- generators and the schedule -------------------------------------------------

LDBC_TINY = dict(json.loads((CHIP / "configs" / "ldbc-snb-sf1.json").read_text()),
                 persons=100, comments=5000, tags=40)
G500_TINY = dict(json.loads((CHIP / "configs" / "graph500-22.json").read_text()),
                 scale=8)


@pytest.mark.parametrize("name,cfg", [("ldbc", LDBC_TINY), ("graph500", G500_TINY)])
def test_generators_repeat_per_seed(name, cfg):
    gen = _gen(name)
    big = 2**31 + 12345
    a, b, c = gen.generate(cfg, big), gen.generate(cfg, big), gen.generate(cfg, 7)
    flat = lambda t: {(k, c): v for k, cols in t.items() for c, v in cols.items()}  # noqa: E731
    fa, fb, fc = flat(a), flat(b), flat(c)
    assert fa.keys() == fb.keys() == fc.keys()
    assert all(np.array_equal(fa[k], fb[k]) for k in fa)
    assert any(not np.array_equal(fa[k], fc[k]) for k in fa)
    for cols in a.values():
        if "src" in cols:
            assert np.all(np.diff(cols["src"]) >= 0)


def test_graph500_is_undirected_simple_and_without_isolated_vertices():
    gen = _gen("graph500")
    g = gen.generate(dict(G500_TINY, scale=12), 3)
    t, ids = g["Node_Edge_Node"], g["Node"]["id"]
    arcs = set(zip(t["src"].tolist(), t["dst"].tolist()))
    assert len(arcs) == len(t["src"])                       # no duplicate arc
    assert arcs == {(d, s) for s, d in arcs}                # each edge both ways
    assert not np.any(t["src"] == t["dst"])                 # no self-loop
    assert np.array_equal(ids, np.unique(t["src"]))         # every vertex has an edge
    w = dict(zip(zip(t["src"].tolist(), t["dst"].tolist()), t["weight"].tolist()))
    assert all(w[(s, d)] == w[(d, s)] for s, d in arcs)     # one weight per edge
    # 16 << 12 edges drawn; a few in ten are duplicates or loops at this scale
    assert 0.5 * (16 << 12) < len(arcs) / 2 < 16 << 12
    assert ids.max() < 1 << 12


def test_graph500_keeps_the_kronecker_skew_under_permuted_labels():
    gen = _gen("graph500")
    t = gen.generate(dict(G500_TINY, scale=12), 5)["Node_Edge_Node"]
    deg = np.bincount(t["src"], minlength=1 << 12)
    hubs = np.argsort(deg)[-16:]
    assert deg.max() > 20 * deg[deg > 0].mean()             # a few hubs hold most edges
    # unpermuted, the hubs are the lowest ids (all-A paths); permuted they spread
    assert np.median(hubs) > 1 << 9


def test_schedule_gives_every_seed_the_same_work():
    driver = load_module(CHIP / "drivers" / "open_loop.py", "test_open_loop")
    traffic = dict(json.loads((CHIP / "traffic" / "bi.json").read_text()),
                   mix={"bi1": 4, "bi2": 4, "bi3": 4, "bi4": 4, "bi5": 4,
                        "pagerank_knows": 1})
    a = driver.schedule(dict(traffic, rate_per_s=3.0), 11, 40)
    b = driver.schedule(dict(traffic, rate_per_s=3.0), 2**31 + 5, 40)
    assert len(a) == len(b) == 120
    key = lambda s: sorted((n, json.dumps(p, sort_keys=True)) for _, n, p in s)  # noqa: E731
    assert key(a) == key(b)
    gaps = lambda s: sorted(np.diff([0.0] + [t for t, _, _ in s])) # noqa: E731
    assert np.allclose(gaps(a), 1 / 3.0) and np.allclose(gaps(b), 1 / 3.0)
    assert [n for _, n, _ in a] != [n for _, n, _ in b]
    block = sum(traffic["mix"].values())
    c = driver.schedule(dict(traffic, rate_per_s=4.2), 3, 40)   # 8 whole mixes
    for i in range(0, len(c), block):               # each run of 21 is one mix
        names = [n for _, n, _ in c[i:i + block]]
        assert {k: names.count(k) for k in traffic["mix"]} == traffic["mix"]


def test_bi_window_holds_one_pagerank_over_knows():
    driver = load_module(CHIP / "drivers" / "open_loop.py", "test_open_loop_bi")
    traffic = json.loads((CHIP / "traffic" / "bi.json").read_text())
    seconds = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())["run_seconds"]
    for seed in (1, 2**31 + 9):
        names = [n for _, n, _ in driver.schedule(traffic, seed, seconds)]
        assert names.count("pagerank_knows") == 1
        assert {n for n in names} == set(traffic["mix"])


def test_open_loop_reference_is_named_by_the_traffic():
    driver = load_module(CHIP / "drivers" / "open_loop.py", "test_open_loop_refs")
    bi = json.loads((CHIP / "traffic" / "bi.json").read_text())
    assert "reference" not in bi
    assert Path(driver.reference_module(CHIP, bi).__file__) == CHIP / "refs" / "ldbc_queries.py"
    named = driver.reference_module(CHIP, dict(bi, reference="pagerank"))
    assert Path(named.__file__) == CHIP / "refs" / "pagerank.py"
    with pytest.raises(BenchError, match="no_such_ref"):
        driver.reference_module(CHIP, dict(bi, reference="no_such_ref"))


def test_ldbc_compact_is_the_answer_without_its_frames(tmp_path):
    """``ldbc_queries.compact`` keeps, field for field, what the open-loop
    driver kept of an installed template's answer before the reference module
    decided it (the driver's former body, below)."""
    import dataclasses

    from refs.ldbc_queries import compact
    from repro.core.engine import GraphLakeEngine
    from repro.gsql.session import GraphSession
    from repro.lakehouse.objectstore import ObjectStore, StoreConfig

    gen = _gen("ldbc")
    store = ObjectStore(StoreConfig(root=str(tmp_path / "lake")))
    gen.write(gen.generate(LDBC_TINY, 4), store, LDBC_TINY)
    engine = GraphLakeEngine(store, gen.graph_schema())
    try:
        engine.startup()
        session = GraphSession.for_engine(engine)
        session.install("bi1", json.loads((CHIP / "traffic" / "bi.json").read_text())
                        ["queries"]["bi1"])
        value = session.query("bi1", tag="Music", date=20090101)
    finally:
        engine.close()
    assert value.frames and value.n_edges_scanned > 0
    want = value.__class__(vset=value.vset, accumulators=value.accumulators,
                           n_edges_scanned=value.n_edges_scanned, frames=[],
                           alias_sets=value.alias_sets)
    got = compact(value)
    assert type(got) is type(want)
    for f in dataclasses.fields(want):
        a, b = getattr(got, f.name), getattr(want, f.name)
        assert a is b or a == b, f.name


def test_serving_refuses_a_reference_without_compact(tmp_path):
    from types import SimpleNamespace

    driver = load_module(CHIP / "drivers" / "open_loop.py", "test_open_loop_api")
    (tmp_path / "refs").mkdir()
    (tmp_path / "refs" / "no_compact.py").write_text(
        "def reference(tables, dtype=None): pass\n"
        "def program_answer(kept, raw_of_dense, want): pass\n"
        "def same_answer(a, b): pass\n")
    ctx = SimpleNamespace(cell=SimpleNamespace(chip_dir=tmp_path), config={},
                          traffic={"reference": "no_compact"})
    with pytest.raises(BenchError, match="lacks compact$"):
        driver.start(ctx)


# -- the controls fail ------------------------------------------------------------

def test_float32_accumulators_change_a_bi_answer():
    from refs.ldbc_queries import LDBCReference, same_answer

    cfg = dict(LDBC_TINY, persons=2000, comments=100000, tags=800)
    tables = _gen("ldbc").generate(cfg, 4)
    f64, f32 = LDBCReference(tables), LDBCReference(tables, np.float32)
    assert same_answer(f64.bi1("Music", 20090101), f32.bi1("Music", 20090101))
    assert not same_answer(f64.bi3(500), f32.bi3(500))   # sums pass 2**24


def test_bfloat16_ranks_miss_the_limit_and_float32_ranks_meet_it():
    from refs.pagerank import lower_precision, max_rel_err, pagerank_f64

    traffic = json.loads((CHIP / "traffic" / "pagerank.json").read_text())
    g = _gen("graph500").generate(dict(G500_TINY, scale=12), 9)
    ids, t = g["Node"]["id"], g["Node_Edge_Node"]
    s, d = np.searchsorted(ids, t["src"]), np.searchsorted(ids, t["dst"])
    n, k, damping = len(ids), traffic["supersteps"], traffic["damping"]
    want = pagerank_f64(s, d, n, damping, k)
    limit = traffic["limits"]["rank_max_rel_err"]
    assert max_rel_err(lower_precision(s, d, n, damping, k, "float32"), want) < limit
    assert max_rel_err(lower_precision(s, d, n, damping, k, "bfloat16"), want) > limit
