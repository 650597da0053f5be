"""Unit tests of the program-span reduction (``program_trace.py``) and the
per-layer readers that read program spans, on the CPU."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import program_trace  # noqa: E402
import trace_reduce  # noqa: E402
from harness import SPAN_PREFIX, WORK_REL, breakdown, load_module  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
W0, W1 = "1 serve-worker-0", "2 serve-worker-1"

# two workers, a scheduler and a main thread; every time in ns
EV = {
    "window": [0, 1000],
    "spans": [
        ["serve.unit", 0, 500, W0, {"riders": 2, "rider_wait_s": 0.004}],
        ["query.hop", 50, 350, W0, {"rows_in": 10, "rows_out": 4}],
        ["predicate.V", 100, 200, W0, {"rows_in": 10, "rows_out": 4}],
        ["read.V", 200, 300, W0, {"rows": 4}],
        ["lake.decode", 220, 260, W0, {"rows": 4}],
        ["serve.unit", 100, 700, W1, {"riders": 1, "rider_wait_s": 0.002}],
        ["predicate.U", 150, 650, W1, {"rows_in": 5, "rows_out": 5}],
        ["serve.stall", 900, 900, "0 serve-scheduler", {"late_s": 0.25}],
        ["pagerank.upload", -100, 20, "3 python3", {"bytes": 8}],
        ["pagerank.upload", 710, 720, "3 python3", {"bytes": 264_000_000}],
    ],
    "bench_spans": [["request.bi1", 0, 900], ["request.bi2", 600, 900]],
    "devices": [[["%fusion.1 fusion", 0, 100, "pagerank.gather"],
                 ["%while.4 while", 720, 800, "pagerank.segment_sum"],
                 ["%fusion.11 fusion", 730, 790, "pagerank.segment_sum"],
                 ["%copy copy", 850, 870, ""]]],
}
NEW_READERS = ["predicate_share", "column_read_share", "worker_wait_ms", "host_stall_ms",
               "h2d_mb_per_superstep"]


def _layer(name):
    return load_module(CHIP / "layers" / f"{name}.py",
                       "test_program_layer_" + name.replace(".", "_"))


def _recorded_pagerank() -> dict:
    """The recorded chip fixture of the benchmark's own spans, in
    ``program_trace``'s form: no program spans, no scopes."""
    ev = json.loads((DATA / "pagerank_trace_events.json").read_text())
    window = next([s, e] for n, s, e in ev["spans"] if n == "window")
    return {"window": window, "spans": [],
            "bench_spans": [[n, s, e] for n, s, e in ev["spans"] if n != "window"],
            "devices": [[[op, s, e, ""] for op, s, e in ops] for ops in ev["devices"]]}


# -- reduction -----------------------------------------------------------------

def test_self_time_and_attribute_sums_per_thread():
    prog = program_trace.reduce(EV)["program"]
    unit = prog["serve.unit"]
    assert unit["count"] == 2 and unit["riders"] == 3
    assert unit["rider_wait_s"] == pytest.approx(0.006)
    assert unit["total_s"] == pytest.approx(1100e-9)
    assert unit["self_s"] == pytest.approx((500 - 300 + 600 - 500) * 1e-9)
    assert prog["query.hop"]["self_s"] == pytest.approx(100e-9)   # less predicate, read
    assert prog["read.V"]["self_s"] == pytest.approx(60e-9)       # less its decode
    assert prog["predicate.U"]["self_s"] == pytest.approx(500e-9)
    # an upload begun before the window: clipped time, no count, no bytes
    assert prog["pagerank.upload"]["count"] == 1
    assert prog["pagerank.upload"]["bytes"] == 264_000_000
    assert prog["pagerank.upload"]["total_s"] == pytest.approx(30e-9)


def test_idle_gap_splits_evenly_among_threads_then_falls_back():
    gaps = dict(program_trace.reduce(EV)["idle_gaps"])
    # [100, 720]: its middle lies in worker 0's unit and worker 1's predicate;
    # [800, 850]: no program span, the latest-begun benchmark span; [870, 1000]:
    # nothing
    assert gaps == pytest.approx({"serve.unit": 310e-9, "predicate.U": 310e-9,
                                  "request.bi2": 50e-9, "none": 130e-9})
    assert sum(gaps.values()) == pytest.approx(800e-9)   # the idle time


def test_device_scopes_cover_busy_time_once():
    scopes = dict(program_trace.reduce(EV)["device_scopes"])
    assert scopes == pytest.approx({"pagerank.gather": 100e-9,
                                    "pagerank.segment_sum": 80e-9,   # while holds its body
                                    "unscoped": 20e-9})


@pytest.mark.parametrize("path,scope", [
    ("jit(_pagerank_step_csr)/jit(main)/pagerank.segment_sum/while/body", "pagerank.segment_sum"),
    ("jit(f)/pagerank.gather/gather", "pagerank.gather"),
    ("jit(_pagerank_step_csr)/jit(main)/add", ""),
    ("", ""),
])
def test_outer_scope(path, scope):
    assert program_trace.outer_scope(path) == scope


def test_recorded_chip_trace_reduces_as_before():
    """The recorded PageRank fixture (benchmark spans only): the device numbers
    and the idle-gap names of ``trace_reduce`` stay as they were, and with no
    program span the program's reduction names each gap as it does."""
    ev = json.loads((DATA / "pagerank_trace_events.json").read_text())
    r = trace_reduce.reduce(ev, 1)
    assert r["window_s"] == pytest.approx(63.418441061, abs=1e-9)
    assert r["busy_s"] == pytest.approx(13.351873566, abs=1e-9)
    assert [n for n, _ in r["device_ops"][:2]] == ["%while.4 while", "%fusion.15 fusion"]
    assert [v for _, v in r["device_ops"][:2]] == pytest.approx([13.350928508, 12.091048031])
    assert dict(r["idle_gaps"]) == pytest.approx({"job.pagerank": 50.066567495})
    p = program_trace.reduce(_recorded_pagerank())
    assert p["program"] == {}
    assert dict(p["idle_gaps"]) == pytest.approx(dict(r["idle_gaps"]))
    assert dict(p["device_scopes"]) == pytest.approx({"unscoped": r["busy_s"]})


def test_for_run_finds_the_trace_of_the_window(tmp_path):
    import jax
    import jax.profiler

    trace_dir = tmp_path / WORK_REL / "some-config" / "trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + "window"):
        with jax.profiler.TraceAnnotation(program_trace.PROGRAM_PREFIX + "serve.unit",
                                          riders=1, rider_wait_s=0.5):
            pass
    jax.profiler.stop_trace()
    summary = trace_reduce.summarize(trace_dir, SPAN_PREFIX, 1)
    ev = program_trace.for_run({"trace": summary}, tmp_path)
    assert [s[0] for s in ev["spans"]] == ["serve.unit"]
    other = dict(summary, window_s=summary["window_s"] + 1.0)
    assert program_trace.for_run({"trace": other}, tmp_path) is None
    assert program_trace.for_run({"trace": None}, tmp_path) is None


# -- the readers ------------------------------------------------------------------

def test_program_span_readers(monkeypatch):
    monkeypatch.setattr(program_trace, "for_run", lambda obs, root: EV)
    obs = {"trace": {"window_s": 1e-6, "busy_s": 2e-7}, "supersteps": 2}
    # predicates: 100 ns on worker 0, 500 on worker 1, of 1100 ns of units
    assert _layer("predicate_share").read(obs) == pytest.approx(100 * 600 / 1100)
    assert _layer("column_read_share").read(obs) == pytest.approx(100 * 100 / 1100)
    assert _layer("worker_wait_ms").read(obs) == pytest.approx(2.0)
    assert _layer("host_stall_ms").read(obs) == pytest.approx(250.0)
    assert _layer("h2d_mb_per_superstep").read(obs) == pytest.approx(132.0)


def test_host_stall_reads_zero_when_no_stall_came(monkeypatch):
    ev = dict(EV, spans=[s for s in EV["spans"] if s[0] != "serve.stall"])
    monkeypatch.setattr(program_trace, "for_run", lambda obs, root: ev)
    assert _layer("host_stall_ms").read({"trace": {"window_s": 1e-6}}) == 0.0


@pytest.mark.parametrize("name", NEW_READERS)
def test_program_span_reader_with_nothing_to_read_returns_none(name, monkeypatch):
    empty = {"cache_delta": {"hits": 0, "misses": 0}, "trace": None,
             "device": {"kind": "TPU v5 lite"}}
    assert _layer(name).read(empty) is None
    # a traced run of a program without spans
    monkeypatch.setattr(program_trace, "for_run", lambda obs, root: _recorded_pagerank())
    assert _layer(name).read({"trace": {"window_s": 1.0}, "supersteps": 2}) is None


# -- recorded traced runs on one v5e -----------------------------------------------

def _recorded(name) -> dict:
    """Events of a ``--trace 1`` run on one v5e (``program_trace.py --events
    --cut-s``): the window's first seconds and what overlaps them."""
    return json.loads((DATA / f"{name}_program_events.json").read_text())


def test_recorded_bi_run_names_its_idle_time_by_program_spans(monkeypatch):
    """ldbc-sf1.bi, seed 2147483801, the window's first 16.7 s (the PageRank
    over Knows runs from 16.61 s on)."""
    ev = _recorded("bi")
    r = program_trace.reduce(ev)
    window = (ev["window"][1] - ev["window"][0]) / 1e9
    busy = sum(v for _, v in r["device_scopes"])
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(window - busy)
    bench = {n for n, _, _ in ev["bench_spans"]} | {"none"}
    named = sum(v for n, v in gaps.items() if n not in bench)
    assert named >= 0.9 * (window - busy)
    assert {"serve.unit", "query.hop", "read.E", "predicate.V"} <= set(r["program"])
    monkeypatch.setattr(program_trace, "for_run", lambda obs, root: ev)
    obs = {"trace": {"window_s": window}}
    for name in ("predicate_share", "column_read_share"):
        assert 0 < _layer(name).read(obs) < 100
    assert _layer("worker_wait_ms").read(obs) > 0
    assert _layer("host_stall_ms").read(obs) >= 0


def test_recorded_pagerank_run_scopes_its_busy_time(monkeypatch):
    """graph500-22.pagerank, seed 2147483811, the window's first 24 s: one
    upload and two supersteps."""
    ev = _recorded("pagerank")
    r = program_trace.reduce(ev)
    scopes = dict(r["device_scopes"])
    busy = sum(scopes.values())
    assert sum(v for n, v in scopes.items() if n.startswith("pagerank.")) >= 0.95 * busy
    assert max(scopes, key=scopes.get) == "pagerank.segment_sum"
    gaps = dict(r["idle_gaps"])
    assert max(gaps, key=gaps.get) == "pagerank.upload"
    monkeypatch.setattr(program_trace, "for_run", lambda obs, root: ev)
    mb = _layer("h2d_mb_per_superstep").read({"trace": {"window_s": 24.0}, "supersteps": 2})
    assert mb == pytest.approx((63539332 * 4 + 1244954 * 4 + 1244953 * 4) / 2 / 1e6)


# -- the result line's breakdown ----------------------------------------------------

def _benchmark_form(ev: dict) -> dict:
    """Recorded events as ``trace_reduce`` reads a trace: benchmark spans only."""
    return {"spans": [["window", *ev["window"]]] + ev["bench_spans"],
            "devices": [[op[:3] for op in ops] for ops in ev["devices"]]}


@pytest.mark.parametrize("name", ["bi", "pagerank"])
def test_breakdown_names_idle_gaps_by_program_spans(name):
    ev = _recorded(name)
    summary = trace_reduce.reduce(_benchmark_form(ev), 1)
    program = program_trace.reduce(ev)
    b = breakdown(summary, program)
    assert list(b) == ["device_ops", "idle_gaps"]
    assert b["device_ops"] == summary["device_ops"]
    assert b["idle_gaps"] == program["idle_gaps"] != summary["idle_gaps"]
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        sum(v for _, v in summary["idle_gaps"]))
    assert b["idle_gaps"][0][0] in program["program"]


def test_breakdown_without_program_spans_names_gaps_by_benchmark_spans():
    summary = trace_reduce.reduce(json.loads((DATA / "pagerank_trace_events.json").read_text()), 1)
    b = breakdown(summary, program_trace.reduce(_recorded_pagerank()))
    assert b == {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}
    assert [n for n, _ in b["idle_gaps"]] == ["job.pagerank"]
