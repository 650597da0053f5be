"""Whole runs of the chip benchmark's harness on the CPU, at tiny sizes.

A throwaway benchmark is made in a temporary checkout from new files only (a
configuration, a traffic mix and a ``BENCHMARK.json`` entry per cell) beside
a copy of the harness's code, which no test edits.  The runs skip the
harness's look for a chip; everything else is a real run.  The fault tests
break the timed path underneath and must see ``correct`` come out false.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

from harness import run_cell  # noqa: E402


def _tiny_root(tmp: Path) -> Path:
    root = tmp / "checkout"
    shutil.copytree(CHIP, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    chip = root / "benchmarks" / "chip"
    spec = json.loads((REPO / "BENCHMARK.json").read_text())

    ldbc = json.loads((chip / "configs" / "ldbc-snb-sf1.json").read_text())
    ldbc.update(name="tiny-ldbc", persons=200, comments=20000, tags=160,
                row_group_rows=2048)
    g500 = json.loads((chip / "configs" / "graph500-22.json").read_text())
    g500.update(name="tiny-g500", scale=10, row_group_rows=4096)
    bi = json.loads((chip / "traffic" / "bi.json").read_text())
    bi["rate_per_s"] = 20.0
    bi["mix"] = {"bi1": 4, "bi2": 4, "bi3": 4, "bi4": 4, "bi5": 4, "pagerank_knows": 1}
    pr = json.loads((chip / "traffic" / "pagerank.json").read_text())
    for name, body in (("configs/tiny-ldbc.json", ldbc), ("configs/tiny-g500.json", g500),
                       ("traffic/tiny-bi.json", bi), ("traffic/tiny-pagerank.json", pr)):
        (chip / name).write_text(json.dumps(body))

    rename = {"ldbc-sf1.bi": "tiny.bi", "graph500-22.pagerank": "tiny.pagerank"}
    spec["configs"] = [
        {"name": "tiny-ldbc", "source": "test", "file": "benchmarks/chip/configs/tiny-ldbc.json",
         "reduced": [], "why": "test"},
        {"name": "tiny-g500", "source": "test", "file": "benchmarks/chip/configs/tiny-g500.json",
         "reduced": [], "why": "test"}]
    spec["workloads"] = [
        {"name": "tiny.bi", "config": "tiny-ldbc", "traffic": "tiny-bi", "chips": 1, "why": "test"},
        {"name": "tiny.pagerank", "config": "tiny-g500", "traffic": "tiny-pagerank",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("chipbench"))


def _run(root, workload, trace=False, seed=2**31 + 11, seconds=1.5):
    return run_cell(workload, seed, seconds, trace, root, time.perf_counter(),
                    on_chip=False, log=lambda m: None)


def _line_contract(out: dict, spec: dict, workload: str, trace: bool):
    keys = list(out)
    assert keys[:3] == ["correct", "attempted", "failed"] and keys[-1] == "checks"
    assert {"metrics", "device"} <= set(keys)
    json.loads(json.dumps(out))
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]}
    if trace:
        assert set(out["metrics"]) <= want
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("workload", ["tiny.bi", "tiny.pagerank"])
def test_throwaway_cell_runs_correct(root, workload):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    out = _run(root, workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    _line_contract(out, spec, workload, trace=False)


def test_traced_run_reports_per_layer_metrics(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    out = _run(root, "tiny.bi", trace=True)
    assert out["correct"], out["checks"]
    _line_contract(out, spec, "tiny.bi", trace=True)
    assert {"queue_wait_ms", "service_p50_ms", "edge_list_build_s"} <= set(out["metrics"])


def test_answer_altered_is_not_correct(root, monkeypatch):
    import repro.gsql.session as session

    def altered(fn):
        def run(*a, **k):
            res = fn(*a, **k)
            for r in (res if isinstance(res, list) else [res]):
                r.n_edges_scanned += 1
            return res
        return run

    monkeypatch.setattr(session, "execute_compiled", altered(session.execute_compiled))
    monkeypatch.setattr(session, "execute_compiled_batch",
                        altered(session.execute_compiled_batch))
    out = _run(root, "tiny.bi", seed=5)
    assert not out["correct"]
    assert out["checks"]["answers_wrong_or_missing"]["value"] > 0


@pytest.mark.parametrize("workload", ["tiny.bi", "tiny.pagerank"])
def test_step_returning_its_state_is_not_correct(root, monkeypatch, workload):
    from repro.core import algorithms

    monkeypatch.setattr(algorithms, "_pagerank_step_csr",
                        lambda rank, *a, **k: rank)
    monkeypatch.setattr(algorithms, "_pagerank_step", lambda rank, *a, **k: rank)
    out = _run(root, workload, seed=6)
    assert not out["correct"]
    assert out["checks"]["rank_max_rel_err"]["value"] > \
        out["checks"]["rank_max_rel_err"]["limit"]


def test_ranks_altered_is_not_correct(root, monkeypatch):
    from repro.core import algorithms

    real = algorithms.pagerank

    def altered(*a, **k):
        r = real(*a, **k).copy()
        r[len(r) // 2] *= 1.05
        return r

    monkeypatch.setattr(algorithms, "pagerank", altered)
    out = _run(root, "tiny.pagerank", seed=7)
    assert not out["correct"]


def test_cli_without_a_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload", "ldbc-sf1.bi",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_cli_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "ldbc-sf1.bi",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
