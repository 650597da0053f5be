"""Whole runs of the chip benchmark's harness on the CPU, at tiny sizes.

Every cell of ``BENCHMARK.json`` has a CPU twin: a throwaway checkout holds a
copy of the benchmark in which each configuration file is merged with
``tiny/configs/<config>.json`` and each traffic file with
``tiny/traffic/<traffic>.json`` where there is one (top-level keys replace).
Cells keep their names, so every metric's ``workloads`` list holds as it is,
and the tests take the cells from ``BENCHMARK.json``: a cell added with new
files and appended names is run with no edit here.  The runs skip the
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
TINY = Path(__file__).resolve().parent / "tiny"
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(CHIP))

import control  # noqa: E402
from harness import CHIP_REL, find_cell, load_module, run_cell  # noqa: E402

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())


def _traffic(workload: dict) -> dict:
    return json.loads((CHIP / "traffic" / f"{workload['traffic']}.json").read_text())


def _cells(keep=lambda traffic: True) -> list:
    return [w["name"] for w in SPEC["workloads"] if keep(_traffic(w))]


CELLS = _cells()
OPEN_LOOP_CELLS = _cells(lambda t: t["driver"] == "open_loop")
JOB_CELLS = _cells(lambda t: t["driver"] == "jobs")
# cells whose window runs a PageRank: a job cell, or analytics beside serving
PAGERANK_CELLS = _cells(lambda t: t.get("algorithm") == "pagerank" or any(
    a["algorithm"] == "pagerank" for a in t.get("analytics", {}).values()))


def _merge(path: Path, overrides: Path, into: Path) -> None:
    body = json.loads(path.read_text())
    body.update(json.loads(overrides.read_text()))
    into.parent.mkdir(parents=True, exist_ok=True)
    into.write_text(json.dumps(body))


def _tiny_root(tmp: Path, src: Path = REPO) -> Path:
    """A checkout under ``tmp`` holding the CPU twin of every cell of the
    benchmark at ``src`` (a checkout's root); ``src`` is left as it was."""
    root = tmp / "checkout"
    shutil.copytree(src / CHIP_REL, root / CHIP_REL,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(src / "BENCHMARK.json", root)
    spec = json.loads((src / "BENCHMARK.json").read_text())
    tiny = src / CHIP_REL / TINY.relative_to(CHIP)
    for c in spec["configs"]:
        overrides = tiny / "configs" / f"{c['name']}.json"
        if not overrides.is_file():
            pytest.fail(f"configuration {c['name']!r} has no CPU twin: add "
                        f"{overrides.relative_to(src)}, the keys of {c['file']} that make "
                        f"it small enough for the CPU", pytrace=False)
        _merge(src / c["file"], overrides, root / c["file"])
    for name in {w["traffic"] for w in spec["workloads"]}:
        overrides = tiny / "traffic" / f"{name}.json"
        if overrides.is_file():
            rel = CHIP_REL / "traffic" / f"{name}.json"
            _merge(src / rel, overrides, root / rel)
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
    want = _applies(spec, "per_layer" if trace else "end_to_end", workload)
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


def _applies(spec: dict, kind: str, workload: str) -> set:
    return {m["name"] for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]}


@pytest.mark.parametrize("workload", CELLS)
def test_throwaway_cell_runs_correct(root, workload):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    out = _run(root, workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    _line_contract(out, spec, workload, trace=False)


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_per_layer_metrics(root, workload):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    out = _run(root, workload, trace=True)
    assert out["correct"], out["checks"]
    _line_contract(out, spec, workload, trace=True)
    program_read = {"queue_wait_ms", "service_p50_ms", "edge_list_build_s"}
    assert program_read & _applies(spec, "per_layer", workload) <= set(out["metrics"])


def _alter_answers(monkeypatch) -> None:
    """Every answer of an installed template one edge off where it is made, on
    each route the session module sends it: the full engine, the shared scan
    and the point-lookup tier."""
    import repro.gsql.session as session

    def altered(fn):
        def run(*a, **k):
            res = fn(*a, **k)
            for r in (res if isinstance(res, list) else [res]):
                r.n_edges_scanned += 1
            return res
        return run

    for name in ("execute_compiled", "execute_compiled_batch", "execute_lookup"):
        monkeypatch.setattr(session, name, altered(getattr(session, name)))


@pytest.mark.parametrize("workload", OPEN_LOOP_CELLS)
def test_answer_altered_is_not_correct(root, monkeypatch, workload):
    _alter_answers(monkeypatch)
    out = _run(root, workload, seed=5)
    assert not out["correct"]
    assert out["checks"]["answers_wrong_or_missing"]["value"] > 0


@pytest.mark.parametrize("workload", PAGERANK_CELLS)
def test_step_returning_its_state_is_not_correct(root, monkeypatch, workload):
    from repro.core import algorithms

    monkeypatch.setattr(algorithms, "_pagerank_step_csr",
                        lambda rank, *a, **k: rank)
    monkeypatch.setattr(algorithms, "_pagerank_step", lambda rank, *a, **k: rank)
    out = _run(root, workload, seed=6)
    assert not out["correct"]
    assert out["checks"]["rank_max_rel_err"]["value"] > \
        out["checks"]["rank_max_rel_err"]["limit"]


@pytest.mark.parametrize("workload", JOB_CELLS)
def test_ranks_altered_is_not_correct(root, monkeypatch, workload):
    from repro.core import algorithms

    real = algorithms.pagerank

    def altered(*a, **k):
        r = real(*a, **k).copy()
        r[len(r) // 2] *= 1.05
        return r

    monkeypatch.setattr(algorithms, "pagerank", altered)
    out = _run(root, workload, seed=7)
    assert not out["correct"]


# -- a cell from new files alone ---------------------------------------------------

def _add_cell_from_new_files(src: Path, cell: str = "extra.bi",
                             traffic: str = "extra-bi", refs: tuple = ()) -> None:
    """What a later change adds for a cell: a configuration (a copy of the
    LDBC one), its CPU twin, a traffic mix and the ``refs`` modules it names
    as new files, entries appended to ``BENCHMARK.json``, and the cell's name
    appended to the lists of the metrics it reports.  ``extra-bi`` is the BI
    mix; any other ``traffic`` is ``tests/data/<traffic>.json``."""
    chip = src / CHIP_REL
    shutil.copy(chip / "configs" / "ldbc-snb-sf1.json", chip / "configs" / "extra-ldbc.json")
    shutil.copy(chip / "tests" / "tiny" / "configs" / "ldbc-snb-sf1.json",
                chip / "tests" / "tiny" / "configs" / "extra-ldbc.json")
    if traffic == "extra-bi":
        body = dict(json.loads((chip / "traffic" / "bi.json").read_text()),
                    reference="ldbc_queries")
    else:
        body = json.loads((DATA / f"{traffic}.json").read_text())
    (chip / "traffic" / f"{traffic}.json").write_text(json.dumps(body))
    for name in refs:
        shutil.copy(DATA / f"{name}.py", chip / "refs" / f"{name}.py")
    spec = json.loads((src / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "extra-ldbc", "source": "test",
                            "file": "benchmarks/chip/configs/extra-ldbc.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "extra-ldbc",
                              "traffic": traffic, "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("query_p50_ms", "queue_wait_ms"):
            m["workloads"].append(cell)
    (src / "BENCHMARK.json").write_text(json.dumps(spec, indent=2))


def _only_appended(old, new) -> bool:
    """``new`` is ``old`` with entries appended to its lists, and no other change."""
    if isinstance(old, dict):
        return (isinstance(new, dict) and old.keys() == new.keys()
                and all(_only_appended(old[k], new[k]) for k in old))
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(_only_appended(a, b) for a, b in zip(old, new)))
    return old == new


def _files(src: Path) -> dict:
    return {p.relative_to(src): p.read_bytes() for p in sorted(src.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def _later_checkout(tmp: Path) -> Path:
    src = tmp / "later"
    shutil.copytree(CHIP, src / CHIP_REL, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", src)
    return src


def _assert_only_added(before: dict, after: dict) -> None:
    """No file of ``before`` changed, but for entries appended to
    ``BENCHMARK.json``."""
    spec_rel = Path("BENCHMARK.json")
    assert all(after[rel] == data for rel, data in before.items() if rel != spec_rel)
    assert _only_appended(json.loads(before[spec_rel]), json.loads(after[spec_rel]))


def test_a_cell_from_new_files_alone_runs_correct_with_its_control(tmp_path):
    src = _later_checkout(tmp_path)
    before = _files(src)
    _add_cell_from_new_files(src)
    root = _tiny_root(tmp_path, src)
    _assert_only_added(before, _files(src))

    spec = json.loads((root / "BENCHMARK.json").read_text())
    seed = 2**31 + 23
    out = _run(root, "extra.bi", seed=seed)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    _line_contract(out, spec, "extra.bi", trace=False)
    assert "query_p50_ms" in out["metrics"]
    r = control.readings(find_cell(root, "extra.bi"), seed, 1.5, load_module)
    assert r["seed"] == seed and r["requests"] == out["attempted"]
    assert r["answers_wrong_or_missing"] >= 0
    assert r["rank_max_rel_err.float32"] < r["rank_max_rel_err.bfloat16"]


def test_a_lookup_routed_cell_from_new_files_alone_is_checked_on_its_route(
        tmp_path, monkeypatch):
    """A cell whose templates the server sends through the point-lookup tier:
    its reference's ``compact`` keeps what ``ldbc_queries.compact`` drops (the
    route), every answer reaches the check from that tier, and an answer
    altered there is caught."""
    src = _later_checkout(tmp_path)
    before = _files(src)
    _add_cell_from_new_files(src, "extra.point", "extra-point", refs=("point_ref",))
    root = _tiny_root(tmp_path, src)
    _assert_only_added(before, _files(src))

    spec = json.loads((root / "BENCHMARK.json").read_text())
    out = _run(root, "extra.point", seed=2**31 + 29)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    _line_contract(out, spec, "extra.point", trace=False)
    routes = sys.modules["chipbench_ref_point_ref"].ROUTES
    assert routes == ["lookup"] * out["attempted"]

    _alter_answers(monkeypatch)
    out = _run(root, "extra.point", seed=2**31 + 31)
    assert not out["correct"]
    assert out["checks"]["answers_wrong_or_missing"]["value"] > 0


def test_a_config_without_a_cpu_twin_fails_naming_the_file(tmp_path):
    src = _later_checkout(tmp_path)
    _add_cell_from_new_files(src)
    (src / CHIP_REL / "tests" / "tiny" / "configs" / "extra-ldbc.json").unlink()
    with pytest.raises(pytest.fail.Exception,
                       match="add benchmarks/chip/tests/tiny/configs/extra-ldbc.json"):
        _tiny_root(tmp_path, src)


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
