"""The chip benchmark's harness: finds a cell's files by name and runs it once.

Everything that belongs to one configuration, traffic mix or per-layer metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: sizes, engine settings, guarantees; its
  ``generator`` key names ``generators/<generator>.py``;
- ``traffic/<traffic>.json``: the mix; its ``driver`` key names
  ``drivers/<driver>.py``, which runs set-up, the window and the check;
- ``refs/<reference>.py``: the plain reference of an open-loop traffic's
  templates, named by the traffic file's ``reference`` key (``ldbc_queries``
  where it has none).  It gives ``reference``, ``compact``,
  ``program_answer`` and ``same_answer`` (``drivers/open_loop.py``,
  ``reference_module``); ``compact(result)`` is what the check needs of one
  answer, whichever route served it (full engine, shared scan, or the
  point-lookup tier), and the window keeps nothing else;
- ``layers/<metric>.py``: one reader per per-layer metric.

A reference's ``same_answer`` compares ``n_edges_scanned``, which the
program promises bit-identical on every route (``core/lookup.py``): the
tests' answer fault alters that field on each route an installed template
can take, and has to see ``correct`` come out false.  The control
(``control.py``) runs the reference with ``dtype=np.float32``.  For a
template with no float accumulator, that ``dtype`` applies to what the
template orders or compares by, so that the control still reads above the
exact limit of 0: ``creationDate`` values over 2**24 held as float32 merge
ties and move ``>`` and ``<=`` boundaries, which reorders a top-k or lets
one more row through a date filter.

A driver reports raw observations; this module picks the cell's end-to-end
metrics (``--trace 0``) or per-layer metrics (``--trace 1``) from them and
prints the result line.  Nothing here is specific to one cell.

A cell is added with new files and additions to ``BENCHMARK.json`` alone: its
entries under ``configs``, ``workloads`` and the metrics, and its name
appended to the ``workloads`` list of each metric it reports.  Its CPU twin,
which the tests run, is ``tests/tiny/configs/<config>.json`` (required) and
``tests/tiny/traffic/<traffic>.json`` (optional): top-level keys that replace
those of the configuration and traffic files.  Only the tests read them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

CHIP_REL = Path("benchmarks") / "chip"
WORK_REL = Path(".smoke_data") / "chipbench"
SPAN_PREFIX = "chipbench."


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a bad spec, a crash)."""


def load_module(path: Path, name: str):
    """Import one benchmark file by path (names may hold dots or dashes)."""
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    root: Path
    spec: dict
    workload: dict
    config: dict           # the configuration file's contents
    traffic: dict          # the traffic file's contents

    @property
    def chip_dir(self) -> Path:
        return self.root / CHIP_REL

    def applies(self, metric: dict) -> bool:
        names = metric.get("workloads")
        return names is None or self.workload["name"] in names


def find_cell(root: Path, workload: str) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / CHIP_REL / "traffic" / f"{w['traffic']}.json")
    return Cell(root, spec, w, config, traffic)


def device_info(chips: int, require_tpu: bool) -> dict:
    """The devices as JAX reports them.  A measurement run needs TPUs, at
    least as many as the cell asks for; it never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if require_tpu:
        if jax.default_backend() != "tpu":
            raise BenchError(f"JAX backend is {jax.default_backend()!r}, not a TPU")
        if len(devs) < chips:
            raise BenchError(f"{len(devs)} chips visible, the cell needs {chips}")
    devs = devs[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts XLA backend compilations (a persistent-cache hit is none)."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self._armed = False

        def on_event(event: str, duration: float, **_):
            if self._armed and "backend_compile" in event:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def arm(self) -> None:
        self._armed = True

    def disarm(self) -> None:
        self._armed = False


class Context:
    """What a driver gets: the cell, the seed, a work directory, set-up and
    window clocks, host spans, and the chip's memory peak."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 work: Path, t_start: float, log: Callable[[str], None]):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.config, self.traffic = cell.config, cell.traffic
        self.work, self.t_start, self.log = work, t_start, log
        self.chips = int(cell.workload["chips"])
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.memory_peak: Optional[int] = None
        self.trace_dir = work / "trace"
        self.compiles = CompileCounter()
        self.window_compiles = 0

    # -- data -----------------------------------------------------------------

    def generator(self):
        name = self.config["generator"]
        return load_module(self.cell.chip_dir / "generators" / f"{name}.py",
                           f"chipbench_gen_{name}")

    def lake_dir(self) -> Path:
        """The lake's directory, emptied: one lake per configuration."""
        d = self.work / "lake"
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True)
        return d

    def make_engine(self, store, schema):
        from repro.core.cache.manager import CacheConfig
        from repro.core.engine import GraphLakeEngine

        e = self.config["engine"]
        return GraphLakeEngine(
            store, schema,
            cache_config=CacheConfig(memory_budget_bytes=e["cache_memory_bytes"],
                                     disk_budget_bytes=e["cache_disk_bytes"]),
            n_io_threads=e["io_threads"], enable_prefetch=e["prefetch"],
            materialize_topology=e["materialize_topology"])

    # -- clocks and spans -------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span in the profiler's trace (recorded when tracing); the
        set-up's spans are logged with their length."""
        import jax.profiler

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        if name.startswith("setup."):
            self.log(f"{name} {time.perf_counter() - t0:.3f} s")

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self.log(f"set-up done in {self.setup_s:.3f} s")

    @contextlib.contextmanager
    def window(self):
        """The measured window: no compilation may happen inside it; with
        ``trace`` the profiler records it."""
        import jax.profiler

        if self.setup_s is None:
            self.setup_done()
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self.compiles.count = 0
        self.compiles.arm()
        t0 = time.perf_counter()
        try:
            with self.span("window"):
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            self.compiles.disarm()
            self.window_compiles = self.compiles.count
            if self.trace:
                jax.profiler.stop_trace()
        self.log(f"window closed after {self.window_s:.3f} s, "
                 f"{self.window_compiles} compilations inside it")

    def read_memory(self) -> None:
        self.memory_peak = memory_peak_bytes(self.chips)


def _fmt(v) -> str:
    return repr(v) if isinstance(v, float) else str(v)


def breakdown(summary: dict, program: dict) -> dict:
    """The result line's ``breakdown``: the device operations that took most
    time, and the idle gaps named by the program's spans
    (``program_trace.reduce``) where the window holds any, else by the
    benchmark's own (``trace_reduce``)."""
    gaps = program["idle_gaps"] if program["program"] else summary["idle_gaps"]
    return {"device_ops": summary["device_ops"], "idle_gaps": gaps}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path, t_start: float, on_chip: bool = True,
             log: Callable[[str], None] = None) -> dict:
    """Run one cell once and return the result line's object.

    ``on_chip=False`` is for tests on the CPU only: it skips the look for a
    TPU and leaves JAX's persistent compilation cache alone."""
    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    cell = find_cell(root, workload)
    device = device_info(int(cell.workload["chips"]), on_chip)
    if on_chip:
        from repro.launch.compile_cache import enable_compile_cache

        log(f"compile cache {enable_compile_cache()}")
    log(f"{workload} seed {seed} on {device}")
    work = root / WORK_REL / cell.workload["config"]
    work.mkdir(parents=True, exist_ok=True)
    ctx = Context(cell, seed, seconds, trace, work, t_start, log)
    driver = load_module(cell.chip_dir / "drivers" / f"{cell.traffic['driver']}.py",
                         f"chipbench_driver_{cell.traffic['driver']}")
    obs = driver.run(ctx)

    checks = dict(obs["checks"])
    checks["compilations_in_window"] = {"value": ctx.window_compiles, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    raw = dict(obs["metrics"])
    raw["setup_s"] = ctx.setup_s
    device["memory_peak_bytes"] = ctx.memory_peak or 0
    out = {"correct": correct, "attempted": obs["attempted"],
           "failed": obs["failed"]}

    summary = None
    if trace:
        from trace_reduce import summarize

        summary = summarize(ctx.trace_dir, SPAN_PREFIX, ctx.chips)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in cell.spec[kind]:
        if not cell.applies(m):
            continue
        if trace:
            reader = load_module(cell.chip_dir / "layers" / f"{m['name']}.py",
                                 "chipbench_layer_" + m["name"].replace(".", "_"))
            value = reader.read({**obs["observations"], "trace": summary,
                                 "device": device})
        else:
            value = raw.get(m["name"])
        if value is None:
            if trace:
                continue
            raise BenchError(f"the driver reported no {m['name']}")
        if not math.isfinite(value):
            raise BenchError(f"{m['name']} is {value}")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if summary is not None:
        import program_trace

        program = program_trace.reduce(
            program_trace.load(program_trace.newest_trace(ctx.trace_dir)), ctx.chips)
        out["breakdown"] = breakdown(summary, program)
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {_fmt(c['value'])} (limit {_fmt(c['limit'])})")
    return out
