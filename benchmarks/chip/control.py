"""The control of a cell's check: the plain reference put in the program's
place and computed one precision lower, at the cell's own size.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3 \
        [--seconds <run_seconds>]

For each seed it makes the cell's data, takes the requests a run of that
seed sends (open-loop cells) or the jobs it runs (job cells), and reads each
number the cell compares, as the control would give it:

- open-loop answers from the traffic's reference (``refs/<reference>.py``,
  see ``drivers/open_loop.py``) with float32 accumulators, counted against
  the same reference in float64 (``answers_wrong_or_missing``);
- ranks from the power iteration in bfloat16, against float64
  (``rank_max_rel_err``), and, for scale, the same in float32.

A sound limit lies below every control reading.  The benchmark's own runs
never run this.  The last line of standard output is the readings as JSON.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


def _analytics_reading(tables, schema, a: dict, dtype: str) -> float:
    from refs.pagerank import lower_precision, max_rel_err, pagerank_tables

    et = schema.edge_types[a["edge_type"]]
    vt = schema.vertex_types[et.src_type].table
    ids, edges = tables[vt]["id"], tables[et.table]
    want = pagerank_tables(tables, et.table, vt, a["damping"], a["supersteps"])
    got = lower_precision(np.searchsorted(ids, edges["src"]),
                          np.searchsorted(ids, edges["dst"]), len(ids),
                          a["damping"], a["supersteps"], dtype)
    return max_rel_err(got, want)


def readings(cell, seed: int, seconds: float, load_module) -> dict:
    gen = load_module(cell.chip_dir / "generators" / f"{cell.config['generator']}.py",
                      "gen")
    tables, schema = gen.generate(cell.config, seed), gen.graph_schema()
    traffic = cell.traffic
    out = {"seed": seed}
    if traffic["driver"] == "open_loop":
        driver = load_module(cell.chip_dir / "drivers" / "open_loop.py", "driver")
        ref_module = driver.reference_module(cell.chip_dir, traffic)
        plan = driver.schedule(traffic, seed, seconds)
        f64 = ref_module.reference(tables)
        f32 = ref_module.reference(tables, np.float32)
        differs = {}
        for _, name, params in plan:
            key = (name, tuple(sorted(params.items())))
            if name in traffic["queries"] and key not in differs:
                differs[key] = not ref_module.same_answer(f64.answer(name, params),
                                                          f32.answer(name, params))
        out["answers_wrong_or_missing"] = sum(
            differs.get((n, tuple(sorted(p.items()))), False) for _, n, p in plan)
        out["requests"] = len(plan)
        analytics = list(traffic.get("analytics", {}).values())
    else:
        analytics = [traffic]
    for a in analytics:
        out["rank_max_rel_err.bfloat16"] = _analytics_reading(tables, schema, a, "bfloat16")
        out["rank_max_rel_err.float32"] = _analytics_reading(tables, schema, a, "float32")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(CHIP_DIR))
    from harness import device_info, find_cell, load_module, load_json

    cell = find_cell(ROOT, args.workload)
    device = device_info(int(cell.workload["chips"]), True)
    seconds = args.seconds or load_json(ROOT / "BENCHMARK.json")["run_seconds"]
    rows = []
    for seed in args.seeds:
        rows.append(readings(cell, seed, seconds, load_module))
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload, "device": device, "rows": rows}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
