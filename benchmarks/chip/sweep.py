"""Find an open-loop cell's knee on the chip and write 4/5 of it as its rate.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> [--write]

One process, one set-up (the cell's own: lake, engine, server, warm-up);
then each rate of the traffic file's ``sweep.rates_per_s`` in turn, for
``sweep.seconds`` each, through the same open-loop sender the benchmark
uses.  A rate is sustained when every request is answered, the p95 latency
is under ``sweep.p95_limit_ms``, and the backlog does not grow: the last
third of the requests waits no more than twice as long as the first third.
The knee is the highest sustained rate below the first that is not.  With
``--write`` the traffic file's ``rate_per_s`` becomes 4/5 of the knee.  The
last line of standard output is the sweep as JSON.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


def sustained(records: list, p95_ms: float, limit_ms: float) -> bool:
    lat = [r["latency_s"] if r else float("inf") for r in records]
    third = max(1, len(lat) // 3)
    first, last = sum(lat[:third]) / third, sum(lat[-third:]) / third
    return p95_ms <= limit_ms and last <= 2 * first


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(CHIP_DIR))

    from harness import WORK_REL, Context, device_info, find_cell, load_module
    from repro.launch.compile_cache import enable_compile_cache

    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    cell = find_cell(ROOT, args.workload)
    device = device_info(int(cell.workload["chips"]), True)
    enable_compile_cache()
    sweep = cell.traffic["sweep"]
    work = ROOT / WORK_REL / cell.workload["config"]
    work.mkdir(parents=True, exist_ok=True)
    ctx = Context(cell, args.seed, sweep["seconds"], False, work, T_START, log)
    driver = load_module(CHIP_DIR / "drivers" / f"{cell.traffic['driver']}.py", "driver")
    serving = driver.start(ctx)
    rows, knee = [], None
    try:
        for rate in sweep["rates_per_s"]:
            traffic = dict(cell.traffic, rate_per_s=rate)
            plan = driver.schedule(traffic, args.seed, sweep["seconds"])
            t0 = time.perf_counter()
            records, lateness = driver.send(serving, ctx, plan)
            wall = time.perf_counter() - t0
            p50, p95 = driver.latency_ms(records, 50), driver.latency_ms(records, 95)
            ok = sum(1 for r in records if r and r["ok"])
            good = ok == len(plan) and sustained(records, p95, sweep["p95_limit_ms"])
            rows.append({"rate_per_s": rate, "requests": len(plan), "answered": ok,
                         "answered_per_s": ok / wall, "p50_ms": p50, "p95_ms": p95,
                         "lateness_max_ms": max(lateness) * 1e3, "sustained": good})
            log(json.dumps(rows[-1]))
            if not good:
                break
            knee = rate
    finally:
        serving.close()
    out = {"workload": args.workload, "device": device, "rows": rows, "knee_per_s": knee}
    if knee is not None:
        out["rate_per_s"] = round(0.8 * knee, 3)
        if args.write:
            path = CHIP_DIR / "traffic" / f"{cell.workload['traffic']}.json"
            t = json.loads(path.read_text())
            t["rate_per_s"] = out["rate_per_s"]
            path.write_text(json.dumps(t, indent=2) + "\n")
    print(json.dumps(out), flush=True)
    return 0 if knee is not None else 1


if __name__ == "__main__":
    sys.exit(main())
