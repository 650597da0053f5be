"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration and its
traffic are found by name from ``BENCHMARK.json`` (see ``harness.py``).  The
last line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``: each number compared for ``correct`` beside its limit.  The
same numbers are the last lines of standard error.

It exits non-zero and prints no result when JAX finds no TPU, or fewer chips
than the cell asks for, or when anything in the run fails.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(CHIP_DIR))
    try:
        from harness import run_cell

        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                       ROOT, T_START)
    except Exception as e:  # noqa: BLE001 - every failure ends the run typed
        traceback.print_exc()
        print(f"run FAILED: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
