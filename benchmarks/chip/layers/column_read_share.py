"""Executor and read path: the share of the workers' request time spent
materializing columns (percent).

The time of the program's ``read.*`` spans (seed, E, U, V and ACCUM column
reads, with the ``lake.*`` fetches and decodes inside them) that run inside a
``serve.unit`` span, over the time of the ``serve.unit`` spans, in the traced
window.  Nothing to read gives no value.
"""

from pathlib import Path

import program_trace

ROOT = Path(__file__).resolve().parents[3]


def read(obs: dict):
    ev = program_trace.for_run(obs, ROOT)
    if ev is None:
        return None
    spans = program_trace.window_spans(ev)
    units = program_trace.unit_seconds(spans)
    if units <= 0:
        return None
    return 100.0 * program_trace.in_units(spans, "read.", self_time=False) / units
