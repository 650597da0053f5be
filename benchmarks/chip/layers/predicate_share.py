"""Executor and read path: the share of the workers' request time spent
evaluating WHERE conjuncts (percent).

The self time of the program's ``predicate.*`` spans (seed, E, U, V stages)
that run inside a ``serve.unit`` span, over the time of the ``serve.unit``
spans, in the traced window.  Nothing to read (no trace, or a program
without these spans) gives no value.
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
    return 100.0 * program_trace.in_units(spans, "predicate.", self_time=True) / units
