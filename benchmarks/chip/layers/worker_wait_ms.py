"""Serving: mean time a request waited for a free worker after the scheduler
dispatched it (ms), the part of ``queue_wait_ms`` that is neither the
batching window nor scheduler lag.

The sum of the ``rider_wait_s`` attribute of the ``serve.unit`` spans that
begin in the traced window, over the sum of their ``riders``.  Nothing to
read gives no value.
"""

from pathlib import Path

import program_trace

ROOT = Path(__file__).resolve().parents[3]


def read(obs: dict):
    ev = program_trace.for_run(obs, ROOT)
    if ev is None:
        return None
    unit = program_trace.program(program_trace.window_spans(ev)).get("serve.unit")
    if not unit or not unit.get("riders"):
        return None
    return 1e3 * unit["rider_wait_s"] / unit["riders"]
