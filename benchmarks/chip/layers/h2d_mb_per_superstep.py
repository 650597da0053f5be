"""Algorithms and kernels: host-to-device bytes per PageRank superstep (MB).

The sum of the ``bytes`` attribute of the ``pagerank.upload`` spans that
begin in the traced window (each job uploads its CSR and out-degrees), over
the supersteps the window completed.  Nothing to read gives no value.
"""

from pathlib import Path

import program_trace

ROOT = Path(__file__).resolve().parents[3]


def read(obs: dict):
    steps = obs.get("supersteps")
    ev = program_trace.for_run(obs, ROOT) if steps else None
    if ev is None:
        return None
    upload = program_trace.program(program_trace.window_spans(ev)).get("pagerank.upload")
    if not upload or not upload.get("bytes"):
        return None
    return upload["bytes"] / steps / 1e6
