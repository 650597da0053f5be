"""Serving: the window's scheduler stalls (ms), the time something, as a
rule the interpreter lock, kept the server's scheduler from running.

The scheduler counts a wake-up more than 10 ms past its timeout as a stall
(``QueryServer.stats["stall_s"]``) and marks each with a ``serve.stall``
span carrying ``late_s``.  This is the sum of ``late_s`` over the window,
0 when no stall came; a trace without ``serve.unit`` spans (a program that
marks no stalls) gives no value.
"""

from pathlib import Path

import program_trace

ROOT = Path(__file__).resolve().parents[3]


def read(obs: dict):
    ev = program_trace.for_run(obs, ROOT)
    if ev is None:
        return None
    prog = program_trace.program(program_trace.window_spans(ev))
    if program_trace.UNIT not in prog:
        return None
    return 1e3 * prog.get("serve.stall", {}).get("late_s", 0.0)
