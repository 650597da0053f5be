"""Program spans on the profiler's clock.

``span(name, **attrs)`` is a host span named ``graphlake.<name>``: a
``jax.profiler.TraceAnnotation``, so it lands in the same trace, on the same
clock, as the device's operations.  Attribute values are numbers or short
strings (no ``,``, ``=`` or ``#``: the profiler's metadata encoding splits on
them).  Values known only at the end go in with ``set_metadata``::

    with span("query.hop", edge_type=et, rows_in=n) as s:
        frame = scan()
        s.set_metadata(rows_out=len(frame))

A span is recorded exactly when a profiler is running; otherwise it is one
no-op ``TraceMe`` (about 1 µs).  Nothing is buffered here: the profiler keeps
the spans and writes them out with its trace.

``scope(name)`` is ``jax.named_scope``, for code inside ``jit``: it names
the HLO ops (their ``op_name`` metadata) and leaves the program unchanged.
"""

from __future__ import annotations

import ctypes
import sys
import threading

import jax
from jax.profiler import TraceAnnotation

PREFIX = "graphlake."
_PR_SET_NAME = 15


def span(name: str, **attrs) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + name, **attrs)


def scope(name: str):
    return jax.named_scope(name)


def name_os_thread() -> None:
    """Give the calling thread's Python name to its OS thread (Linux), which
    is what names the thread's line in a profiler trace."""
    if sys.platform.startswith("linux"):
        prctl = ctypes.CDLL(None).prctl
        prctl.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                          ctypes.c_ulong, ctypes.c_ulong]
        prctl.restype = ctypes.c_int
        prctl(_PR_SET_NAME, threading.current_thread().name.encode()[:15], 0, 0, 0)
