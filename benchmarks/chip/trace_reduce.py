"""Reduce a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` a traced run wrote into plain tuples:
each device's operations (the ``XLA Ops`` line of every ``/device:TPU:<i>``
plane) and the benchmark's host spans (``TraceAnnotation``s whose names
start with the span prefix).  ``reduce`` works on those tuples only, so it
can be checked on a small recorded event list:

- ``window_s``: the length of the span named ``window``;
- ``busy_s``: the union of each device's operation intervals inside the
  window, averaged over the devices;
- ``device_ops``: the ten operation names that took the most device time in
  the window (seconds, averaged over the devices);
- ``idle_gaps``: the window's idle time on the first device, each gap
  named by the innermost host span that covers its middle, the ten largest
  totals (``"none"`` where no span covers it).
"""

from __future__ import annotations

import collections
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# an XLA op event is named by its HLO text: "%fusion.3 = f32[...] fusion(...)"
HLO_TEXT = re.compile(r"(%[\w.\-]+) = .*?\b([a-z][\w\-]*)\(")
OPS_LINE = "XLA Ops"
TOP = 10


def load(trace_dir: Path, prefix: str) -> dict:
    """``{"devices": [[(op, start_ns, end_ns), ...] per device],
    "spans": [(name, start_ns, end_ns), ...]}`` from the newest trace."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                spans.extend((e.name[len(prefix):], e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name.startswith(prefix))
    return {"devices": [devices[k] for k in sorted(devices)], "spans": spans}


def op_name(text: str) -> str:
    """``%fusion.3 fusion`` for an op named by its HLO text, else the text."""
    m = HLO_TEXT.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(events: dict, chips: int) -> dict:
    spans = events["spans"]
    windows = [(s, e) for name, s, e in spans if name == "window"]
    if not windows:
        raise ValueError("the trace holds no window span")
    w0, w1 = windows[0]
    devices = events["devices"][:chips]
    busy, op_time = [], collections.Counter()
    unions = []
    for ops in devices:
        clipped = [(max(s, w0), min(e, w1), name) for name, s, e in ops if e > w0 and s < w1]
        for s, e, name in clipped:
            op_time[name] += (e - s) / 1e9 / max(1, len(devices))
        u = _union([(s, e) for s, e, _ in clipped])
        unions.append(u)
        busy.append(sum(e - s for s, e in u) / 1e9)
    gaps = collections.Counter()
    if unions:
        inner = sorted((s, e, name) for name, s, e in spans if name != "window")
        edges = [w0] + [t for iv in unions[0] for t in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            covering = [(s, name) for s, e, name in inner if s <= mid <= e]
            gaps[max(covering)[1] if covering else "none"] += (g1 - g0) / 1e9
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "device_ops": [[n, v] for n, v in op_time.most_common(TOP)],
            "idle_gaps": [[n, v] for n, v in gaps.most_common(TOP)]}


def summarize(trace_dir: Path, prefix: str, chips: int) -> dict:
    return reduce(load(trace_dir, prefix), chips)
