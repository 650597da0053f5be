"""The program's own spans in a traced run, beside the device's operations.

``trace_reduce.py`` reads the device's operations and the benchmark's spans.
This module reads, from the same ``.xplane.pb`` and so on the same clock, the
program's spans (host events named ``graphlake.<name>``, made by
``src/repro/tracing.py``) and each device operation's ``named_scope`` path.
``load`` turns them into plain lists (a small JSON of them is a test
fixture); ``reduce`` works on those lists only:

- ``program``: per span name, the count and the sums of its numeric
  attributes (spans that begin in the window), and its total and self
  seconds (less child spans on the same thread), clipped to the window;
- ``device_scopes``: per outermost program scope (``pagerank.*``), the
  device seconds its operations cover in the window, and ``unscoped`` for
  the rest of the busy time; the ten largest, averaged over the devices;
- ``idle_gaps``: the window's idle time on the first device, each gap split
  evenly among the innermost program spans that cover its middle, one per
  thread; where none does, the innermost benchmark span that covers it, else
  ``none``.  The totals add up to the idle time (ten largest kept).

The per-layer readers that read program spans call ``for_run``.

    python3 benchmarks/chip/program_trace.py <trace dir> [--events <out.json>]

prints ``reduce``'s result as one JSON line; ``--events`` also writes the
loaded lists, cut to the window's first ``--cut-s`` seconds when given.
"""

from __future__ import annotations

import argparse
import collections
import json
import re
import sys
from pathlib import Path

PROGRAM_PREFIX = "graphlake."
BENCH_PREFIX = "chipbench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# the stat that carries an op's ``op_name`` metadata, its ``named_scope``
# path, e.g. "jit(_pagerank_step_csr)/pagerank.gather/gather:".  On a v5e
# (JAX 0.9.0) it is a stat of the op's event *metadata* in the device plane,
# which ``ProfileData`` does not expose: ``op_scopes`` reads it from the file.
SCOPE_STAT = "tf_op"
# a scope the program names: ``<layer>.<stage>``, e.g. ``pagerank.gather``
PROGRAM_SCOPE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z0-9_.]+$")
UNIT = "serve.unit"
# attributes that name things: a lone request id reads as a number
ID_ATTRS = {"rids"}
TOP = 10


def newest_trace(trace_root: Path):
    files = sorted(Path(trace_root).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def load(trace_file: Path) -> dict:
    """``{"window": [start_ns, end_ns] or None,
    "spans": [[name, start_ns, end_ns, thread, {attr: number}], ...],
    "bench_spans": [[name, start_ns, end_ns], ...],
    "devices": [[[op, start_ns, end_ns, scope], ...] per device]}``"""
    from jax.profiler import ProfileData

    from trace_reduce import op_name

    data = ProfileData.from_file(str(trace_file))
    scopes = op_scopes(trace_file)
    window, spans, bench, devices = None, [], [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            of_op = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([op_name(e.name), e.start_ns, e.end_ns,
                                outer_scope(of_op.get(e.name, ""))]
                               for e in line.events)
            continue
        if not plane.name.startswith("/host"):
            continue
        for li, line in enumerate(plane.lines):
            thread = f"{li} {line.name}"
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    attrs = {k: v for k, v in e.stats if k not in ID_ATTRS
                             and isinstance(v, (int, float)) and not isinstance(v, bool)}
                    spans.append([e.name[len(PROGRAM_PREFIX):], e.start_ns, e.end_ns,
                                  thread, attrs])
                elif e.name == BENCH_PREFIX + "window":
                    window = [e.start_ns, e.end_ns]
                elif e.name.startswith(BENCH_PREFIX):
                    bench.append([e.name[len(BENCH_PREFIX):], e.start_ns, e.end_ns])
    return {"window": window, "spans": spans, "bench_spans": bench,
            "devices": [devices[k] for k in sorted(devices)]}


def outer_scope(path: str) -> str:
    """The outermost program scope of an ``op_name`` path, else ``""``."""
    for part in path.rstrip(":").split("/"):
        if PROGRAM_SCOPE.match(part):
            return part
    return ""


def _xspace_class():
    """A message class for the few fields of ``tsl``'s ``XSpace`` that
    ``op_scopes`` needs (field numbers of ``xplane.proto``; the rest of the
    file parses as unknown fields)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="xplane_scopes.proto",
                                             package="xplane_scopes", syntax="proto3")
    for name, fields in (
            ("XStat", [("metadata_id", 1, F.TYPE_INT64, ""), ("str_value", 5, F.TYPE_STRING, ""),
                       ("ref_value", 7, F.TYPE_UINT64, "")]),
            ("XStatMetadata", [("name", 2, F.TYPE_STRING, "")]),
            ("XEventMetadata", [("name", 2, F.TYPE_STRING, ""), ("stats", 5, F.TYPE_MESSAGE, "XStat*")]),
            ("EventMetadataEntry", [("key", 1, F.TYPE_INT64, ""),
                                    ("value", 2, F.TYPE_MESSAGE, "XEventMetadata")]),
            ("StatMetadataEntry", [("key", 1, F.TYPE_INT64, ""),
                                   ("value", 2, F.TYPE_MESSAGE, "XStatMetadata")]),
            ("XPlane", [("name", 2, F.TYPE_STRING, ""),
                        ("event_metadata", 4, F.TYPE_MESSAGE, "EventMetadataEntry*"),
                        ("stat_metadata", 5, F.TYPE_MESSAGE, "StatMetadataEntry*")]),
            ("XSpace", [("planes", 1, F.TYPE_MESSAGE, "XPlane*")])):
        msg = fdp.message_type.add(name=name)
        for fname, number, ftype, tname in fields:
            f = msg.field.add(name=fname, number=number, type=ftype,
                              label=F.LABEL_REPEATED if tname.endswith("*") else F.LABEL_OPTIONAL)
            if tname:
                f.type_name = ".xplane_scopes." + tname.rstrip("*")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("xplane_scopes.XSpace"))


def op_scopes(trace_file: Path) -> dict:
    """``{device plane: {op's HLO text: its SCOPE_STAT}}`` from the file."""
    space = _xspace_class().FromString(Path(trace_file).read_bytes())
    out = {}
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        ids = [k for k, v in names.items() if v == SCOPE_STAT]
        out[plane.name] = {e.value.name: s.str_value or names.get(s.ref_value, "")
                           for e in plane.event_metadata for s in e.value.stats
                           if s.metadata_id in ids}
    return out


def for_run(obs: dict, root: Path):
    """The loaded events of the traced run that ``obs`` comes from: the
    newest trace under the checkout's work directory whose window is the
    one the harness measured.  ``None`` when there is none."""
    from harness import WORK_REL

    t = obs.get("trace")
    if not t:
        return None
    files = sorted(Path(root, WORK_REL).glob("*/trace/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True)
    for f in files:
        ev = load(f)
        w = ev["window"]
        if w and abs((w[1] - w[0]) / 1e9 - t["window_s"]) < 1e-6:
            return ev
    return None


# -- reduction -----------------------------------------------------------------

def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window_spans(ev: dict) -> list:
    """The program spans that overlap the window, clipped to it, each a dict
    with ``name``, ``start``, ``end`` (ns), ``thread``, ``attrs``,
    ``in_window`` (it began in the window), ``self_ns`` and ``parents``
    (the names of the spans around it on its thread, outermost first)."""
    w0, w1 = ev["window"]
    out = []
    by_thread = collections.defaultdict(list)
    for name, s, e, thread, attrs in ev["spans"]:
        if e < w0 or s > w1:
            continue
        sp = {"name": name, "start": max(s, w0), "end": min(e, w1), "thread": thread,
              "attrs": attrs, "in_window": w0 <= s <= w1, "raw": (s, e)}
        sp["self_ns"] = sp["end"] - sp["start"]
        out.append(sp)
        by_thread[thread].append(sp)
    for spans in by_thread.values():
        spans.sort(key=lambda sp: (sp["raw"][0], -sp["raw"][1]))
        stack: list = []
        for sp in spans:
            while stack and stack[-1]["raw"][1] < sp["raw"][1]:
                stack.pop()
            sp["parents"] = [p["name"] for p in stack]
            if stack:
                stack[-1]["self_ns"] -= sp["end"] - sp["start"]
            stack.append(sp)
    for sp in out:
        del sp["raw"]
    return out


def program(spans: list) -> dict:
    out: dict = {}
    for sp in spans:
        d = out.setdefault(sp["name"], {"count": 0, "total_s": 0.0, "self_s": 0.0})
        d["total_s"] += (sp["end"] - sp["start"]) / 1e9
        d["self_s"] += sp["self_ns"] / 1e9
        if sp["in_window"]:
            d["count"] += 1
            for k, v in sp["attrs"].items():
                d[k] = d.get(k, 0) + v
    return out


def in_units(spans: list, prefix: str, self_time: bool) -> float:
    """Seconds of the spans named ``<prefix>*`` that run inside a
    ``serve.unit`` (their own time only with ``self_time``)."""
    return sum((sp["self_ns"] if self_time else sp["end"] - sp["start"]) / 1e9
               for sp in spans
               if sp["name"].startswith(prefix) and UNIT in sp["parents"])


def unit_seconds(spans: list) -> float:
    return sum((sp["end"] - sp["start"]) / 1e9 for sp in spans if sp["name"] == UNIT)


def device_scopes(ev: dict, chips: int = 1) -> list:
    w0, w1 = ev["window"]
    devices = ev["devices"][:chips]
    total: collections.Counter = collections.Counter()
    for ops in devices:
        by_scope = collections.defaultdict(list)
        for _, s, e, scope in ops:
            if e > w0 and s < w1:
                by_scope[scope].append((max(s, w0), min(e, w1)))
        busy = sum(e - s for s, e in _union(iv for ivs in by_scope.values() for iv in ivs))
        scoped = 0
        for scope, ivs in by_scope.items():
            if scope:
                t = sum(e - s for s, e in _union(ivs))
                total[scope] += t / 1e9 / len(devices)
                scoped += t
        if busy > scoped:
            total["unscoped"] += (busy - scoped) / 1e9 / len(devices)
    return [[n, v] for n, v in total.most_common(TOP)]


def idle_gaps(ev: dict, spans: list) -> list:
    w0, w1 = ev["window"]
    if not ev["devices"]:
        return []
    busy = _union((max(s, w0), min(e, w1)) for _, s, e, _ in ev["devices"][0]
                  if e > w0 and s < w1)
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    by_thread = collections.defaultdict(list)
    for sp in spans:
        by_thread[sp["thread"]].append(sp)
    bench = [(s, e, name) for name, s, e in ev["bench_spans"]]
    gaps: collections.Counter = collections.Counter()
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        names = []
        for thread_spans in by_thread.values():
            covering = [(sp["start"], -sp["end"], sp["name"]) for sp in thread_spans
                        if sp["start"] <= mid <= sp["end"]]
            if covering:
                names.append(max(covering)[2])
        if not names:
            covering = [(s, name) for s, e, name in bench if s <= mid <= e]
            names = [max(covering)[1] if covering else "none"]
        for name in names:
            gaps[name] += (g1 - g0) / 1e9 / len(names)
    return [[n, v] for n, v in gaps.most_common(TOP)]


def reduce(ev: dict, chips: int = 1) -> dict:
    spans = window_spans(ev)
    return {"program": program(spans), "device_scopes": device_scopes(ev, chips),
            "idle_gaps": idle_gaps(ev, spans)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", type=Path)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--events", type=Path)
    ap.add_argument("--cut-s", type=float)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    f = newest_trace(args.trace_dir)
    if f is None:
        print(f"no .xplane.pb under {args.trace_dir}", file=sys.stderr)
        return 1
    ev = load(f)
    print(json.dumps(reduce(ev, args.chips)), flush=True)
    if args.events:
        args.events.write_text(json.dumps(cut(ev, args.cut_s) if args.cut_s else ev))
    return 0


def cut(ev: dict, seconds: float) -> dict:
    """``ev`` with its window cut to its first ``seconds``, and only what
    overlaps that: a small trace that still reduces consistently."""
    w0 = ev["window"][0]
    w1 = min(ev["window"][1], w0 + int(seconds * 1e9))
    inside = lambda s, e: e >= w0 and s <= w1  # noqa: E731
    return {"window": [w0, w1],
            "spans": [sp for sp in ev["spans"] if inside(sp[1], sp[2])],
            "bench_spans": [sp for sp in ev["bench_spans"] if inside(sp[1], sp[2])],
            "devices": [[op for op in ops if inside(op[1], op[2])] for ops in ev["devices"]]}


if __name__ == "__main__":
    sys.exit(main())
