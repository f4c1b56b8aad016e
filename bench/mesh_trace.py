"""A traced run on several chips, chip by chip, on the profiler's clock.

``trace_reduce.py`` and ``program_trace.py`` average each device number over
the chips; a sharded search also needs each chip's own: a fast shard's
collective waits for the slowest, so the chips' busy times differ, and the
merge's share differs with them. This reads the same newest ``*.xplane.pb``
(decoded with ``xplane_pb2.py``, as ``program_trace`` does) and keeps, for
every device plane that ran an operation:

  * ``busy_s``: the union of its operations' intervals, clipped to the
    ``bench.window`` span;
  * ``scope_s``: its device seconds by the outermost stage scope on each
    operation's ``tf_op`` path, the per-shard stages of
    ``program_trace.STAGES`` and the cross-shard ``merge`` of
    ``core/distributed.py`` (``unscoped`` otherwise);
  * ``merge_collective_s``: its device seconds in the all-gather and
    all-reduce operations (any collective, by its instruction name or by
    its opcode: a ``psum`` lowers to an instruction ``%psum.N = ...
    all-reduce(...)``) inside the ``merge`` scope.

And, on the first device, ``idle_by_span``: its idle seconds in the window by
the innermost host span of the cell's window loop (``sharded.*``) covering
them, else ``no span``.
"""
from __future__ import annotations

import re

import program_trace
import trace_reduce

MERGE = "merge"
SPAN = "sharded."
STAGES = program_trace.STAGES | {MERGE}
_NUM = re.compile(r"\.\d+$")
#: a collective's opcode in an operation's HLO text, after its result shape
_OPCODE = re.compile(r"\s(?:all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)[\w-]*\(")

_parsed: dict = {}


def stage(tf_op: str) -> str:
    """The outermost stage scope on an operation's ``tf_op`` path, not
    counting the operation's own name at its end."""
    path = tf_op.rsplit(":", 1)[0].split("/")[:-1]
    return next((c for c in path if c in STAGES), program_trace.UNSCOPED)


def read(rec: dict) -> dict | None:
    """The reduction of the run's trace (see :func:`reduce`), or None where
    the run was not traced."""
    path = program_trace.newest() if rec.get("trace") else None
    if path is None:
        return None
    if path not in _parsed:
        _parsed[path] = reduce(path)
    return _parsed[path]


def reduce(xplane: str) -> dict | None:
    """One trace -> ``window_s``, ``devices`` (per device plane that ran an
    operation, in plane order: ``busy_s``, ``scope_s``,
    ``merge_collective_s``) and ``idle_by_span`` (first device). None where
    no device ran an operation."""
    import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(xplane, "rb") as f:
        space.ParseFromString(f.read())
    window, spans, devs = None, [], []
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            names = {k: md.name for k, md in plane.event_metadata.items()
                     if md.name == trace_reduce.WINDOW
                     or md.name.startswith(SPAN)}
            for line in plane.lines:
                for s, e, ev in program_trace._events(plane, line):
                    name = names.get(ev.metadata_id)
                    if name is None or e <= s:
                        continue
                    if name == trace_reduce.WINDOW:
                        window = window or (s, e)
                    else:
                        spans.append((s, e, name))
        elif trace_reduce._DEVICE.match(plane.name):
            meta = {}
            for k, md in plane.event_metadata.items():
                tf_op = program_trace._stats(plane, md.stats).get("tf_op", "")
                name, _, rest = md.name.partition(" = ")
                op = _NUM.sub("", name.lstrip("%"))
                meta[k] = (stage(tf_op) if tf_op else program_trace.UNSCOPED,
                           bool(trace_reduce._COLLECTIVE.search(op)
                                or _OPCODE.search(rest)))
            ops = [(s, e, meta[ev.metadata_id])
                   for line in plane.lines if line.name == "XLA Ops"
                   for s, e, ev in program_trace._events(plane, line)]
            if ops:
                devs.append(ops)
    if not devs:
        return None
    if window:
        lo, hi = window
    else:
        lo = min(s for ops in devs for s, _, _ in ops)
        hi = max(e for ops in devs for _, e, _ in ops)
    out, busy0 = [], None
    for ops in devs:
        clipped, scope_ns, coll_ns = [], {}, 0.0
        for s, e, (scope, collective) in ops:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            scope_ns[scope] = scope_ns.get(scope, 0.0) + (e - s)
            if scope == MERGE and collective:
                coll_ns += e - s
        busy = trace_reduce._union(clipped)
        if busy0 is None:
            busy0 = busy
        out.append({"busy_s": sum(e - s for s, e in busy) * 1e-9,
                    "scope_s": {k: v * 1e-9
                                for k, v in sorted(scope_ns.items())},
                    "merge_collective_s": coll_ns * 1e-9})
    gaps, prev = [], lo
    for s, e in busy0 + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    in_window = [(max(s, lo), min(e, hi), name)
                 for s, e, name in spans if e > lo and s < hi]
    return {"window_s": (hi - lo) * 1e-9, "devices": out,
            "idle_by_span": dict(sorted(program_trace._idle_by_span(
                gaps, in_window).items()))}
