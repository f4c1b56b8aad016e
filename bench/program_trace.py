"""The program's own spans in a traced run, on the profiler's clock.

Where ``trace_reduce.py`` names each device operation by its HLO
instruction, this reads what the program itself names:

  * the stage scopes of the served program (``jax.named_scope`` in
    ``core/query.py``): each device operation carries its scope path in its
    ``tf_op`` metadata (``jit(run)/top_m/top_k``); the outermost stage name
    on that path, not counting the operation's own name at its end, is the
    operation's stage, else ``unscoped``;
  * the server's phases (``obs.trace`` spans in ``serve/server.py``): host
    events named ``serve.*``, with their arguments (``batch_id``, ``fill``,
    ``bucket``).

It reads the newest ``*.xplane.pb`` under ``.traces/*/plugins/profile/*/``,
the file ``trace_reduce.reduce_dir`` read a moment earlier in the same
process (a metric reader gets only the run's record), and keeps the parse
by path. ``jax.profiler.ProfileData`` gives event stats but not event
metadata, so the XSpace is decoded with ``xplane_pb2.py``, kept beside this
file; the benchmark never imports ``tensorflow``. Everything is clipped to
the ``bench.window`` span, as in ``trace_reduce``; device times are means
over the devices that ran any operation.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

import trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))

#: the served program's stage scopes (``QueryPipeline.search`` and
#: ``probe_buckets``), the names staged mode records its stages under
STAGES = frozenset({"scorer_logits", "top_m", "gather", "freq_topc",
                    "quant_rerank", "refine", "rerank", "freq_dense",
                    "probe_stats"})
#: jitted programs that are one stage whole, by the name on their ops' path.
#: JAX's persistent compile cache keys an executable without its ops'
#: metadata, so one compiled from the same program without the scope (by a
#: checkout that predates it) serves the scoped one too, and carries no
#: ``probe_stats`` on its ops; the program's name still says the stage
PROGRAMS = {"jit(probe_buckets)": "probe_stats"}
UNSCOPED = "unscoped"
NO_SPAN = "no span"
SPAN = "serve."
BATCH = "serve.batch"
_NUM = re.compile(r"\.\d+$")

_parsed: dict = {}


def newest() -> str | None:
    """The newest trace the profiler wrote under ``.traces``."""
    found = glob.glob(os.path.join(BENCH, ".traces", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def read(rec: dict) -> dict | None:
    """The reduction of the run's trace (see :func:`reduce`), or None where
    the run was not traced."""
    path = newest() if rec.get("trace") else None
    if path is None:
        return None
    if path not in _parsed:
        _parsed[path] = reduce(path)
    return _parsed[path]


def stage(tf_op: str) -> str:
    """The outermost stage scope or whole-stage program on an operation's
    ``tf_op`` path (``jit(run)/top_m/top_k:``); the last component is the
    operation's own name (``gather`` is also a primitive), so it does not
    count."""
    path = tf_op.rsplit(":", 1)[0].split("/")[:-1]
    return next((PROGRAMS.get(c, c) for c in path
                 if c in STAGES or c in PROGRAMS), UNSCOPED)


def _stats(plane, stats) -> dict:
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        value = getattr(st, kind)
        if kind == "ref_value":
            value = plane.stat_metadata[value].name
        out[plane.stat_metadata[st.metadata_id].name] = value
    return out


def _events(plane, line):
    """(start_ns, end_ns, event) of a line's events."""
    for ev in line.events:
        s = line.timestamp_ns + ev.offset_ps / 1000
        yield s, s + ev.duration_ps / 1000, ev


def _innermost(spans):
    """The spans' boundaries, swept -> (points, names): ``names[i]`` is the
    innermost span covering ``[points[i], points[i+1])`` (of those open,
    the one that started last, the shorter on a tie), or NO_SPAN."""
    events = sorted([(s, 1, i) for i, (s, _, _) in enumerate(spans)]
                    + [(e, 0, i) for i, (_, e, _) in enumerate(spans)])
    points, names, open_ = [], [], {}
    for t, starts, i in events:               # at one instant, ends first
        if starts:
            open_[i] = (spans[i][0], -spans[i][1])
        else:
            open_.pop(i)
        name = spans[max(open_, key=open_.get)][2] if open_ else NO_SPAN
        if points and points[-1] == t:
            names[-1] = name
        else:
            points.append(t)
            names.append(name)
    return points, names


def _idle_by_span(gaps, spans) -> dict:
    """Idle intervals put down to the innermost ``serve.*`` span covering
    each part of them, in seconds."""
    points, names = _innermost(spans)
    out = {}
    for gs, ge in gaps:
        t, i = gs, bisect.bisect_right(points, gs) - 1
        while t < ge:
            name = names[i] if i >= 0 else NO_SPAN
            end = min(points[i + 1], ge) if i + 1 < len(points) else ge
            out[name] = out.get(name, 0.0) + (end - t) * 1e-9
            t, i = end, i + 1
    return out


def reduce(xplane: str) -> dict | None:
    """One trace -> ``scope_s`` (device seconds by stage scope, with an
    ``unscoped`` entry), ``scope_op_s`` (the same by instruction name within
    each scope), ``batches`` (the ``serve.batch`` spans that start in the
    window: ``batch_id``, ``fill``, ``bucket``, ``start_s`` from the
    window's start, ``dur_s``), ``idle_by_span`` (the first device's idle
    seconds in the window by the innermost ``serve.*`` span covering them,
    else ``no span``), and ``window_s``, ``busy_s``, ``idle_s``. None where
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
                for s, e, ev in _events(plane, line):
                    name = names.get(ev.metadata_id)
                    if name is None or e <= s:
                        continue
                    if name == trace_reduce.WINDOW:
                        window = window or (s, e)
                    else:
                        spans.append((s, e, name,
                                      _stats(plane, ev.stats)))
        elif trace_reduce._DEVICE.match(plane.name):
            meta = {}
            for k, md in plane.event_metadata.items():
                tf_op = _stats(plane, md.stats).get("tf_op", "")
                op = _NUM.sub("", md.name.partition(" = ")[0].lstrip("%"))
                meta[k] = (stage(tf_op) if tf_op else UNSCOPED, op)
            ops = [(s, e, meta[ev.metadata_id])
                   for line in plane.lines if line.name == "XLA Ops"
                   for s, e, ev in _events(plane, line)]
            if ops:
                devs.append(ops)
    if not devs:
        return None
    if window:
        lo, hi = window
    else:
        lo = min(s for ops in devs for s, _, _ in ops)
        hi = max(e for ops in devs for _, e, _ in ops)
    n = len(devs)
    scope_ns, op_ns = {UNSCOPED: 0.0}, {}
    busy0 = None
    for ops in devs:
        clipped = []
        for s, e, (scope, op) in ops:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            scope_ns[scope] = scope_ns.get(scope, 0.0) + (e - s)
            by_op = op_ns.setdefault(scope, {})
            by_op[op] = by_op.get(op, 0.0) + (e - s)
        if busy0 is None:
            busy0 = trace_reduce._union(clipped)
    gaps, prev = [], lo
    for s, e in busy0 + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    in_window = [(max(s, lo), min(e, hi), name)
                 for s, e, name, _ in spans if e > lo and s < hi]
    batches = [{"batch_id": a.get("batch_id"), "fill": a.get("fill"),
                "bucket": a.get("bucket"), "start_s": (s - lo) * 1e-9,
                "dur_s": (e - s) * 1e-9}
               for s, e, name, a in sorted(spans, key=lambda x: x[0])
               if name == BATCH and lo <= s < hi]
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(e - s for s, e in busy0) * 1e-9,
            "idle_s": sum(e - s for s, e in gaps) * 1e-9,
            "scope_s": {k: v / n * 1e-9 for k, v in sorted(scope_ns.items())},
            "scope_op_s": {k: {o: t / n * 1e-9 for o, t in sorted(v.items())}
                           for k, v in sorted(op_ns.items())},
            "batches": batches,
            "idle_by_span": dict(sorted(_idle_by_span(gaps,
                                                      in_window).items()))}


def per_batch_ms(rec: dict, seconds) -> float | None:
    """``seconds(reduction)`` of device time over the window's
    ``serve.batch`` spans, in ms a batch; None where the trace holds no
    batch or ``seconds`` finds nothing (returns None)."""
    red = read(rec)
    if not red or not red["batches"]:
        return None
    s = seconds(red)
    return None if s is None else 1e3 * s / len(red["batches"])
