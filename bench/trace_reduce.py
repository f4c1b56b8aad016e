"""Reduce a JAX profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read.

  * the window: the host span ``bench.window`` the cell's module opens
    around its measured window (else the span of all device operations);
  * busy: the union of the intervals in which an operation ran on a device,
    clipped to the window, averaged over the devices that ran any;
  * device time by instruction name, for every instruction that ran: the
    summed time of the operations whose HLO instruction has that name,
    without its number (a Pallas kernel's custom-call carries the kernel's
    name), so a metric reader can pick any kernel by its name;
  * collective time: the summed device time of all-gather, all-reduce,
    reduce-scatter, all-to-all and collective-permute operations;
  * the device operations that took most time, grouped by name path;
  * the longest idle gaps on the first device, each with the host event
    that overlapped it most: what the host was doing meanwhile.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
_COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                         r"all-to-all|collective-permute)")
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_NUM = re.compile(r"\.\d+$")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def _path(ev) -> str:
    """The name an operation is grouped and matched by. A TPU trace names
    each op by its HLO text (``%freq_topc.1 = (s32[512,1024]...) custom-call
    (...)``): the instruction's name without its number, and the shape of
    its first result (``freq_topc s32[512,1024]``). A Pallas kernel's
    instruction carries the kernel's name."""
    text = ev.name
    name, eq, rest = text.partition(" = ")
    name = _NUM.sub("", name.lstrip("%"))
    if not eq:
        return name
    shape = _SHAPE.search(rest)
    return f"{name} {shape.group(0)}" if shape else name


def instruction(path: str) -> str:
    """The instruction name of a grouped op path."""
    return path.split(" ", 1)[0]


def _union(intervals):
    """Merge [start, end) intervals -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def op_events(plane):
    """(start_ns, end_ns, event) of every operation on a device plane."""
    out = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            out.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev))
    return out


def reduce(xplane: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane)
    host, devs = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            host.extend((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for line in plane.lines for ev in line.events
                        if ev.duration_ns > 0)
        elif _DEVICE.match(plane.name):
            ops = op_events(plane)
            if ops:
                devs.append(ops)
    if not devs:
        return None
    win = [(s, e) for s, e, n in host if n == WINDOW]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for ops in devs for s, _, _ in ops)
        hi = max(e for ops in devs for _, e, _ in ops)
    window_s = (hi - lo) * 1e-9
    busy, op_ns, coll_ns, by_name = [], {}, 0.0, {}
    merged0 = None
    for ops in devs:
        clipped = []
        for s, e, ev in ops:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            path = _path(ev)
            by_name[path] = by_name.get(path, 0.0) + (e - s)
            op = instruction(path)
            op_ns[op] = op_ns.get(op, 0.0) + (e - s)
            if _COLLECTIVE.search(op):
                coll_ns += e - s
        merged = _union(clipped)
        if merged0 is None:
            merged0 = merged
        busy.append(sum(e - s for s, e in merged))
    n = len(devs)
    gaps, prev = [], lo
    for s, e in merged0 + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for gs, ge in gaps[:top]:
        best, name = 0, "nothing traced"
        for s, e, nm in host:
            if nm == WINDOW:
                continue
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, name = ov, nm
        idle.append([name, (ge - gs) * 1e-9])
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s,
            "busy_s": sum(busy) / n * 1e-9,
            "devices": n,
            "op_s": {k: v / n * 1e-9 for k, v in sorted(op_ns.items())},
            "collective_s": coll_ns / n * 1e-9,
            "device_ops": [[k, v / n * 1e-9] for k, v in ops_top],
            "idle_gaps": idle}


def reduce_dir(path: str, **kw):
    """Reduce the newest trace the profiler wrote under ``path``."""
    found = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        return None
    return reduce(found[-1], **kw)
