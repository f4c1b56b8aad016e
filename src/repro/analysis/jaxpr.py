"""Jaxpr walker — the analyzer behind every "this intermediate never
exists" contract in the repo (compact-query [Q, L], store fp32 [L, D],
fit [R, L, B]) and the per-contract peak-intermediate-bytes report.

Promoted from ``benchmarks/jaxpr_walk.py`` (which remains as a deprecated
re-exporting shim): one copy, so a JAX representation change (the
jit/scan sub-jaxpr layout, a new control-flow primitive) gets fixed here,
not in drifting clones. The walk recurses EXPLICITLY into the sub-jaxpr
params of ``jit``/``scan``/``cond``/``while`` (ClosedJaxpr), ``shard_map``
and ``pallas_call`` (raw Jaxpr) and lists/tuples of either — the shapes it
yields inside ``shard_map`` are the PER-SHARD block shapes, which is
exactly what a per-device memory contract wants to see.

Negative proofs built on :func:`materializes_dims` (asserting a shape is
ABSENT) are vacuous unless paired with a positive control that DOES trip
the detector — ``repro.analysis.contracts`` enforces that pairing
mechanically.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


def _sub_jaxprs(p):
    """Every sub-jaxpr reachable from one eqn param value."""
    if hasattr(p, "jaxpr") and hasattr(p, "consts"):       # ClosedJaxpr
        yield p.jaxpr
    elif hasattr(p, "eqns"):                               # raw Jaxpr
        # (shard_map and pallas_call carry their body like this)
        yield p
    elif isinstance(p, (list, tuple)):
        for q in p:
            yield from _sub_jaxprs(q)


def iter_eqns(jaxpr):
    """Every equation in a jaxpr, recursing into sub-jaxprs (jit/scan/
    cond/while bodies, shard_map and pallas_call kernels)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                yield from iter_eqns(sub)


def iter_avals(jaxpr):
    """Every intermediate aval in a jaxpr, recursing into sub-jaxprs."""
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            yield v.aval


def traced_avals(fn, *args):
    """Trace ``fn(*args)`` (abstractly — nothing executes) and yield every
    intermediate aval."""
    yield from iter_avals(jax.make_jaxpr(fn)(*args).jaxpr)


def traced_shapes(fn, args, dtype=None):
    """All intermediate shapes (optionally of one dtype) of fn(*args)."""
    return [tuple(a.shape) for a in traced_avals(fn, *args)
            if getattr(a, "shape", None)
            and (dtype is None or getattr(a, "dtype", None) == dtype)]


def materializes_dims(fn, args, *dims, dtype=None):
    """True iff some intermediate's shape contains ALL the given distinctive
    dims (optionally restricted to one dtype) — the detector behind the
    [Q, L] / [L, D] / [R, L, B] proofs. Always pair a negative assertion
    with a positive control, or it is vacuous."""
    for a in traced_avals(fn, *args):
        shape = getattr(a, "shape", None)
        if not isinstance(shape, tuple) or not shape:
            continue
        if dtype is not None and getattr(a, "dtype", None) != dtype:
            continue
        if all(d in shape for d in dims):
            return True
    return False


#: top-level primitives that launch device work as a separate dispatch —
#: a jitted call and a bare pallas_call each cost one kernel round-trip
_DISPATCH_PRIMITIVES = ("jit", "pallas_call")


def count_dispatches(fn, args) -> int:
    """Number of TOP-LEVEL dispatch sites (jit / pallas_call eqns) in the
    trace of ``fn(*args)``. Deliberately NOT recursive — a jit that nests
    further jits/pallas_calls still launches as one fused executable, while
    N sibling eqns at the top level are N separate dispatches with an HBM
    round-trip between each (the cost the megakernel removes). This is the
    detector behind ``max_dispatches`` / ``query.mega_single_dispatch``."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return sum(1 for e in jaxpr.eqns
               if e.primitive.name in _DISPATCH_PRIMITIVES)


def _aval_bytes(a) -> int:
    shape = getattr(a, "shape", None)
    dt = getattr(a, "dtype", None)
    if shape is None or dt is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * dt.itemsize


@dataclasses.dataclass(frozen=True)
class PeakIntermediate:
    """The largest single traced intermediate and where it came from."""
    bytes: int
    shape: tuple
    dtype: str
    primitive: str


def peak_report(fn, *args) -> PeakIntermediate:
    """Largest single traced intermediate with its producing primitive —
    what the audit CLI reports per contract as ``analysis_peak_bytes``."""
    best = PeakIntermediate(0, (), "", "")
    for eqn in iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        for v in eqn.outvars:
            b = _aval_bytes(v.aval)
            if b > best.bytes:
                best = PeakIntermediate(
                    b, tuple(v.aval.shape), str(v.aval.dtype),
                    eqn.primitive.name)
    return best


def peak_intermediate_bytes(fn, *args) -> int:
    """Largest single traced intermediate, in bytes."""
    return peak_report(fn, *args).bytes
