"""Span/trace — wall-clock attribution with explicit device fencing, on the
profiler's clock.

jax dispatch is asynchronous: ``t1 - t0`` around a jitted call measures
Python dispatch, not device work. A :class:`Span` therefore exposes
``fence(x)`` — ``jax.block_until_ready`` on the stage's OUTPUT — so the
recorded duration covers exactly the device work needed to produce that
output, attributed to the right stage:

    with trace(registry, "serve_stage_seconds", stage="freq_topc") as sp:
        cid, cnt = sp.fence(freq_fn(cands))

Every span also opens a ``jax.profiler.TraceAnnotation`` under its name,
with its labels as the annotation's arguments: under a running profiler it
lands in the trace's host plane, on the same clock as the device's
operations; with no profiler on it costs about a microsecond and records
nothing. A span given a registry also times itself into the registry
histogram named by ``name`` with the given labels (``time.perf_counter``;
default bounds ``LATENCY_BUCKETS``, 1us..100s log-spaced); given None it is
the annotation alone:

    with trace(None, "serve.batch", batch_id=7, fill=389, bucket=512):
        ...

A span records on exit even when the body raises — failed requests still
show up in the latency distribution and in the trace rather than silently
vanishing.
"""
from __future__ import annotations

import time

import jax

from repro.obs.registry import LATENCY_BUCKETS, MetricRegistry

__all__ = ["Span", "trace", "fence"]


def fence(x):
    """Block until every array in ``x`` (any pytree) is computed; returns
    ``x``. The explicit synchronization point that makes host-side timing
    attribute device work to the right stage."""
    return jax.block_until_ready(x)


class Span:
    """Context manager that annotates one stage on the profiler's clock
    and, given a registry, times it into a registry histogram.

    Attributes after exit: ``seconds`` (the measured duration). Reentrant
    use is not supported — make a new Span per stage.
    """

    def __init__(self, registry: MetricRegistry | None, name: str,
                 labels: dict | None = None, bounds=LATENCY_BUCKETS):
        self._hist = (registry.histogram(name, labels, bounds)
                      if registry is not None else None)
        self.name = name
        self.labels = dict(labels or {})
        self.seconds: float | None = None
        self._t0: float | None = None
        self._note = None

    def fence(self, x):
        """``jax.block_until_ready`` on this stage's output; returns it."""
        return fence(x)

    def __enter__(self) -> "Span":
        self._note = jax.profiler.TraceAnnotation(self.name, **self.labels)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._note.__exit__(exc_type, exc, tb)
        if self._hist is not None:
            self._hist.observe(self.seconds)
        return False


def trace(registry: MetricRegistry | None, name: str, *,
          bounds=LATENCY_BUCKETS, **labels) -> Span:
    """Sugar: ``with trace(reg, "serve_stage_seconds", stage="gather") as sp``
    — labels are keyword arguments; ``registry=None`` annotates only."""
    return Span(registry, name, labels or None, bounds)
