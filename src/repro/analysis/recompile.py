"""Recompile detector — counts TRACES, not calls.

A jitted entry point should compile exactly once per (static config,
arg-structure) key; every extra trace is latency (seconds of XLA time at
production shapes) and a symptom of a cache-key bug: a python float where a
``jnp.float32`` scalar belongs (weak-type drift), an int that became an
int64, a ``None`` member that became an array, a dataclass missing
``__hash__``. These slip through functional tests because the RESULT is
identical — only the trace count betrays them.

Two complementary counters:

  :func:`jit_cache_size`     reads a jitted function's own tracing-cache
                             size (``_cache_size``) — counts every distinct
                             trace jax retained for it.
  :class:`TraceCounter`      wraps an arbitrary python callable so a jitted
                             wrapper around it ticks the counter once per
                             TRACE (python body execution), independent of
                             jax internals. This is how ``PipelineCache``'s
                             own ``compiles`` counter works; the class is
                             here for fixtures that sweep other callables.

:func:`sweep` is the contract-facing entry: run a callable over variants,
report traces-before/after and per-variant deltas, and
:func:`diagnose_drift` explains the canonical weak-type failure in terms a
contract violation message can carry.
"""
from __future__ import annotations

import dataclasses


def jit_cache_size(jitted) -> int:
    """Number of retained traces of a ``jax.jit`` callable, or of the one
    a ``functools.partial`` binds (0 before the first call)."""
    return int(getattr(jitted, "func", jitted)._cache_size())


class TraceCounter:
    """Wrap ``fn`` so every TRACE (python execution under jit) ticks
    ``.count`` — calls served from the compile cache do not."""

    def __init__(self, fn):
        self.fn = fn
        self.count = 0

    def __call__(self, *args, **kwargs):
        self.count += 1
        return self.fn(*args, **kwargs)


@dataclasses.dataclass(frozen=True)
class SweepReport:
    """Trace accounting of one parameter sweep."""
    traces: int                 # total traces observed over the sweep
    expected: int               # distinct keys the sweep should compile
    per_variant: tuple          # (label, traces_after_this_variant) pairs

    @property
    def ok(self) -> bool:
        return self.traces <= self.expected

    @property
    def extra(self) -> int:
        return max(0, self.traces - self.expected)

    def first_offender(self):
        """Label of the first variant whose call pushed traces past
        ``expected`` (None when ok) — names the drifting parameter."""
        for label, after in self.per_variant:
            if after > self.expected:
                return label
        return None


def sweep(call, variants, expected: int, *,
          counter=None, jitted=None) -> SweepReport:
    """Run ``call(variant)`` for every ``(label, variant)`` pair and count
    traces via ``counter`` (a :class:`TraceCounter` or any object with a
    ``.count``/``.compiles`` int attribute, e.g. a ``PipelineCache``) or via
    ``jitted`` (a jit callable, read with :func:`jit_cache_size`).

    ``expected`` is the number of DISTINCT cache keys in the sweep; more
    traces than that means some variant retraced an existing key."""
    def _read() -> int:
        if jitted is not None:
            return jit_cache_size(jitted)
        for attr in ("count", "compiles"):
            v = getattr(counter, attr, None)
            if isinstance(v, int):
                return v
        raise TypeError("counter must expose .count or .compiles")

    base = _read()
    per_variant = []
    for label, variant in variants:
        call(variant)
        per_variant.append((str(label), _read() - base))
    return SweepReport(traces=_read() - base, expected=expected,
                       per_variant=tuple(per_variant))


def diagnose_drift(report: SweepReport) -> str:
    """Human-readable verdict for a failed sweep — what a contract
    violation message carries."""
    if report.ok:
        return (f"ok: {report.traces} trace(s) for "
                f"{report.expected} key(s)")
    return (f"{report.traces} traces for {report.expected} distinct key(s) "
            f"(+{report.extra} unexpected retrace(s)); first offender: "
            f"{report.first_offender()!r}. Usual causes: weak-type drift "
            "(python scalar vs jnp scalar), int->int64 promotion, a None "
            "member that became an array, or an unhashable static field.")
