"""Contract DSL + registry — declarative static invariants, declared beside
the entry points they govern and proven by ``python -m repro.launch.audit``.

A :class:`Contract` bundles:

  * a :class:`Fixture` — a LAZY builder of a concrete toy call
    (``fn``, ``args``, named dims). Lazy because contracts are declared at
    import time in hot modules (``core/query.py``, ``fit/engine.py``...);
    building toy indexes there would tax every importer. Nothing heavy runs
    until the contract is audited.
  * a list of checks from the factories below — the DSL:

      ``forbid_dims("Q", "L")``         no traced intermediate carries ALL
                                        the named dims (the compact-query
                                        [Q, L] proof)
      ``require_dims("Q", "k")``        some intermediate DOES carry the
                                        dims (non-vacuity sighting)
      ``max_intermediate_bytes(2**20)`` peak single traced intermediate
      ``max_dispatches(1)``             at most N top-level dispatch eqns
                                        (jit/pallas_call) in the trace —
                                        the fused-megakernel proof
      ``require_dtype_free(np.float32, "L", "D")``
                                        no intermediate of that dtype
                                        carries the dims (int8 store proof)
      ``require_donated(argnums=(0,))`` compiled module aliases every
                                        flattened donated leaf
                                        (input_output_alias)
      ``max_trace_count(1)``            the fixture's sweep compiles at
                                        most N distinct traces
      ``allowed_collectives({"all-gather": 4096})``
                                        compiled program emits only the
                                        named collective kinds, each within
                                        its byte bound

  * a ``control`` fixture for NEGATIVE checks (forbid_dims,
    require_dtype_free, max_intermediate_bytes): a deliberately-violating
    variant on which at least one negative check MUST fail. A negative
    proof without a failing control is vacuous — maybe the walker went
    blind, maybe the dims are wrong — so :meth:`Contract.audit` runs the
    control first and reports ``control_ok=False`` (a violation!) if the
    control unexpectedly passes. ``require_donated`` auto-generates its
    control (the same fixture re-jitted WITHOUT donation must not alias);
    ``max_trace_count`` uses its drift sweep the same way.

Registration is process-wide::

    from repro.analysis import contracts as C
    C.register(C.Contract(
        id="query.compact_no_dense_table",
        site="repro.core.query.QueryPipeline.search",
        fixture=lambda: ...,  # returns C.Fixture(...)
        checks=[C.forbid_dims("Q", "L"), C.require_dims("Q", "k")],
        control=lambda: ...,  # the dense-mode variant
    ))

and ``repro.analysis.load_all()`` imports every contract-bearing module so
the CLI and tests see one authoritative registry.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Optional

import numpy as np

from repro.analysis import hlo as _hlo
from repro.analysis import jaxpr as _jaxpr
from repro.analysis import recompile as _recompile


# ------------------------------------------------------------- fixtures ----
@dataclasses.dataclass
class Fixture:
    """One concrete toy call a contract is proven over.

    ``dims`` names the distinctive sizes (``{"Q": 6, "L": 4096}``) that
    checks reference by name — sizes chosen so no OTHER dimension collides
    with them, exactly like the in-test proofs this subsystem replaces.
    ``sweep`` is only for ``max_trace_count``: ``(call, variants, counter
    or jitted)`` per :func:`repro.analysis.recompile.sweep`.
    """
    fn: Callable
    args: tuple
    dims: dict = dataclasses.field(default_factory=dict)
    donate_argnums: tuple = ()
    static_argnums: tuple = ()
    sweep: Optional[dict] = None    # dict(call=, variants=, counter=|jitted=)

    def resolve(self, names):
        missing = [n for n in names if n not in self.dims]
        if missing:
            raise KeyError(
                f"fixture does not define dim(s) {missing}; has "
                f"{sorted(self.dims)}")
        return tuple(self.dims[n] for n in names)


# ---------------------------------------------------------------- checks ----
@dataclasses.dataclass(frozen=True)
class CheckResult:
    check: str           # e.g. 'forbid_dims(Q,L)'
    passed: bool
    detail: str = ""


@dataclasses.dataclass(frozen=True)
class Check:
    """One verifiable predicate over a fixture. ``negative`` checks are the
    ones a control fixture must be able to trip."""
    kind: str
    negative: bool
    run: Callable    # Fixture -> CheckResult
    label: str

    def __str__(self):
        return self.label


def forbid_dims(*names: str, dtype=None):
    """No traced intermediate carries ALL the named dims."""
    label = f"forbid_dims({','.join(names)}" + (
        f", dtype={np.dtype(dtype).name})" if dtype is not None else ")")

    def run(fx: Fixture) -> CheckResult:
        dims = fx.resolve(names)
        hit = _jaxpr.materializes_dims(fx.fn, fx.args, *dims, dtype=dtype)
        return CheckResult(label, not hit,
                           f"dims {dict(zip(names, dims))} "
                           + ("MATERIALIZED" if hit else "absent"))
    return Check("forbid_dims", True, run, label)


def require_dims(*names: str, dtype=None):
    """Some intermediate DOES carry the dims — the non-vacuity sighting
    that proves the walk saw the interesting part of the program."""
    label = f"require_dims({','.join(names)})"

    def run(fx: Fixture) -> CheckResult:
        dims = fx.resolve(names)
        hit = _jaxpr.materializes_dims(fx.fn, fx.args, *dims, dtype=dtype)
        return CheckResult(label, hit,
                           f"dims {dict(zip(names, dims))} "
                           + ("sighted" if hit else "NEVER SEEN (vacuous?)"))
    return Check("require_dims", False, run, label)


def max_intermediate_bytes(limit: int):
    """Largest single traced intermediate must stay under ``limit``."""
    label = f"max_intermediate_bytes({limit})"

    def run(fx: Fixture) -> CheckResult:
        rep = _jaxpr.peak_report(fx.fn, *fx.args)
        return CheckResult(
            label, rep.bytes <= limit,
            f"peak {rep.bytes}B {rep.dtype}{list(rep.shape)} "
            f"from {rep.primitive!r} (limit {limit}B)")
    return Check("max_intermediate_bytes", True, run, label)


def max_dispatches(limit: int):
    """At most ``limit`` top-level dispatch eqns (jit / pallas_call) in
    the fixture's trace — the single-launch proof of a fused pipeline.
    Negative: the control (the per-stage split of the same computation)
    must exceed the limit, or the counter went blind."""
    label = f"max_dispatches({limit})"

    def run(fx: Fixture) -> CheckResult:
        n = _jaxpr.count_dispatches(fx.fn, fx.args)
        return CheckResult(label, n <= limit,
                           f"{n} top-level dispatch(es) (limit {limit})")
    return Check("max_dispatches", True, run, label)


def require_dtype_free(dtype, *names: str):
    """No intermediate of ``dtype`` carries the named dims — e.g. the int8
    store never holds an fp32 tensor shaped by both L and D."""
    dt = np.dtype(dtype)
    label = f"require_dtype_free({dt.name}, {','.join(names)})"

    def run(fx: Fixture) -> CheckResult:
        dims = fx.resolve(names)
        hit = _jaxpr.materializes_dims(fx.fn, fx.args, *dims, dtype=dt)
        return CheckResult(label, not hit,
                           f"{dt.name} with dims {dict(zip(names, dims))} "
                           + ("MATERIALIZED" if hit else "absent"))
    return Check("require_dtype_free", True, run, label)


def require_donated(argnums: tuple = None):
    """Every flattened leaf of the donated args must appear in the compiled
    module's ``input_output_alias``. Control is AUTO-GENERATED: the same
    fixture compiled WITHOUT donation must alias none of those leaves."""
    label = f"require_donated({argnums if argnums is not None else 'fixture'})"

    def run(fx: Fixture) -> CheckResult:
        nums = tuple(argnums) if argnums is not None else fx.donate_argnums
        if not nums:
            return CheckResult(label, False,
                               "no donate_argnums on fixture or check")
        rep = _hlo.audit_donation(fx.fn, fx.args, nums,
                                  static_argnums=fx.static_argnums)
        return CheckResult(
            label, rep.ok,
            f"{len(rep.aliased)}/{len(rep.expected)} donated leaves "
            f"aliased" + (f"; MISSING flat params {list(rep.missing)}"
                          if rep.missing else ""))
    return Check("require_donated", False, run, label)


def max_trace_count(expected: int):
    """The fixture's sweep must compile at most ``expected`` distinct
    traces; any extra retrace (weak-type drift, unstable key) fails."""
    label = f"max_trace_count({expected})"

    def run(fx: Fixture) -> CheckResult:
        if not fx.sweep:
            return CheckResult(label, False, "fixture has no sweep")
        rep = _recompile.sweep(
            fx.sweep["call"], fx.sweep["variants"], expected,
            counter=fx.sweep.get("counter"), jitted=fx.sweep.get("jitted"))
        return CheckResult(label, rep.ok, _recompile.diagnose_drift(rep))
    return Check("max_trace_count", False, run, label)


def allowed_collectives(bounds: dict):
    """Compiled program may emit ONLY the collective kinds named in
    ``bounds``, each within its byte bound. ``{"all-gather": 4096}`` means:
    all-gather up to 4096 bytes, everything else zero. A bound may be a
    callable ``fixture -> int`` so caps can scale with fixture dims (e.g.
    the device count the audit actually runs under)."""
    label = "allowed_collectives(" + ",".join(
        f"{k}<={'fn' if callable(v) else v}"
        for k, v in sorted(bounds.items())) + ")"

    def run(fx: Fixture) -> CheckResult:
        prof = _hlo.collective_profile(fx.fn, fx.args, warn=False)
        bad = []
        for kind, b in sorted(prof["collectives"].items()):
            if b <= 0:
                continue
            cap = bounds.get(kind)
            cap = cap(fx) if callable(cap) else cap
            if cap is None:
                bad.append(f"{kind}={b:.0f}B (not allowed)")
            elif b > cap:
                bad.append(f"{kind}={b:.0f}B > {cap}B")
        seen = {k: v for k, v in prof["collectives"].items() if v}
        return CheckResult(
            label, not bad,
            "; ".join(bad) if bad else
            f"collective bytes {seen!r} within bounds")
    return Check("allowed_collectives", False, run, label)


# -------------------------------------------------------------- contract ----
@dataclasses.dataclass(frozen=True)
class ContractReport:
    contract_id: str
    site: str
    passed: bool
    skipped: bool
    checks: tuple            # CheckResult...
    control_ok: Optional[bool]   # None = no control applicable
    control_detail: str = ""
    peak_bytes: int = 0
    error: str = ""

    def to_dict(self) -> dict:
        return {
            "id": self.contract_id, "site": self.site,
            "passed": self.passed, "skipped": self.skipped,
            "checks": [dataclasses.asdict(c) for c in self.checks],
            "control_ok": self.control_ok,
            "control_detail": self.control_detail,
            "peak_bytes": self.peak_bytes,
            "error": self.error,
        }


@dataclasses.dataclass(frozen=True)
class Contract:
    """One named invariant: fixture + checks (+ control for negatives)."""
    id: str
    site: str                               # dotted path of the governed API
    fixture: Callable                       # () -> Fixture, lazy
    checks: tuple                           # Check...
    description: str = ""
    control: Optional[Callable] = None      # () -> Fixture, lazy
    min_devices: int = 1                    # skip (not fail) below this

    def __post_init__(self):
        object.__setattr__(self, "checks", tuple(self.checks))
        neg = [c for c in self.checks if c.negative]
        if neg and self.control is None:
            raise ValueError(
                f"contract {self.id!r} has negative check(s) "
                f"{[str(c) for c in neg]} but no control fixture — a "
                "negative proof without a failing positive control is "
                "vacuous")

    def audit(self, *, run_control: bool = True) -> ContractReport:
        """Prove the contract on its fixture; on negative contracts, first
        prove the control TRIPS at least one negative check."""
        import jax
        if jax.device_count() < self.min_devices:
            return ContractReport(
                self.id, self.site, passed=True, skipped=True, checks=(),
                control_ok=None,
                control_detail=(f"needs >= {self.min_devices} devices, "
                                f"have {jax.device_count()}"))
        try:
            fx = self.fixture()
            neg = [c for c in self.checks if c.negative]
            control_ok, control_detail = None, ""
            if run_control and neg and self.control is not None:
                cfx = self.control()
                tripped = [c.run(cfx) for c in neg]
                failing = [t for t in tripped if not t.passed]
                control_ok = bool(failing)
                control_detail = ("control tripped: " + "; ".join(
                    f"{t.check}: {t.detail}" for t in failing)
                    if failing else
                    "CONTROL PASSED ALL NEGATIVE CHECKS — proof is vacuous")
            results = tuple(c.run(fx) for c in self.checks)
            # auto-control for donation: same fn, no donation -> no alias
            don = [c for c in self.checks if c.kind == "require_donated"]
            if run_control and don and control_ok is None:
                undons = _hlo.aliased_params(
                    _hlo.compiled_text(fx.fn, fx.args,
                                       static_argnums=fx.static_argnums))
                control_ok = not undons
                control_detail = (
                    "control (re-jit without donation) aliases nothing"
                    if control_ok else
                    f"undonated compile still aliases {sorted(undons)}")
            peak = 0
            try:
                peak = _jaxpr.peak_intermediate_bytes(fx.fn, *fx.args)
            except Exception:       # sweeps etc. may not be traceable
                pass
            passed = all(r.passed for r in results) and control_ok is not False
            return ContractReport(
                self.id, self.site, passed=passed, skipped=False,
                checks=results, control_ok=control_ok,
                control_detail=control_detail, peak_bytes=peak)
        except Exception as e:      # a broken fixture is a failure, loudly
            return ContractReport(
                self.id, self.site, passed=False, skipped=False, checks=(),
                control_ok=None, error=f"{type(e).__name__}: {e}")


# -------------------------------------------------------------- registry ----
class ContractRegistry:
    """Process-wide, import-time-populated, thread-safe contract store."""

    def __init__(self):
        self._lock = threading.Lock()
        self._contracts: dict[str, Contract] = {}

    def register(self, contract: Contract) -> Contract:
        with self._lock:
            prev = self._contracts.get(contract.id)
            if prev is not None and prev.site != contract.site:
                raise ValueError(
                    f"contract id {contract.id!r} already registered for "
                    f"site {prev.site!r}")
            self._contracts[contract.id] = contract
        return contract

    def get(self, contract_id: str) -> Contract:
        with self._lock:
            try:
                return self._contracts[contract_id]
            except KeyError:
                known = sorted(self._contracts)
                raise KeyError(
                    f"unknown contract {contract_id!r}; registered: "
                    f"{known}") from None

    def ids(self) -> list:
        with self._lock:
            return sorted(self._contracts)

    def audit(self, contract_id: str, **kw) -> ContractReport:
        return self.get(contract_id).audit(**kw)

    def audit_all(self, **kw) -> list:
        return [self.get(cid).audit(**kw) for cid in self.ids()]


#: the process-wide registry every declaration site writes into
REGISTRY = ContractRegistry()


def register(contract: Contract) -> Contract:
    return REGISTRY.register(contract)


def audit(contract_id: str, **kw) -> ContractReport:
    """Audit one registered contract — the call tests assert on:
    ``assert audit("query.compact_no_dense_table").passed``."""
    return REGISTRY.audit(contract_id, **kw)
