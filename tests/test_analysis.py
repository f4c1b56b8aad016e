"""repro.analysis coverage: contract DSL + registry semantics (vacuous
controls, negative-without-control rejection, min_devices skip), the
recompile detector (weak-type drift; PipelineCache compiles once per key),
and the audit CLI's seeded self-violations — each analyzer must detect the
regression class it guards against, asserted via subprocess exit codes."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (Contract, ContractRegistry, Fixture, audit,
                            forbid_dims, load_all, max_trace_count,
                            require_dims)
from repro.analysis import recompile as RC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fixture(fn=None, args=None, dims=None, **kw):
    if fn is None:
        fn = lambda x: x + 1.0
        args = (jnp.zeros((3,), jnp.float32),)
    return Fixture(fn=fn, args=args, dims=dims or {}, **kw)


# ------------------------------------------------------------ DSL/registry --
def test_negative_check_requires_control():
    """A forbid_* contract without a positive control is vacuous by
    construction and must be rejected at declaration time."""
    with pytest.raises(ValueError, match="vacuous"):
        Contract(id="t.neg", site="tests", fixture=lambda: _fixture(),
                 checks=[forbid_dims("Q", "L")])


def test_registry_rejects_id_collision_across_sites():
    reg = ContractRegistry()
    mk = lambda site: Contract(id="t.dup", site=site,
                               fixture=lambda: _fixture(),
                               checks=[max_trace_count(1)])
    reg.register(mk("site.a"))
    reg.register(mk("site.a"))          # same site: idempotent re-import
    with pytest.raises(ValueError, match="already registered"):
        reg.register(mk("site.b"))


def test_registry_unknown_id_lists_known():
    reg = ContractRegistry()
    with pytest.raises(KeyError, match="unknown contract"):
        reg.get("t.nope")


def test_vacuous_control_fails_audit():
    """A control that passes every negative check proves nothing; the audit
    itself must fail, not silently bless the contract."""
    fx = lambda: _fixture(dims={"Q": 3, "L": 7})    # never builds [3, 7]
    c = Contract(id="t.vacuous", site="tests", fixture=fx,
                 checks=[forbid_dims("Q", "L")], control=fx)
    r = c.audit()
    assert r.control_ok is False
    assert not r.passed
    assert "vacuous" in r.control_detail


def test_control_trips_makes_audit_pass():
    def dense():
        f = lambda x: jnp.broadcast_to(x[:, None], (3, 7)) * 2.0
        return _fixture(fn=f, args=(jnp.zeros((3,), jnp.float32),),
                        dims={"Q": 3, "L": 7})
    c = Contract(id="t.real", site="tests",
                 fixture=lambda: _fixture(dims={"Q": 3, "L": 7}),
                 checks=[forbid_dims("Q", "L")], control=dense)
    r = c.audit()
    assert r.passed and r.control_ok, r.to_dict()


def test_min_devices_skips_not_fails():
    c = Contract(id="t.devices", site="tests", fixture=lambda: _fixture(),
                 checks=[max_trace_count(1)], min_devices=4097)
    r = c.audit()
    assert r.skipped and r.passed
    assert "devices" in r.control_detail


def test_broken_fixture_is_loud_failure():
    def boom():
        raise RuntimeError("fixture exploded")
    c = Contract(id="t.broken", site="tests", fixture=boom,
                 checks=[max_trace_count(1)])
    r = c.audit()
    assert not r.passed and r.error and "fixture exploded" in r.error


# ------------------------------------------------------ recompile detector --
def test_sweep_catches_weak_type_drift():
    """The canonical cache-key bug: a python float then a jnp.float32
    scalar retrace ONE logical key — result identical, trace count not."""
    jitted = jax.jit(lambda x, s: x * s)
    x = jnp.ones((8,), jnp.float32)
    rep = RC.sweep(lambda s: jax.block_until_ready(jitted(x, s)),
                   [("python-float", 2.0),
                    ("jnp-float32-scalar", jnp.float32(2.0))],
                   expected=1, jitted=jitted)
    assert not rep.ok and rep.extra == 1
    assert rep.first_offender() == "jnp-float32-scalar"
    assert "weak-type" in RC.diagnose_drift(rep)


def test_sweep_ok_on_stable_keys():
    jitted = jax.jit(lambda x: x * 2.0)
    rep = RC.sweep(
        lambda v: jax.block_until_ready(jitted(v)),
        [("a", jnp.ones((4,), jnp.float32)),
         ("b", jnp.zeros((4,), jnp.float32)),        # same key: no retrace
         ("wider", jnp.ones((8,), jnp.float32))],    # new shape: one more
        expected=2, jitted=jitted)
    assert rep.ok and rep.traces == 2
    assert "ok" in RC.diagnose_drift(rep)


def test_trace_counter_ticks_per_trace_not_per_call():
    tc = RC.TraceCounter(lambda x: x + 1.0)
    jitted = jax.jit(tc)
    for _ in range(3):
        jitted(jnp.zeros((4,), jnp.float32))
    jitted(jnp.zeros((6,), jnp.float32))
    assert tc.count == 2


def test_pipeline_cache_compiles_once_per_key():
    """The registered contract over the real serving PipelineCache: 4
    distinct (params, topC, mode) keys, each swept twice, exactly 4
    compiles."""
    load_all()
    r = audit("search.cache_compiles_once")
    assert r.passed, r.to_dict()


# ------------------------------------------------- audit CLI self-violation --
@pytest.mark.parametrize("seed",
                         ["dense_table", "drop_donation", "extra_retrace",
                          "split_dispatch"])
def test_seeded_violation_detected(seed, tmp_path):
    """`--seed-violation X` registers a deliberately broken program; the
    audit MUST exit 1 (exit 2 would mean the analyzer is blind, exit 0
    that the violation wasn't even flagged)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), env.get("PYTHONPATH", "")]).rstrip(
            os.pathsep)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.audit",
         "--seed-violation", seed, "--no-trajectory",
         "--json", str(tmp_path / "ANALYSIS.json")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    assert out.returncode == 1, (seed, out.returncode,
                                 out.stdout[-2000:], out.stderr[-2000:])
    assert "[FAIL] seeded." in out.stdout


# --------------------------------------------------------- dispatch counter --
def test_count_dispatches_sees_jit_eqns():
    """One jitted call is one top-level dispatch; N sibling jits are N. The
    counter reads the primitive name the installed jax gives a jit eqn, so
    a rename shows up here instead of as a vacuous contract."""
    from repro.analysis.jaxpr import count_dispatches
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.zeros((4,), jnp.float32)
    assert count_dispatches(f, (x,)) == 1
    for n in (2, 3):
        fs = [jax.jit(lambda x, i=i: x + i) for i in range(n)]

        def siblings(x, fs=fs):
            return [g(x) for g in fs]

        assert count_dispatches(siblings, (x,)) == n
    # an un-jitted op sequence launches nothing of its own
    assert count_dispatches(lambda x: x * 2.0 + 1.0, (x,)) == 0
