import os
# fake CPU devices only: pinned before jax starts, so this never takes a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8"
                           ).strip()

"""Static-contract audit CLI — the CI gate over repro.analysis.

Runs every contract registered beside the repo's entry points (compact
query, quantized store, fit round donation/retrace, mesh collectives, each
kernel dispatch site) over their concrete toy fixtures, REQUIRING each
negative contract's positive control to trip (no vacuous proofs), writes
``artifacts/ANALYSIS.json``, records ``analysis_peak_bytes{contract=...}``
rows into the longitudinal trajectory (artifacts/TRAJECTORY.jsonl, unit
"bytes" — gated the same way latency is), and exits nonzero on any
violation. MUST run as a module (the 8 fake host devices above let the mesh
contracts run on CPU; set before jax init):

    PYTHONPATH=src python -m repro.launch.audit                  # everything
    PYTHONPATH=src python -m repro.launch.audit --contract query.compact_no_dense_table
    PYTHONPATH=src python -m repro.launch.audit --list
    PYTHONPATH=src python -m repro.launch.audit --seed-violation dense_table

``--seed-violation {dense_table,drop_donation,extra_retrace,
split_dispatch}`` registers a deliberately-violating contract and audits it
alone — the self-test that each analyzer actually detects the regression
class it guards against (asserted by tests/test_analysis.py via
subprocess).
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

ART = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts")


# ------------------------------------------------------- seeded violations --
def _seed_dense_table():
    """A pipeline that DOES build the [Q, L] table, registered under the
    compact contract's checks — the jaxpr walker must fail it."""
    from repro.analysis import contracts as C

    def fixture():
        from repro.analysis import fixtures as FX
        return FX.query_search("dense")

    return C.Contract(
        id="seeded.dense_table",
        site="repro.launch.audit --seed-violation dense_table",
        description="deliberate violation: dense mode under the compact "
                    "no-[Q, L] contract",
        fixture=fixture,
        checks=[C.forbid_dims("Q", "L")],
        control=fixture,
    )


def _seed_drop_donation():
    """A fit-round-shaped update whose output CANNOT alias its donated
    input (shape changes) — the HLO donation auditor must fail it, the way
    it would a refactor that broke the FitState double-buffer guarantee."""
    from repro.analysis import contracts as C
    from repro.analysis.contracts import Fixture

    def fixture():
        s = jnp.zeros((64,), jnp.float32)

        def fn(state, g):
            # output [128] can never alias the donated [64] input
            return jnp.concatenate([state + g, state - g])
        return Fixture(fn=fn, args=(s, s), donate_argnums=(0,))

    return C.Contract(
        id="seeded.drop_donation",
        site="repro.launch.audit --seed-violation drop_donation",
        description="deliberate violation: donation requested but the "
                    "compiled module aliases nothing",
        fixture=fixture,
        checks=[C.require_donated()],
    )


def _seed_extra_retrace():
    """A weak-type drift sweep (python float, then jnp.float32 scalar) that
    retraces a jitted fn under one logical key — the recompile detector
    must fail it."""
    from repro.analysis import contracts as C
    from repro.analysis.contracts import Fixture

    def fixture():
        jitted = jax.jit(lambda x, s: x * s)
        x = jnp.ones((8,), jnp.float32)
        variants = [("python-float", 2.0),
                    ("jnp-float32-scalar", jnp.float32(2.0))]
        return Fixture(
            fn=lambda: jnp.zeros(()), args=(),
            sweep={"call": lambda s: jax.block_until_ready(jitted(x, s)),
                   "variants": variants, "jitted": jitted})

    return C.Contract(
        id="seeded.extra_retrace",
        site="repro.launch.audit --seed-violation extra_retrace",
        description="deliberate violation: weak-type drift retraces one "
                    "logical cache key",
        fixture=fixture,
        checks=[C.max_trace_count(1)],
    )


def _seed_split_dispatch():
    """The compact query path run as six separate stage jits — six
    top-level dispatches under the megakernel's single-dispatch contract.
    The dispatch counter must fail it, the way it would a refactor that
    quietly hoisted a stage back out of the fused mega path."""
    from repro.analysis import contracts as C

    def fixture():
        from repro.analysis import fixtures as FX
        return FX.mega_split_control()

    return C.Contract(
        id="seeded.split_dispatch",
        site="repro.launch.audit --seed-violation split_dispatch",
        description="deliberate violation: per-stage dispatch sequence "
                    "under the mega single-dispatch contract",
        fixture=fixture,
        checks=[C.max_dispatches(1)],
        control=fixture,
    )


SEEDED = {"dense_table": _seed_dense_table,
          "drop_donation": _seed_drop_donation,
          "extra_retrace": _seed_extra_retrace,
          "split_dispatch": _seed_split_dispatch}


# ---------------------------------------------------------------- reporting --
def _print_report(r) -> None:
    status = ("SKIP" if r.skipped else "PASS" if r.passed else "FAIL")
    print(f"[{status}] {r.contract_id}  ({r.site})")
    if r.skipped:
        print(f"       {r.control_detail}")
        return
    if r.error:
        print(f"       fixture error: {r.error}")
    for c in r.checks:
        print(f"       {'ok ' if c.passed else 'BAD'} {c.check}: {c.detail}")
    if r.control_ok is not None:
        print(f"       {'ok ' if r.control_ok else 'BAD'} "
              f"control: {r.control_detail}")
    if r.peak_bytes:
        print(f"       peak intermediate: {r.peak_bytes} bytes")


def _record_trajectory(reports, path=None) -> None:
    """analysis_peak_bytes{contract=...} rows, unit='bytes' — the
    longitudinal gate then catches future memory regressions exactly like
    latency ones (benchmarks/trajectory.py)."""
    try:
        from benchmarks import trajectory
    except ImportError:     # not running from the repo root: skip quietly
        return
    rows = [(f"analysis_peak_bytes{{contract={r.contract_id}}}",
             r.peak_bytes, None)
            for r in reports if not r.skipped and r.peak_bytes > 0]
    if rows:
        trajectory.record("analysis", rows, unit="bytes", path=path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.launch.audit",
        description="prove every registered static contract (memory, "
                    "donation, recompile, collectives); nonzero exit on "
                    "any violation")
    ap.add_argument("--contract", action="append", default=None,
                    help="audit only this contract id (repeatable)")
    ap.add_argument("--list", action="store_true",
                    help="list registered contract ids and exit")
    ap.add_argument("--no-control", action="store_true",
                    help="skip the positive-control runs (faster, but "
                    "negative proofs are then unverified)")
    ap.add_argument("--json", default=os.path.join(ART, "ANALYSIS.json"),
                    help="report path (default artifacts/ANALYSIS.json)")
    ap.add_argument("--no-trajectory", action="store_true",
                    help="do not append analysis_peak_bytes rows to "
                    "artifacts/TRAJECTORY.jsonl")
    ap.add_argument("--seed-violation", choices=sorted(SEEDED),
                    help="register a deliberately-violating contract and "
                    "audit it alone (must exit nonzero — analyzer "
                    "self-test)")
    args = ap.parse_args(argv)

    from repro.analysis import REGISTRY, load_all
    load_all()

    if args.list:
        for cid in REGISTRY.ids():
            print(cid)
        return 0

    if args.seed_violation:
        contract = SEEDED[args.seed_violation]()
        REGISTRY.register(contract)
        ids = [contract.id]
    elif args.contract:
        ids = list(args.contract)
    else:
        ids = REGISTRY.ids()

    t0 = time.time()
    reports = []
    for cid in ids:
        reports.append(REGISTRY.audit(cid,
                                      run_control=not args.no_control))
        _print_report(reports[-1])

    n_pass = sum(r.passed and not r.skipped for r in reports)
    n_skip = sum(r.skipped for r in reports)
    n_fail = sum(not r.passed for r in reports)
    ok = n_fail == 0

    out = {
        "ts": time.time(),
        "n_devices": jax.device_count(),
        "jax_version": jax.__version__,
        "seconds": round(time.time() - t0, 2),
        "passed": ok,
        "n_pass": n_pass, "n_skip": n_skip, "n_fail": n_fail,
        "contracts": [r.to_dict() for r in reports],
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
    with open(args.json, "w", encoding="utf-8") as fh:
        json.dump(out, fh, indent=1)
    if not args.no_trajectory and not args.seed_violation:
        _record_trajectory(reports)

    print(f"\naudit: {n_pass} passed, {n_skip} skipped, {n_fail} failed "
          f"({out['seconds']}s, {jax.device_count()} devices, "
          f"jax {jax.__version__}) -> {args.json}")
    if args.seed_violation and ok:
        print("SEEDED VIOLATION WAS NOT DETECTED — analyzer is blind",
              file=sys.stderr)
        return 2
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
