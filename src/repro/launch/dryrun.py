import os
# fake CPU devices only: pinned before jax starts, so this never takes a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and record memory/cost/collective analysis.

MUST be run as a script/module (sets XLA device count before jax init):

    PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch gemma-7b
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi          # all

Results are cached incrementally in artifacts/dryrun_<mesh>.json so repeated
invocations only compile missing cells (--force recompiles).
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCHS, get_arch
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_opt, opt_state_specs

ART = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts")

# HLO collective ops we bill to the interconnect (operand bytes)
_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)\[([\d,]*)\]")

_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "f64": 8}


def _shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of collective ops in (scheduled) HLO text."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _COLL_RE.search(line.split("=")[1].split("(")[0]) if "=" in line else None
        if not m:
            continue
        if "-start" in line and "-done" not in line:
            pass  # count starts; done lines carry no new bytes
        elif "-done" in line:
            continue
        kind = m.group(1)
        # operand bytes: shapes on the lhs of '=' describe the RESULT; use
        # result bytes as the wire proxy (AG result > operand; RS result <).
        lhs = line.split("=")[0]
        out[kind] += _shape_bytes(lhs)
        out["count"] += 1
    return out


def lower_cell(arch_name: str, shape: str, mesh):
    arch = get_arch(arch_name)
    cell = arch.cells[shape]
    if cell.skip:
        return {"status": "skip", "reason": cell.skip}

    params_sds = cell.params(mesh) if cell.params else arch.abstract_params()
    if cell.param_specs is not None:
        pspecs = cell.param_specs(mesh, params_sds)
    else:
        pspecs = arch.rules().specs(params_sds)

    inputs_sds = cell.inputs(mesh)
    in_specs = cell.in_specs(mesh)

    def named(tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            tree, is_leaf=lambda x: isinstance(x, P))

    step = cell.step(mesh) if cell.step_with_mesh else cell.step()

    if cell.kind == "train":
        opt = make_opt(arch.opt, **arch.opt_kw)
        opt_sds = jax.eval_shape(opt.init, params_sds)
        opt_specs = opt_state_specs(arch.opt, params_sds, pspecs, mesh)
        state_sds = {"params": params_sds, "opt": opt_sds}
        state_specs = {"params": named(pspecs), "opt": named(opt_specs)}
        fn = jax.jit(step,
                     in_shardings=(state_specs, named(in_specs)),
                     out_shardings=(state_specs, None),
                     donate_argnums=(0,))
        lowered = fn.lower(state_sds, inputs_sds)
    else:
        # decode-style cells update their KV caches in place: donate them so
        # the cache isn't double-buffered (14.8 -> ~7.4 GiB on gemma decode).
        donate = (1,) if "caches" in inputs_sds else ()
        fn = jax.jit(step, in_shardings=(named(pspecs), named(in_specs)),
                     donate_argnums=donate)
        lowered = fn.lower(params_sds, inputs_sds)

    return {"status": "lowered", "lowered": lowered}


def analyze(lowered, want_hlo: bool = True):
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    rec = {"compile_s": round(compile_s, 1)}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            rec[k] = getattr(mem, k, None)
    if cost:
        rec["flops"] = cost.get("flops")
        rec["bytes_accessed"] = cost.get("bytes accessed")
        rec["transcendentals"] = cost.get("transcendentals")
    if want_hlo:
        try:
            txt = compiled.as_text()
        except Exception:
            txt = lowered.as_text()
        rec["collectives"] = collective_bytes(txt)
        rec["hlo_lines"] = txt.count("\n")
        # trip-count-corrected roofline inputs (see repro.analysis.hlo):
        # raw cost_analysis counts while bodies ONCE; scan-heavy programs
        # under-count 30-200x without this.
        try:
            from repro.analysis.hlo import analyze_hlo
            rec["corrected"] = analyze_hlo(txt)
        except Exception as e:  # parser must never fail the dry-run
            rec["corrected"] = {"error": f"{type(e).__name__}: {e}"}
    return rec


def check_store_accounting(rec: dict, n_shards: int) -> dict:
    """Per-shard byte accounting for irli-deep1b/serve_query: the compiled
    cell's arguments must carry int8 CODE bytes, not fp32 vectors.

    Returns the accounting dict (also stashed on the result record);
    raises if the compiled argument footprint could only be explained by a
    fp32 base payload. ``argument_size_in_bytes`` may be reported globally
    or per-device depending on the backend, so the assertion brackets both:
    it must not exceed the GLOBAL int8-store argument total, and the int8
    payload itself must beat fp32 by >= 3x (pure config math)."""
    from repro.configs.irli_deep1b import serve_store_bytes
    acct = serve_store_bytes(n_shards)
    ratio = acct["fp32_per_shard"] / acct["int8_per_shard"]
    if ratio < 3.0:
        raise AssertionError(
            f"store accounting: int8 payload only {ratio:.2f}x smaller "
            "than fp32 — the serve cell is not declaring code bytes")
    args = rec.get("argument_size_in_bytes")
    if args is not None:
        # global args = store + members + scorer + queries; a fp32 base
        # would blow past this bound by ~n_shards * (fp32 - int8) bytes
        # (~37 GB at P=512). Slack covers the replicated scorer (w2 alone
        # is R*H*B*4 ≈ 2.45 GiB) + queries + alignment.
        slack = 4 << 30
        global_budget = n_shards * (acct["int8_per_shard"]
                                    + acct["members_per_shard"]) + slack
        if args > global_budget:
            raise AssertionError(
                f"store accounting: compiled argument bytes {args} exceed "
                f"the int8-store budget {global_budget} — fp32 vectors "
                "are back in the serve arguments")
    rec["store_accounting"] = dict(acct, fp32_over_int8=round(ratio, 2))
    return acct


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    os.makedirs(ART, exist_ok=True)
    out_path = args.out or os.path.join(ART, f"dryrun_{args.mesh}.json")
    results = {}
    if os.path.exists(out_path):   # --force re-runs selected cells but never
        with open(out_path) as f:  # discards other cells' cached results
            results = json.load(f)

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    print(f"mesh: {mesh.devices.shape} axes={mesh.axis_names} "
          f"devices={len(jax.devices())}", flush=True)

    cells = []
    for name in ([args.arch] if args.arch else list(ARCHS)):
        arch = get_arch(name)
        for shape in (
                [args.shape] if args.shape else list(arch.cells)):
            cells.append((name, shape))

    for name, shape in cells:
        key = f"{name}/{shape}"
        if key in results and results[key].get("status") in ("ok", "skip") \
                and not args.force:
            print(f"[cache] {key}: {results[key]['status']}", flush=True)
            continue
        print(f"[lower] {key} ...", flush=True)
        t0 = time.time()
        try:
            with jax.set_mesh(mesh):
                r = lower_cell(name, shape, mesh)
                if r["status"] == "skip":
                    results[key] = {"status": "skip", "reason": r["reason"]}
                    print(f"[skip]  {key}: {r['reason']}", flush=True)
                else:
                    rec = analyze(r["lowered"])
                    if name == "irli-deep1b" and shape == "serve_query":
                        check_store_accounting(rec, len(jax.devices()))
                    rec["status"] = "ok"
                    rec["lower_s"] = round(time.time() - t0 - rec["compile_s"], 1)
                    results[key] = rec
                    print(f"[ok]    {key}: compile={rec['compile_s']}s "
                          f"flops={rec.get('flops'):.3g} "
                          f"temp={rec.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
                          f"coll={rec['collectives']['count']}", flush=True)
        except Exception as e:
            results[key] = {"status": "error",
                            "error": f"{type(e).__name__}: {e}",
                            "trace": traceback.format_exc()[-2000:]}
            print(f"[FAIL]  {key}: {type(e).__name__}: {e}", flush=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)

    n_ok = sum(1 for v in results.values() if v["status"] == "ok")
    n_skip = sum(1 for v in results.values() if v["status"] == "skip")
    n_err = sum(1 for v in results.values() if v["status"] == "error")
    print(f"done: {n_ok} ok / {n_skip} skip / {n_err} error -> {out_path}",
          flush=True)
    sys.exit(1 if n_err else 0)


if __name__ == "__main__":
    main()
