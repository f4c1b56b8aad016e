"""Readings that a sharded cell's correctness limits are set from, with the
cell's own result for each seed.

    python3 bench/calibrate_sharded.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--trace-seeds <n> ...] [--out <file>]

In one process, for each seed: one run of the cell exactly as
``run.py`` makes it (set-up, window, check; traced for ``--trace-seeds``),
and besides the comparison of the sampled answers with the reference (the
lower readings), the same sampled queries answered by the reference one
precision step lower and compared with it (the control, the upper
readings; see ``configs/irli_reference_sharded.py``). Prints one JSON line
per seed, also appended to ``--out``: the result line ``run.py`` would
print, the seed, the control's readings, and for a traced seed each chip's
busy time and device time by scope (``mesh_trace.py``). A seed whose run
raises is logged with its traceback and ends the readings (exit code 1):
a fault of the program or the cell repeats on every seed, and four chips
are dear. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    spec = H.spec()
    wl, cf = H.cell(spec, args.workload)
    tr = H.traffic(wl["traffic"])
    H.use_compile_cache()
    try:
        devs = H.devices(wl["chips"])
    except H.NoAccelerator as e:
        H.log(f"calibrate_sharded: {e}")
        return 2
    sys.path.insert(0, os.path.join(H.CHECKOUT, "src"))
    mod = H.kind(cf)
    import mesh_trace

    def line_of(seed: int) -> dict:
        trace = seed in args.trace_seeds
        out = mod.run(cf, tr, seed=seed, seconds=args.seconds, trace=trace,
                      devs=devs, clock=H.Clock(), workload=args.workload,
                      control=True)
        metrics = {}
        for m in H.cell_metrics(spec, args.workload, per_layer=False):
            metrics[m["name"]] = out["end_to_end"].get(m["name"])
        if trace:
            for m in H.cell_metrics(spec, args.workload, per_layer=True):
                value = H.metric_reader(m["name"]).read(out["record"])
                if value is not None and math.isfinite(value):
                    metrics[m["name"]] = value
        line = {"seed": seed, "correct": out["correct"],
                "attempted": out["attempted"], "failed": out["failed"],
                "metrics": metrics, "checks": out["checks"],
                "control": out["control"],
                "device": {**H.device_info(devs),
                           "memory_peak_bytes": out["memory_peak_bytes"]}}
        if trace and out["record"]["trace"]:
            red = out["record"]["trace"]
            line["device"].update(busy_s=red["busy_s"],
                                  window_s=red["window_s"])
            line["breakdown"] = {"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]}
            line["chips"] = mesh_trace.read(out["record"])
        return line

    for seed in args.seeds:
        try:
            text = json.dumps(line_of(seed))
        except Exception:
            H.log(f"calibrate_sharded: seed {seed} failed\n"
                  + traceback.format_exc())
            return 1
        finally:
            gc.collect()
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
