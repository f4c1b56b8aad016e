"""Readings that a serve cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] --control-seeds <n> [<n> ...]

In one process, for each seed: set the cell up, serve its traffic for
``--seconds``, and compare a sample of the answers with the reference, as a
benchmark run does (the lower readings). For each control seed also: answer
the same sampled requests with the reference one precision step lower (the
control, see ``configs/irli_reference.py``) and compare those with the
reference (the upper readings). Prints one JSON line per seed; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    spec = H.spec()
    wl, cf = H.cell(spec, args.workload)
    tr = H.traffic(wl["traffic"])
    H.use_compile_cache()
    try:
        H.devices(wl["chips"])
    except H.NoAccelerator as e:
        H.log(f"calibrate: {e}")
        return 2
    sys.path.insert(0, os.path.join(H.CHECKOUT, "src"))
    mod = H.kind(cf)
    watch = H.CompileWatch()
    import gen
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        shard = mod.Shard(cf, seed)
        due = gen.arrivals(seed, tr["rate_qps"], args.seconds)
        rows = np.asarray(gen.query_rows(gen.sub_key(seed, gen.QUERIES),
                                         shard.base, n=len(due),
                                         metric=cf["metric"]))
        rec = mod.window(shard, rows, due, args.seconds, watch, None)
        shard.free()
        got, ctrl = mod.check(cf, shard, rows, rec["futures"], rec["ok"],
                              seed, control=seed in args.control_seeds)
        out = {"seed": seed, "unanswered": int(len(due) - rec["ok"].sum()),
               "dropped": shard.dropped, "program": got}
        if ctrl is not None:
            out["control"] = ctrl
        print(json.dumps(out), flush=True)
        del shard, rec
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
