"""Sweep offered rates on one set-up to find a serve cell's knee: the
highest rate at which answers keep pace with arrivals for a whole window.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates <q/s> [<q/s> ...]

Prints one JSON line per rate: requests offered, answered inside the
window, left unanswered when it closed, and the p50 and p99 latency from
each request's due time. The benchmark's own runs never run this; the
rates it finds are fixed in the traffic files.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    wl, cf = H.cell(H.spec(), args.workload)
    H.use_compile_cache()
    try:
        H.devices(wl["chips"])
    except H.NoAccelerator as e:
        H.log(f"sweep: {e}")
        return 2
    sys.path.insert(0, os.path.join(H.CHECKOUT, "src"))
    mod = H.kind(cf)
    import gen
    shard = mod.Shard(cf, args.seed)
    watch = H.CompileWatch()
    for rate in args.rates:
        due = gen.arrivals(args.seed, rate, args.seconds)
        rows = np.asarray(gen.query_rows(gen.sub_key(args.seed, gen.QUERIES),
                                         shard.base, n=len(due),
                                         metric=cf["metric"]))
        rec = mod.window(shard, rows, due, args.seconds, watch, None)
        lat = rec["latency_s"] * 1e3
        print(json.dumps({
            "rate_qps": rate, "offered": len(due),
            "answered_in_window": rec["completed_in_window"],
            "unanswered_at_close": len(due) - rec["completed_in_window"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "mean_batch_fill": rec["server"]["serve_batch_fill"][0]
            / max(rec["server"]["serve_batch_fill"][1], 1),
            "compiles": watch.compiles}), flush=True)
    shard.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
