"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix come from ``BENCHMARK.json``
at the checkout's root (see ``harness.py`` for how files are found by
name). The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with a trace a
``breakdown``, and last ``checks``: each number that decided ``correct``
beside its limit, which also end standard error. Without a TPU, or with
fewer chips than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, conf_file: dict | None = None,
             traffic: dict | None = None, breakage=None) -> dict:
    """One run of one cell -> the result object. The keyword arguments are
    for tests: run on the CPU, at another size, or with the program broken
    underneath (``breakage``, a context manager, wraps the whole run)."""
    clock = H.Clock()
    spec = H.spec()
    wl, cf = H.cell(spec, workload)
    cf = conf_file if conf_file is not None else cf
    tr = traffic if traffic is not None else H.traffic(wl["traffic"])
    H.use_compile_cache()
    devs = H.devices(wl["chips"], require_tpu)
    sys.path.insert(0, os.path.join(H.CHECKOUT, "src"))
    with breakage() if breakage else contextlib.nullcontext():
        out = H.kind(cf).run(cf, tr, seed=seed, seconds=seconds, trace=trace,
                             devs=devs, clock=clock, workload=workload)
    metrics = {}
    for m in H.cell_metrics(spec, workload, per_layer=trace):
        if trace:
            value = H.metric_reader(m["name"]).read(out["record"])
        else:
            value = out["end_to_end"].get(m["name"])
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {**H.device_info(devs),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and out["record"].get("trace"):
        red = out["record"]["trace"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except H.NoAccelerator as e:
        H.log(f"bench: {e}")
        return 2
    for name, c in result["checks"].items():
        H.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
