"""What every cell shares: finding a cell's files by name, the compile cache,
the accelerator check, counting compiles, tracing, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration's file (``configs/<name>.json``) names its ``kind``, the
module ``kinds/<kind>.py`` that sets the cell up, runs its window and checks
its answers; the traffic mix is ``traffic/<name>.json``; each per-layer
metric is ``metrics/<name>.py``. A new cell, mix or metric is new files and
new entries, and no edit of a file already here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)

#: JAX's persistent compile cache when the environment names none: a fixed
#: path inside the checkout, because the directory is part of the key
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def cell(spec_: dict, name: str) -> tuple[dict, dict]:
    """(workload entry, configuration file) of a cell."""
    cells = {w["name"]: w for w in spec_["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec_["configs"]}[w["config"]]
    return w, load_json(os.path.join(CHECKOUT, conf["file"]))


def traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def load_module(path: str):
    """Import a file by path (metric readers' names hold dots)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace(
        "-", "_")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def kind(conf_file: dict):
    return load_module(os.path.join(BENCH, "kinds", f"{conf_file['kind']}.py"))


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", f"{name}.py"))


def cell_metrics(spec_: dict, workload: str, per_layer: bool) -> list:
    """The metrics a cell reports: its end-to-end metrics, or with a trace
    its per-layer metrics (those naming the cell, or naming no cells and
    moving an end-to-end metric the cell reports)."""
    e2e = [m for m in spec_["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not per_layer:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in spec_["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else in the checkout; cache every program, so that only a
    checkout's first run of a cell compiles."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def devices(chips: int, require_tpu: bool = True) -> list:
    """The chips the cell runs on; raises NoAccelerator without them."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r})")
    if require_tpu and len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


class CompileWatch:
    """Counts XLA compiles (persistent-cache misses) and traces while
    ``active``; a window that compiles shows here."""

    def __init__(self):
        import jax.monitoring as mon
        self.active = False
        self.compiles = 0
        self.traces = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.active and name == "/jax/compilation_cache/cache_misses":
            self.compiles += 1

    def _duration(self, name, _secs, **_):
        if self.active and name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


def peak_memory(devs) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def trace_dir(workload: str) -> str:
    """Where a cell's traced run writes its trace; one per cell is kept."""
    return os.path.join(BENCH, ".traces", workload)


def start_trace(path: str) -> None:
    import shutil

    import jax
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # Python frames would swamp it
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)


def stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class Clock:
    """Set-up starts when the run does."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def since(self) -> float:
        return time.perf_counter() - self.t0
