"""Cells that serve one IRLI shard through ``IRLIServer`` under open-loop
load.

Set-up (timed as ``setup_s``): the corpus, the scorer weights and the
partition are made on the device from the seed (``gen``, ``shard_index``);
an ``IRLIIndex`` takes the weights and the partition and builds its member
matrix; the corpus is encoded into the int8 store; an ``IRLIServer`` with
its defaults serves it; every pad bucket the server can form is warmed up.

Window: the traffic's arrival schedule and query rows are made before it
starts; inside it the generator's only work per request is one ``submit``
at the request's due time. Each request is timed from when it was due.
After the window closes every request is waited for (a minute at most),
the program's state is freed, and a sample of the answers drawn from the
seed is compared with the plain reference (``configs/irli_reference.py``).
"""
from __future__ import annotations

import concurrent.futures
import functools
import gc
import os
import time

import numpy as np

import gen
import harness as H
import shard_index
import trace_reduce
import work


#: corpus rows the scorer's bucket biases are centred over
CENTRE_ROWS = 4096


def _reference_module():
    return H.load_module(os.path.join(H.BENCH, "configs",
                                      "irli_reference.py"))


class Shard:
    """One shard's data (the benchmark's) and its served index (the
    program's)."""

    def __init__(self, cfg: dict, seed: int):
        import jax
        from repro.core import query as Q
        from repro.core.index import IRLIConfig, IRLIIndex
        from repro.core.search_api import SearchParams
        from repro.serve.server import IRLIServer
        from repro.store import encode

        L, B, R = cfg["n_corpus"], cfg["buckets"], cfg["reps"]
        self.cfg = cfg
        self.phases = {}
        clock = time.perf_counter()

        def phase(name, *arrays):
            nonlocal clock
            jax.block_until_ready(arrays)
            now = time.perf_counter()
            self.phases[name] = now - clock
            clock = now

        self.base = gen.corpus(gen.sub_key(seed, gen.CORPUS), n=L,
                               d=cfg["d"], n_clusters=L // cfg["cluster_rows"],
                               metric=cfg["metric"])
        self.weights = shard_index.centre(
            gen.scorer_weights(gen.sub_key(seed, gen.WEIGHTS), R=R,
                               d=cfg["d"], H=cfg["hidden"], B=B),
            self.base[:CENTRE_ROWS])
        phase("data", self.base, self.weights)
        top = shard_index.affinity_topk(self.weights, self.base,
                                        K=cfg["power_of_k"],
                                        chunk=cfg["affinity_chunk"])
        phase("affinity", top)
        self.assign = shard_index.power_of_k(
            top, B=B, K=cfg["power_of_k"],
            key=gen.sub_key(seed, gen.PARTITION))
        del top
        phase("power_of_k", self.assign)
        icfg = IRLIConfig(d=cfg["d"], n_labels=L, n_buckets=B, n_reps=R,
                          d_hidden=cfg["hidden"], K=cfg["power_of_k"],
                          max_load_slack=cfg["member_width"] / (L // B),
                          seed=seed & 0x7FFFFFFF)
        self.index = IRLIIndex(icfg)
        phase("index_init", self.index.params, self.index.opt_state)
        self.index.params = self.weights
        self.index.assign = self.assign
        self.index.build_index()
        if self.index.index.max_load != cfg["member_width"]:
            raise ValueError(f"member width {self.index.index.max_load} != "
                             f"{cfg['member_width']}")
        self.dropped = self.index.index.dropped
        self.store = encode(self.base, cfg["store"], cfg["store_block"])
        phase("members_store", self.index.index.members, self.store.codes)
        self.params = SearchParams(
            m=cfg["m"], tau=cfg["tau"], k=cfg["k"], topC=cfg["topC"],
            refine_k=cfg["refine_k"], metric=cfg["metric"],
            store_dtype=cfg["store"])
        self.server = IRLIServer(self.index, params=self.params,
                                 base=self.store)
        # every pad bucket, through the calls the batcher makes
        warm = np.asarray(gen.query_rows(gen.sub_key(seed, gen.SAMPLE),
                                         self.base, n=self.server.max_batch,
                                         metric=cfg["metric"]))
        for b in self.server.buckets:
            res = self.index.search(warm[:b], self.store, self.params,
                                    cache=self.server.cache)
            np.asarray(res.ids)
            np.asarray(Q.probe_buckets(self.index.params, warm[:b],
                                       self.params.m))
        phase("warm_up")
        self.width = cfg["reps"] * cfg["m"] * cfg["member_width"]

    def registry(self) -> dict:
        """The server's own counters, as sums and counts."""
        reg = self.server.registry
        out = {}
        for name in ("serve_queue_wait_seconds", "serve_batch_seconds",
                     "serve_batch_fill"):
            s = reg.histogram(name).snapshot()
            out[name] = (s["sum"], s["count"])
        for name in ("serve_requests_total", "serve_pad_waste_total",
                     "serve_batches_total"):
            out[name] = reg.counter(name).value
        return out

    def free(self) -> None:
        """Close the server and drop everything the program holds."""
        self.server.close()
        del self.server, self.index, self.store
        gc.collect()


def _delta(after: dict, before: dict) -> dict:
    return {k: (tuple(a - b for a, b in zip(after[k], before[k]))
                if isinstance(after[k], tuple) else after[k] - before[k])
            for k in after}


def offer(server, rows: np.ndarray, due: np.ndarray, t0: float):
    """Submit ``rows[i]`` at ``t0 + due[i]`` (open loop). Returns the
    futures, submit times and done times (filled by each future's
    callback; NaN until it completes)."""
    n = len(due)
    submitted = np.empty(n)
    done = np.full(n, np.nan)

    def finished(i, _fut):
        done[i] = time.perf_counter()

    futs = []
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        submitted[i] = time.perf_counter()
        f = server.submit(rows[i])
        f.add_done_callback(functools.partial(finished, i))
        futs.append(f)
    return futs, submitted, done


def window(shard: Shard, rows, due, seconds: float, watch, trace: str | None,
           drain_s: float = 60.0) -> dict:
    """Offer the schedule, wait for every answer, and collect what the
    client and the server saw, with how late the generator ran (p99 of
    submit time minus due time)."""
    gc.collect()
    gc.freeze()
    before = shard.registry()
    if trace:
        H.start_trace(trace)
    import jax
    t0 = time.perf_counter() + 0.05
    watch.active = True
    with jax.profiler.TraceAnnotation("bench.window"):
        futs, submitted, done = offer(shard.server, rows, due, t0)
        concurrent.futures.wait(futs, timeout=max(
            0.0, t0 + seconds + drain_s - time.perf_counter()))
    watch.active = False
    if trace:
        H.stop_trace()
    gc.unfreeze()
    after = shard.registry()
    ok = np.array([f.done() and not f.cancelled() and f.exception() is None
                   for f in futs])
    # a future reads done before its callbacks have stamped it
    limit = time.perf_counter() + 1.0
    while np.isnan(done[ok]).any() and time.perf_counter() < limit:
        time.sleep(0.001)
    due_abs = t0 + due
    latency = np.where(ok, done - due_abs, np.inf)
    last = np.nanmax(done) if ok.any() else t0 + seconds
    return {"futures": futs, "ok": ok, "latency_s": latency,
            "lag_ms": float(np.percentile(submitted - due_abs, 99)) * 1e3,
            "drain_s": max(0.0, float(last - (t0 + seconds))),
            "completed_in_window": int(np.sum(ok & (done <= t0 + seconds))),
            "server": _delta(after, before)}


def check(cfg: dict, shard: Shard, rows, futs, ok, seed: int,
          control: bool = False):
    """Compare a sample of the served answers, drawn from the seed, with the
    reference. With ``control``, also answer the same requests with the
    control (the reference one precision step lower) and compare those.
    Returns (the served answers' readings, the control's or None)."""
    ref_mod = _reference_module()
    pick = np.flatnonzero(ok)[gen.sample(seed, int(ok.sum()), cfg["sample"])]
    served = [futs[i].result() for i in pick]
    ids = np.stack([np.asarray(r.ids) for r in served])
    scores = np.stack([np.asarray(r.scores) for r in served])
    ref = ref_mod.ShardReference(cfg, shard.weights, shard.base, shard.assign)
    q = rows[pick]
    want, _ = ref.search(q)
    got = ref_mod.compare(ref, q, ids, scores, want)
    if not control:
        return got, None
    c_ids, c_scores = ref.search(q, precision="control")
    return got, ref_mod.compare(ref, q, c_ids, c_scores, want)


def run(cfg: dict, traffic: dict, *, seed: int, seconds: float, trace: bool,
        devs, clock, workload: str) -> dict:
    watch = H.CompileWatch()
    shard = Shard(cfg, seed)
    due = gen.arrivals(seed, traffic["rate_qps"], seconds)
    rows = np.asarray(gen.query_rows(gen.sub_key(seed, gen.QUERIES),
                                     shard.base, n=len(due),
                                     metric=cfg["metric"]))
    setup_s = clock.since()
    tdir = H.trace_dir(workload) if trace else None
    rec = window(shard, rows, due, seconds, watch, tdir)
    memory_peak = H.peak_memory(devs)
    ok, lat = rec["ok"], rec["latency_s"]
    H.log(f"{workload}: {len(due)} offered, {int(ok.sum())} answered, "
          f"{rec['completed_in_window']} in the window; set-up "
          f"{setup_s:.3f} s {shard.phases}; {shard.dropped} (rep, label) "
          f"slots past the member width; generator lag p99 "
          f"{rec['lag_ms']:.3f} ms; last answer {rec['drain_s']:.3f} s "
          f"after the window; {watch.compiles} compiles and "
          f"{watch.traces} traces in the window")
    record = {
        "seconds": seconds, "latency_ms": lat * 1e3,
        "server": rec["server"],
        "trace": trace_reduce.reduce_dir(tdir) if trace else None,
        "cfg": cfg, "width": shard.width,
    }
    if record["trace"]:
        record["peak"] = work.peaks(devs[0].device_kind)
    futs = rec["futures"]
    shard.free()
    got, _ = check(cfg, shard, rows, futs, ok, seed)
    unanswered = int(len(due) - ok.sum())
    checks = {"unanswered": {"value": unanswered, "limit": 0}}
    for name, value in got.items():
        checks[name] = {"value": value, "limit": cfg["limits"][name]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    end_to_end = {"setup_s": setup_s,
                  "serve_qps": rec["completed_in_window"] / seconds}
    return {"correct": bool(correct), "attempted": len(due),
            "failed": unanswered, "end_to_end": end_to_end,
            "record": record, "checks": checks,
            "memory_peak_bytes": memory_peak}
