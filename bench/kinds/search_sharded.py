"""Cells that serve the paper's sharded deployment: one IRLI shard per chip,
searched as one program by ``make_production_search``, in a closed loop.

Set-up (timed as ``setup_s``): every shard's corpus is made on its own chip
from its own sub-key of the seed; the scorer weights are made on the first
chip, centred on shard 0's first rows and replicated; then the shards are
built concurrently, one thread per chip, each on its own chip: the
benchmark's partition (``shard_index``), the program's member matrix and
int8 store. ``place_shards`` lays them onto the mesh where they were built,
and the one batch shape the window uses is warmed up.

Window: a pool of distinct query batches is made and placed before it;
inside it batches are dispatched back to back, cycling through the pool,
with ``in_flight`` of them outstanding: batch n's results are read after
batch n+1 is dispatched. When the window ends no batch is dispatched, and
what is in flight is waited for (a minute at most, polled, never blocking
without a deadline). The program's state is then freed, and a sample of
the answered queries drawn from the seed is compared with the plain
sharded reference (``configs/irli_reference_sharded.py``).
"""
from __future__ import annotations

import collections
import concurrent.futures
import gc
import os
import time

import jax
import numpy as np

import gen
import harness as H
import shard_index
import trace_reduce
import work

#: shard 0's rows the scorer's bucket biases are centred over
CENTRE_ROWS = 4096


def _reference_module():
    return H.load_module(os.path.join(H.BENCH, "configs",
                                      "irli_reference_sharded.py"))


def _shard_key(seed: int, tag: int, shard: int):
    """Shard ``shard``'s own stream of input ``tag``."""
    return jax.random.fold_in(gen.sub_key(seed, tag), shard)


class Deployment:
    """The shards' data and partitions (the benchmark's) and the placed
    index and search (the program's), one shard per chip of ``devs``."""

    def __init__(self, cfg: dict, seed: int, devs):
        # first, so that a program without them fails before any work
        from repro.core.distributed import (make_production_search,
                                            place_shards)
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core.search_api import SearchParams
        from repro.launch.mesh import make_host_mesh

        self.cfg = cfg
        self.phases = {}
        clock = time.perf_counter()

        def phase(name, *arrays):
            nonlocal clock
            jax.block_until_ready(arrays)
            now = time.perf_counter()
            self.phases[name] = round(now - clock, 3)
            clock = now

        if len(devs) != cfg["shards"]:
            raise ValueError(f"{cfg['shards']} shards need as many chips, "
                             f"got {len(devs)}")
        self.mesh = make_host_mesh(len(devs))
        self.devs = list(self.mesh.devices.flat)
        self.replicated = NamedSharding(self.mesh, PartitionSpec())
        L = cfg["n_corpus"]
        self.bases = [gen.corpus(
            jax.device_put(_shard_key(seed, gen.CORPUS, s), dev), n=L,
            d=cfg["d"], n_clusters=L // cfg["cluster_rows"],
            metric=cfg["metric"]) for s, dev in enumerate(self.devs)]
        w = shard_index.centre(
            gen.scorer_weights(jax.device_put(gen.sub_key(seed, gen.WEIGHTS),
                                              self.devs[0]),
                               R=cfg["reps"], d=cfg["d"], H=cfg["hidden"],
                               B=cfg["buckets"]),
            self.bases[0][:CENTRE_ROWS])
        scorer = jax.device_put(w, self.replicated)
        del w
        # each chip's copy of the replicated scorer, as its own array
        self.weights = [{k: next(sh.data for sh in v.addressable_shards
                                 if sh.device == dev)
                         for k, v in scorer.items()} for dev in self.devs]
        phase("data", self.bases, scorer)
        with concurrent.futures.ThreadPoolExecutor(len(self.devs)) as pool:
            built = list(pool.map(lambda s: self._build(seed, s),
                                  range(len(self.devs))))
        self.assigns = [b[0] for b in built]
        self.dropped = sum(b[3] for b in built)
        self.shard_phases = [b[4] for b in built]
        phase("shards")
        self.scorer, self.members, self.store = place_shards(
            self.mesh, scorer, [b[1] for b in built], [b[2] for b in built])
        del built
        phase("place", self.scorer, self.members, self.store)
        self.params = SearchParams(
            m=cfg["m"], tau=cfg["tau"], k=cfg["k"], topC=cfg["topC"],
            refine_k=cfg["refine_k"], metric=cfg["metric"],
            store_dtype=cfg["store"])
        self.search_fn = make_production_search(self.mesh, self.params)

    def _build(self, seed: int, s: int):
        """Shard ``s``'s partition, member matrix and int8 store, made on
        its own chip -> (assign, members, store, dropped slots, phases)."""
        from repro.core.partition import build_inverted_index
        from repro.store import encode

        cfg, dev, base = self.cfg, self.devs[s], self.bases[s]
        times, clock = {}, time.perf_counter()

        def phase(name, *arrays):
            nonlocal clock
            jax.block_until_ready(arrays)
            now = time.perf_counter()
            times[name] = round(now - clock, 3)
            clock = now

        top = shard_index.affinity_topk(self.weights[s], base,
                                        K=cfg["power_of_k"],
                                        chunk=cfg["affinity_chunk"])
        phase("affinity", top)
        assign = shard_index.power_of_k(
            top, B=cfg["buckets"], K=cfg["power_of_k"],
            key=jax.device_put(_shard_key(seed, gen.PARTITION, s), dev))
        del top
        phase("power_of_k", assign)
        index = build_inverted_index(assign, cfg["buckets"],
                                     max_load=cfg["member_width"])
        store = encode(base, cfg["store"], cfg["store_block"])
        phase("members_store", index.members, store.codes)
        return assign, index.members, store, index.dropped, times

    def queries(self, seed: int, n: int) -> np.ndarray:
        """[n, d] queries: each a noisy copy of a row drawn uniformly from
        all shards (a shard drawn from the seed, then ``gen.query_rows``
        on that shard's rows)."""
        which = np.random.default_rng([seed, gen.QUERIES]).integers(
            0, len(self.bases), n)
        rows = [gen.query_rows(_shard_key(seed, gen.QUERIES, s), base, n=n,
                               metric=self.cfg["metric"])
                for s, base in enumerate(self.bases)]
        rows = np.stack([np.asarray(r) for r in jax.block_until_ready(rows)])
        return rows[which, np.arange(n)]

    def search(self, queries):
        return self.search_fn(self.scorer, self.members, self.store, queries)

    def free(self) -> None:
        """Drop the program's state; the benchmark's data stays for the
        reference."""
        del self.scorer, self.members, self.store, self.search_fn
        gc.collect()


def _ready(res, deadline: float) -> bool:
    """Wait for a result's ids and scores until ``deadline`` (polled)."""
    while not (res.ids.is_ready() and res.scores.is_ready()):
        if time.perf_counter() > deadline:
            return False
        time.sleep(0.0002)
    return True


def window(dep: Deployment, pool, seconds: float, in_flight: int, watch,
           trace: str | None, drain_s: float = 60.0) -> dict:
    """Dispatch the pool's batches back to back for ``seconds``, reading
    each batch's answers once ``in_flight`` are outstanding; then wait
    (``drain_s`` at most) for the rest. Returns per read batch its pool
    index, its answers and when they were read."""
    from repro.obs import trace as span

    rows = pool[0].shape[0]
    pending = collections.deque()
    read = []

    def take(deadline):
        n, res = pending.popleft()
        with span(None, "sharded.read", batch_id=n):
            if not _ready(res, deadline):
                return False
            read.append((n % len(pool), np.asarray(res.ids),
                         np.asarray(res.scores), time.perf_counter()))
        return True

    gc.collect()
    gc.freeze()
    if trace:
        H.start_trace(trace)
    watch.active = True
    n = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            with span(None, "sharded.batch", batch_id=n, rows=rows):
                res = dep.search(pool[n % len(pool)])
            pending.append((n, res))
            n += 1
            if len(pending) >= in_flight and not take(end + drain_s):
                break
    while pending and take(end + drain_s):
        pass
    watch.active = False
    if trace:
        H.stop_trace()
    gc.unfreeze()
    in_window = sum(t <= end for *_, t in read)
    return {"read": read, "dispatched": n, "rows": rows,
            "answered_in_window": in_window * rows,
            "drain_s": max(0.0, (read[-1][3] - end) if read else 0.0)}


def check(cfg: dict, dep: Deployment, pool_rows: np.ndarray, read, seed: int,
          control: bool = False):
    """Compare a sample of the answered queries, drawn from the seed, with
    the sharded reference (and with ``control`` the reference one precision
    step lower too). Returns (the answers' readings, the control's or
    None)."""
    ref_mod = _reference_module()
    rows = pool_rows.shape[1]
    answered = len(read) * rows
    pick = gen.sample(seed, answered, cfg["sample"])
    batch, row = pick // rows, pick % rows
    q = pool_rows[[read[b][0] for b in batch], row]
    ids = np.stack([read[b][1][r] for b, r in zip(batch, row)])
    scores = np.stack([read[b][2][r] for b, r in zip(batch, row)])
    ref = ref_mod.ShardedReference(cfg, dep.weights[0], dep.bases,
                                   dep.assigns)
    want, _ = ref.search(q)
    got = ref_mod.compare(ref, q, ids, scores, want)
    if not control:
        return got, None
    c_ids, c_scores = ref.search(q, precision="control")
    return got, ref_mod.compare(ref, q, c_ids, c_scores, want)


def run(cfg: dict, traffic: dict, *, seed: int, seconds: float, trace: bool,
        devs, clock, workload: str, control: bool = False) -> dict:
    """One run of the cell (``control``: also the control's readings, under
    ``"control"``, for setting the limits)."""
    watch = H.CompileWatch()
    dep = Deployment(cfg, seed, devs)
    batch = traffic["batch"]
    pool_rows = dep.queries(seed, traffic["pool_batches"] * batch).reshape(
        traffic["pool_batches"], batch, cfg["d"])
    pool = [jax.device_put(b, dep.replicated) for b in pool_rows]
    warm = time.perf_counter()
    jax.block_until_ready(dep.search(pool[0]).ids)
    dep.phases["warm_up"] = round(time.perf_counter() - warm, 3)
    setup_s = clock.since()
    tdir = H.trace_dir(workload) if trace else None
    rec = window(dep, pool, seconds, traffic["in_flight"], watch, tdir)
    memory_peak = H.peak_memory(devs)
    attempted = rec["dispatched"] * batch
    answered = len(rec["read"]) * batch
    H.log(f"{workload}: {rec['dispatched']} batches of {batch} dispatched, "
          f"{len(rec['read'])} answered, "
          f"{rec['answered_in_window'] // batch} in the window; set-up "
          f"{setup_s:.3f} s {dep.phases}, per shard {dep.shard_phases}; "
          f"{dep.dropped} (rep, label) slots past the member width; last "
          f"answer {rec['drain_s']:.3f} s after the window; "
          f"{watch.compiles} compiles and {watch.traces} traces in the "
          f"window")
    record = {"seconds": seconds, "cfg": cfg,
              "answered": rec["answered_in_window"],
              "trace": trace_reduce.reduce_dir(tdir) if trace else None}
    if record["trace"]:
        record["peak"] = work.peaks(devs[0].device_kind)
    dep.free()
    got, ctrl = check(cfg, dep, pool_rows, rec["read"], seed, control)
    unanswered = attempted - answered
    checks = {"unanswered": {"value": unanswered, "limit": 0}}
    for name, value in got.items():
        checks[name] = {"value": value, "limit": cfg["limits"][name]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": unanswered,
           "end_to_end": {"setup_s": setup_s,
                          "serve_qps": rec["answered_in_window"] / seconds},
           "record": record, "checks": checks,
           "memory_peak_bytes": memory_peak}
    if control:
        out["control"] = ctrl
    return out
