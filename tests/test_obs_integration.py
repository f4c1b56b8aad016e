"""Telemetry wired through the real subsystems: the staged debug pipeline is
BIT-IDENTICAL to the jitted fused serving path (the acceptance pin — staged
mode is per-stage jits of the same stage functions one big jit fuses, and
the real serving path is always jitted via PipelineCache), per-stage
histograms land in the registry, the server's legacy ``stats`` dict is a
consistent view over its thread-safe registry, and fit/stream record their
load-balance + churn metrics."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import query as Q
from repro.core.index import IRLIIndex, IRLIConfig
from repro.core.search_api import SearchParams
from repro.stream import MutableIRLIIndex

D, B, R, M_PROBE, K_TOP = 16, 16, 2, 4, 5


def _untrained_index(L, seed=0):
    cfg = IRLIConfig(d=D, n_labels=L, n_buckets=B, n_reps=R,
                     d_hidden=32, K=M_PROBE, seed=seed)
    idx = IRLIIndex(cfg)
    idx.build_index()
    return idx


def _fixture(L=400, n_q=8, seed=1):
    rng = np.random.default_rng(seed)
    idx = _untrained_index(L, seed=seed)
    base = jnp.asarray(rng.normal(size=(L, D)), jnp.float32)
    queries = jnp.asarray(rng.normal(size=(n_q, D)), jnp.float32)
    return idx, base, queries


#: the (mode, metric, store_dtype) pipelines the staged pin and the scope
#: check run over
PIPELINES = [
    ("compact", "angular", "fp32"),
    ("compact", "l2", "fp32"),
    ("compact", "angular", "int8"),
    ("compact", "l2", "bf16"),
    ("dense", "angular", "fp32"),
    ("dense", "l2", "fp32"),
]


# ------------------------------------------------- staged == fused (jitted) --
@pytest.mark.parametrize("mode,metric,store_dtype", PIPELINES)
def test_staged_bit_identical_to_jitted_fused(mode, metric, store_dtype):
    """search_staged (per-stage jits + inter-stage fences) must return the
    EXACT arrays of the jitted fused path — same stage functions, only the
    jit boundaries differ. The reference is jit(search) with the frozen
    pipeline static, i.e. what PipelineCache actually serves (eager
    op-by-op execution is NOT the pin: XLA fuses/vectorizes differently
    there and bf16+l2 drifts by 1 ulp)."""
    idx, base, queries = _fixture()
    if store_dtype != "fp32":
        from repro.store.quantized import encode
        base = encode(base, dtype=store_dtype, block=8,
                      keep_exact=(store_dtype == "int8"))
    pipe = Q.QueryPipeline(mode=mode, m=M_PROBE, tau=1, k=K_TOP, topC=64,
                           metric=metric, store_dtype=store_dtype)
    fused = jax.jit(type(pipe).search, static_argnums=0)(
        pipe, idx.params, idx.index.members, base, queries)
    staged = pipe.search_staged(idx.params, idx.index.members, base, queries)
    assert len(fused) == len(staged)
    for f, s in zip(fused, staged):
        f, s = np.asarray(f), np.asarray(s)
        assert f.dtype == s.dtype and f.shape == s.shape
        # bitwise, not approx: compare the raw bytes
        np.testing.assert_array_equal(f.view(np.uint8), s.view(np.uint8))


def test_staged_streaming_matches_fused_and_masks_tombstones():
    """The staged flag threaded through MutableIRLIIndex.search ->
    PipelineCache.search serves identical results to the fused cache path,
    with live delta + tombstone state."""
    idx, base, queries = _fixture(seed=2)
    rng = np.random.default_rng(2)
    mut = MutableIRLIIndex(idx, np.asarray(base))
    mut.insert(rng.normal(size=(50, D)).astype(np.float32))
    dead = rng.choice(400, 30, replace=False)
    mut.delete(dead)
    sp = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    fused = mut.search(queries, sp)
    staged = mut.search(queries, sp, staged=True)
    np.testing.assert_array_equal(np.asarray(fused.ids),
                                  np.asarray(staged.ids))
    np.testing.assert_array_equal(np.asarray(fused.scores),
                                  np.asarray(staged.scores))
    assert not np.isin(np.asarray(staged.ids), dead).any()


def test_staged_records_stage_histograms():
    idx, base, queries = _fixture(seed=3)
    reg = obs.MetricRegistry()
    pipe = Q.QueryPipeline(mode="compact", m=M_PROBE, tau=1, k=K_TOP,
                           topC=64)
    pipe.search_staged(idx.params, idx.index.members, base, queries,
                       registry=reg)
    snap = reg.snapshot()
    for stage in ("scorer_logits", "top_m", "gather", "freq_topc", "rerank"):
        key = f'serve_stage_seconds{{stage="{stage}"}}'
        assert key in snap, sorted(snap)
        assert snap[key]["count"] == 1
        assert snap[key]["sum"] >= 0.0


# ------------------------------------------------ scopes in the served program --
#: the served program's stage scopes (core/query.py), named as staged mode
#: names its stages
STAGES = {"scorer_logits", "top_m", "gather", "freq_topc", "quant_rerank",
          "refine", "rerank", "freq_dense", "probe_stats"}


def _op_paths(lowered, fn_name: str) -> list:
    """The name-stack path of every op of a lowered program (its last
    component is the op itself)."""
    text = lowered.as_text(debug_info=True)
    return [p for p in re.findall(r'loc\("([^"]*)"', text)
            if p.startswith(f"jit({fn_name})/")]


def _stage(path: str) -> str | None:
    return next((c for c in path.split("/")[:-1] if c in STAGES), None)


@pytest.mark.parametrize("mode,metric,store_dtype", PIPELINES)
def test_served_program_carries_stage_scopes(mode, metric, store_dtype):
    """The program PipelineCache serves names each stage it runs, by the
    stage names staged mode records; only the n_candidates glue between
    stages is left out of a stage."""
    from repro.core.search_api import PipelineCache, candidate_width
    idx, base, queries = _fixture()
    if store_dtype != "fp32":
        from repro.store.quantized import encode
        base = encode(base, dtype=store_dtype, block=8,
                      keep_exact=(store_dtype == "int8"))
    sp = SearchParams(mode=mode, m=M_PROBE, tau=1, k=K_TOP, topC=64,
                      metric=metric, store_dtype=store_dtype)
    members = idx.index.members
    resolved = sp.resolve(base.shape[0], queries.shape[0],
                          width=candidate_width(M_PROBE, members))
    fn = PipelineCache().get(resolved, base.shape[0], queries.shape[0])
    paths = _op_paths(fn.lower(idx.params, members, base, queries, None,
                               None), "run")
    if mode == "dense":
        want = {"freq_dense", "rerank"}
    elif store_dtype == "fp32":
        want = {"freq_topc", "rerank"}
    else:
        want = {"freq_topc", "quant_rerank", "refine"}
    want |= {"scorer_logits", "top_m", "gather"}
    assert {_stage(p) for p in paths} - {None} == want
    glue = {p.rsplit("/", 1)[1] for p in paths if _stage(p) is None}
    assert glue <= {"reduce_sum", "ge", "and", "convert_element_type"}
    # the probe's top-m is a scope of its own (no adaptive-m mask here):
    # query.top_m's rounds, all in their inner scope, and no top_k
    top = [p for p in paths if _stage(p) == "top_m"]
    assert top and all("/top_m/max_and_mask/" in p for p in top), top
    assert "top_k" not in {p.rsplit("/", 1)[1] for p in top}


def test_probe_buckets_runs_under_probe_stats_scope():
    """The server's probe-statistics pass is one scope, apart from the
    search's own top_m."""
    idx, _, queries = _fixture()
    paths = _op_paths(Q.probe_buckets.lower(idx.params, queries, M_PROBE),
                      "probe_buckets")
    assert paths and {_stage(p) for p in paths} == {"probe_stats"}


def _host_spans(trace_dir, prefix: str) -> dict:
    """name -> [(start_ns, end_ns, {arg: value})] of the host events whose
    name starts with ``prefix`` in the newest trace under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         {k: v for k, v in ev.stats}))
    return out


def test_server_phases_are_profiler_spans(tmp_path):
    """Under the profiler, every served batch is one ``serve.batch`` span
    whose fill and bucket agree with the server's counters, its phases nest
    inside it, its ``serve.collect`` comes before it, a mutation is a
    ``serve.mutation`` span of its own, and the waits on an empty queue are
    ``serve.wait`` spans."""
    from repro.serve.server import IRLIServer
    idx, base, queries = _fixture(seed=6)
    sp = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    server = IRLIServer(idx, params=sp, base=base, max_batch=4,
                        max_wait_ms=1.0)
    fill = server.registry.histogram("serve_batch_fill",
                                     bounds=obs.COUNT_BUCKETS)
    try:
        server.search(np.asarray(queries[0]), timeout=120)   # compiles
        before, fill0 = server.stats, fill.snapshot()["sum"]
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(3):
                futs = [server.submit(np.asarray(q)) for q in queries[:6]]
                for f in futs:
                    f.result(timeout=120)
            refused = server.insert(np.zeros((1, D), np.float32))
            with pytest.raises(TypeError):      # a frozen index refuses it
                refused.result(timeout=120)
        after = server.stats
    finally:
        server.close()
    spans = _host_spans(tmp_path, "serve.")
    batches = {a["batch_id"]: (s, e, a) for s, e, a in spans["serve.batch"]}
    assert len(batches) == len(spans["serve.batch"])
    assert len(batches) == after["batches"] - before["batches"]
    fills = [a["fill"] for _, _, a in batches.values()]
    assert sum(fills) == after["requests"] - before["requests"] == 18
    assert sum(fills) == fill.snapshot()["sum"] - fill0
    assert sum(a["bucket"] - a["fill"] for _, _, a in batches.values()) \
        == after["pad_waste"] - before["pad_waste"]
    assert all(a["bucket"] == server._bucket(a["fill"])
               for _, _, a in batches.values())
    for phase in ("serve.pad", "serve.probe_stats", "serve.search",
                  "serve.sync", "serve.results"):
        assert sorted(a["batch_id"] for _, _, a in spans[phase]) \
            == sorted(batches), phase
        for s, e, a in spans[phase]:
            bs, be, _ = batches[a["batch_id"]]
            assert bs <= s <= e <= be, phase
    collects = {a["batch_id"]: (s, e) for s, e, a in spans["serve.collect"]}
    assert set(collects) == set(batches)
    assert all(e <= batches[bid][0] for bid, (_, e) in collects.items())
    assert [a["op"] for _, _, a in spans["serve.mutation"]] == ["insert"]
    # between rounds the batcher waits on an empty queue, apart from batches
    assert spans["serve.wait"]
    assert not any(s < be and bs < e for s, e, _ in spans["serve.wait"]
                   for bs, be, _ in batches.values())


# ----------------------------------------------------------- server stats --
def test_server_stats_is_registry_view():
    from repro.serve.server import IRLIServer
    idx, base, queries = _fixture(seed=4)
    sp = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    server = IRLIServer(idx, params=sp, base=base, max_batch=4,
                        max_wait_ms=1.0)
    try:
        futs = [server.submit(np.asarray(q)) for q in queries]
        for f in futs:
            f.result(timeout=120)
    finally:
        server.close()
    st = server.stats
    # legacy dict shape preserved (plain ints + nested cache counters)
    assert sorted(st) == ["batches", "cache", "epoch", "mutations",
                          "pad_waste", "param_groups", "requests"]
    assert st["requests"] == len(queries)
    assert st["batches"] >= 1 and st["mutations"] == 0
    assert isinstance(st["cache"], dict)
    # ... and it is a VIEW over the thread-safe registry, not a second copy
    reg = server.registry.snapshot()
    assert reg["serve_requests_total"]["value"] == st["requests"]
    assert reg["serve_batches_total"]["value"] == st["batches"]
    assert reg["serve_queue_wait_seconds"]["count"] >= len(queries)
    assert reg["serve_batch_fill"]["count"] == st["batches"]
    assert reg["serve_candidates"]["count"] == len(queries)
    # probe-frequency vector: every request probed m buckets per rep
    probes = reg["serve_bucket_probes"]
    assert probes["sum"] == len(queries) * R * M_PROBE
    assert "kl_vs_uniform" in probes


def test_two_servers_do_not_share_counters():
    from repro.serve.server import IRLIServer
    idx, base, queries = _fixture(seed=5)
    sp = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    s1 = IRLIServer(idx, params=sp, base=base, max_batch=4)
    s2 = IRLIServer(idx, params=sp, base=base, max_batch=4)
    try:
        s1.search(np.asarray(queries[0]), timeout=120)
    finally:
        s1.close()
        s2.close()
    assert s1.stats["requests"] == 1
    assert s2.stats["requests"] == 0


# -------------------------------------------------------------- fit metrics --
def test_fit_records_round_metrics():
    rng = np.random.default_rng(0)
    L = 256
    cfg = IRLIConfig(d=D, n_labels=L, n_buckets=B, n_reps=R, d_hidden=32,
                     K=M_PROBE, rounds=2, epochs_per_round=1, batch_size=64,
                     seed=0)
    idx = IRLIIndex(cfg)
    x = rng.normal(size=(128, D)).astype(np.float32)
    gt = rng.integers(0, L, (128, 4)).astype(np.int32)
    reg = obs.MetricRegistry()

    class CollectLog:
        rows = []

        def log(self, row, step=None):
            self.rows.append(dict(row, step=step))

    idx.fit(x, gt, registry=reg, log=CollectLog())
    snap = reg.snapshot()
    assert snap["fit_rounds_total"]["value"] == cfg.rounds
    for key in ("fit_loss", "fit_grad_norm", "fit_churn", "fit_load_std",
                "fit_load_min", "fit_load_max", "fit_load_kl"):
        assert key in snap, sorted(snap)
    assert 0.0 <= snap["fit_churn"]["value"] <= 1.0
    assert snap["fit_load_min"]["value"] <= snap["fit_load_max"]["value"]
    assert snap["fit_load_kl"]["value"] >= 0.0
    assert snap["fit_grad_norm"]["value"] > 0.0
    # the per-round JSONL rows mirror the same fields, one per round
    assert len(CollectLog.rows) == cfg.rounds
    assert CollectLog.rows[0]["round"] == 0
    assert CollectLog.rows[-1]["seconds"] > 0.0
    assert {"loss", "churn", "load_kl"} <= set(CollectLog.rows[0])


# ----------------------------------------------------------- stream metrics --
def test_stream_mutation_metrics():
    idx, base, _ = _fixture(seed=6)
    reg = obs.MetricRegistry()
    mut = MutableIRLIIndex(idx, np.asarray(base), registry=reg)
    rng = np.random.default_rng(6)
    mut.insert(rng.normal(size=(32, D)).astype(np.float32))
    mut.delete(np.arange(16))
    snap = reg.snapshot()
    assert snap["stream_inserts_total"]["value"] == 32
    assert snap["stream_deletes_total"]["value"] == 16
    assert snap["stream_live"]["value"] == 400 + 32 - 16
    assert 0.0 < snap["stream_tombstone_ratio"]["value"] < 1.0
    assert snap["stream_delta_occupancy"]["value"] > 0.0
    before = snap["stream_tombstone_ratio"]["value"]
    mut.compact()
    snap = reg.snapshot()
    assert snap["stream_compactions_total"]["value"] == 1
    assert snap["stream_compaction_seconds"]["count"] == 1
    # compaction folds the delta segments into base (occupancy resets) but
    # deleted IDS stay tombstoned — ids are never reused
    assert snap["stream_delta_occupancy"]["value"] == 0.0
    assert snap["stream_tombstone_ratio"]["value"] == pytest.approx(before)
    assert snap["stream_live"]["value"] == 400 + 32 - 16
