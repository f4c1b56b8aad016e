"""Typed search API: SearchParams validation, the Searcher protocol, the
PipelineCache (compile-once, no cross-params eviction), the deprecated
kwarg shims (bit-identical to the typed path on frozen, streaming, and
per-shard backends), and the server's per-request params with
params-grouped micro-batching, bucket ladder, and blocking timeout."""
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeoutError

import numpy as np
import pytest

from repro.core.distributed import local_search, shard_search_local
from repro.core.index import IRLIIndex, IRLIConfig
from repro.core.search_api import (DEFAULT_CACHE, PipelineCache, SearchParams,
                                   SearchResult, Searcher, as_searcher)
from repro.serve.server import IRLIServer, _bucket_ladder
from repro.stream import MutableIRLIIndex

D, B, R, L = 16, 16, 2, 400


def _untrained_index(L=L, seed=0):
    cfg = IRLIConfig(d=D, n_labels=L, n_buckets=B, n_reps=R, d_hidden=32,
                     K=4, seed=seed)
    idx = IRLIIndex(cfg)
    idx.build_index()
    return idx


@pytest.fixture(scope="module")
def frozen():
    rng = np.random.default_rng(0)
    idx = _untrained_index()
    base = rng.normal(size=(L, D)).astype(np.float32)
    queries = rng.normal(size=(10, D)).astype(np.float32)
    return idx, base, queries


@pytest.fixture(scope="module")
def mutated():
    rng = np.random.default_rng(1)
    base = rng.normal(size=(L, D)).astype(np.float32)
    mut = MutableIRLIIndex(_untrained_index(seed=1), base)
    mut.insert(rng.normal(size=(50, D)).astype(np.float32))
    mut.delete(rng.choice(L, 30, replace=False))
    return mut, rng.normal(size=(10, D)).astype(np.float32)


# ------------------------------------------------------------ SearchParams --
def test_params_validation():
    for bad in (dict(m=0), dict(tau=0), dict(k=-1), dict(topC=0),
                dict(m=2.5), dict(m=True)):
        with pytest.raises(ValueError):
            SearchParams(**bad)
    with pytest.raises(ValueError, match="metric"):
        SearchParams(metric="cosine")
    with pytest.raises(ValueError, match="mode"):
        SearchParams(mode="sparse")


def test_params_hashable_and_resolution():
    a, b = SearchParams(m=4), SearchParams(m=4)
    assert a == b and hash(a) == hash(b) and len({a, b}) == 1
    assert SearchParams().resolve(1_000).mode == "dense"
    # beyond the dense budget auto resolves compact (mega is never auto)
    assert SearchParams().resolve(100_000_000).mode == "compact"
    # an oversized search shape falls back to the staged compact path
    assert SearchParams(m=512, topC=32768).resolve(
        100_000_000).mode == "compact"
    # an explicit mode survives resolution untouched
    assert SearchParams(mode="compact").resolve(1_000).mode == "compact"
    with pytest.raises(ValueError, match="resolve"):
        SearchParams(mode="auto").pipeline()
    with pytest.raises(ValueError, match="resolve"):   # kernels unresolved
        SearchParams(mode="compact").pipeline()
    p = SearchParams(m=3, tau=2, k=7, topC=64, mode="compact").resolve(
        1_000).pipeline()
    assert (p.m, p.tau, p.k, p.topC, p.mode) == (3, 2, 7, 64, "compact")
    assert p.kernels == ()                  # no TPU here: every stage jnp


def test_searcher_protocol(frozen, mutated):
    idx, base, _ = frozen
    mut, _ = mutated
    assert isinstance(mut, Searcher)                 # one-arg search()
    bound = idx.as_searcher(base)
    assert isinstance(bound, Searcher)
    res = bound.search(frozen[2], SearchParams(k=5))
    assert isinstance(res, SearchResult) and res.ids.shape == (10, 5)
    assert isinstance(as_searcher(lambda q, p: res), Searcher)


# ----------------------------------------------------------- PipelineCache --
def test_cache_compiles_once_per_key(frozen):
    idx, base, queries = frozen
    cache = PipelineCache()
    sp = SearchParams(k=5, mode="compact", topC=64)
    outs = [cache.search(sp, idx.params, idx.index.members, base, queries)
            for _ in range(4)]
    assert cache.misses == 1 and cache.hits == 3
    assert cache.compiles == 1          # N searches, ONE trace
    assert len(cache) == 1
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0].ids),
                                      np.asarray(o.ids))


def test_cache_interleaved_params_do_not_evict(frozen):
    idx, base, queries = frozen
    cache = PipelineCache()
    a = SearchParams(k=5, mode="compact", topC=64)
    b = SearchParams(k=7, mode="dense")
    fns = [cache.get(p.resolve(L, 10), L, 10)
           for p in (a, b, a, b, a, b)]
    assert fns[0] is fns[2] is fns[4]   # a's fn survives b's insertions
    assert fns[1] is fns[3] is fns[5]
    assert cache.stats() == {"hits": 4, "misses": 2, "compiles": 0,
                             "entries": 2}
    # and end to end: alternating searches still compile once per params
    for p in (a, b, a, b):
        cache.search(p, idx.params, idx.index.members, base, queries)
    assert cache.compiles == 2


def test_cache_rejects_unresolved_params():
    with pytest.raises(ValueError, match="resolve"):
        PipelineCache().get(SearchParams(mode="auto"), L, 10)


# ------------------------------------------------------- deprecated shims --
def test_shim_equivalence_frozen(frozen):
    idx, base, queries = frozen
    with pytest.deprecated_call():
        ids_old, nc_old = idx.search(queries, base, m=3, tau=1, k=5)
    res = idx.search(queries, base, SearchParams(m=3, tau=1, k=5))
    np.testing.assert_array_equal(np.asarray(ids_old), np.asarray(res.ids))
    np.testing.assert_array_equal(np.asarray(nc_old),
                                  np.asarray(res.n_candidates))
    assert res.epoch == 0


def test_shim_equivalence_streaming(mutated):
    mut, queries = mutated
    with pytest.deprecated_call():
        ids_old, nc_old = mut.search(queries, m=3, tau=1, k=5)
    res = mut.search(queries, SearchParams(m=3, tau=1, k=5))
    np.testing.assert_array_equal(np.asarray(ids_old), np.asarray(res.ids))
    np.testing.assert_array_equal(np.asarray(nc_old),
                                  np.asarray(res.n_candidates))
    assert res.epoch == mut.epoch


def test_shim_equivalence_per_shard(mutated):
    mut, queries = mutated
    s = mut.snapshot
    kw = dict(delta_members=s.delta.members, tombstone=s.tombstone)
    with pytest.deprecated_call():
        ids_old, sc_old = local_search(mut.params, s.members, s.vecs,
                                       queries, m=3, tau=1, k=5, **kw)
    res = local_search(mut.params, s.members, s.vecs, queries,
                       SearchParams(m=3, tau=1, k=5), **kw)
    np.testing.assert_array_equal(np.asarray(ids_old), np.asarray(res.ids))
    np.testing.assert_array_equal(np.asarray(sc_old), np.asarray(res.scores))
    with pytest.deprecated_call():
        ids_old, sc_old = shard_search_local(mut.params, s.members, s.vecs,
                                             queries, m=3, tau=1, k=5, **kw)
    res = shard_search_local(mut.params, s.members, s.vecs, queries,
                             SearchParams(m=3, tau=1, k=5), **kw)
    np.testing.assert_array_equal(np.asarray(ids_old), np.asarray(res.ids))
    np.testing.assert_array_equal(np.asarray(sc_old), np.asarray(res.scores))


def test_shim_equivalence_server(mutated):
    mut, queries = mutated
    sp = SearchParams(m=3, tau=1, k=5)
    with pytest.deprecated_call():
        legacy = IRLIServer(mut, m=3, tau=1, k=5, max_batch=8,
                            max_wait_ms=5.0)
    typed = IRLIServer(mut, params=sp, max_batch=8, max_wait_ms=5.0)
    try:
        old = legacy.search(queries[0], timeout=120)   # bare id row
        new = typed.search(queries[0], timeout=120)    # SearchResult
        assert isinstance(new, SearchResult)
        np.testing.assert_array_equal(np.asarray(old), np.asarray(new.ids))
    finally:
        legacy.close()
        typed.close()


def test_mixing_params_and_legacy_kwargs_raises(frozen, mutated):
    idx, base, queries = frozen
    mut, _ = mutated
    with pytest.raises(TypeError, match="not both"):
        idx.search(queries, base, SearchParams(), m=3)
    with pytest.raises(TypeError, match="not both"):
        mut.search(queries, SearchParams(), k=5)
    s = mut.snapshot
    with pytest.raises(TypeError, match="not both"):
        local_search(mut.params, s.members, s.vecs, queries, SearchParams(),
                     m=3)


def test_positional_legacy_knobs_rejected_clearly(frozen, mutated):
    """A pre-redesign POSITIONAL call (idx.search(q, base, 5, 1, 10)) must
    fail with a clear migration TypeError, not an opaque AttributeError
    deep inside the cache."""
    idx, base, queries = frozen
    mut, _ = mutated
    with pytest.raises(TypeError, match="SearchParams"):
        idx.search(queries, base, 5)
    with pytest.raises(TypeError, match="SearchParams"):
        mut.search(queries, 8)
    s = mut.snapshot
    with pytest.raises(TypeError, match="SearchParams"):
        # old keyword name: params= used to be the SCORER params
        local_search(mut.params, s.members, s.vecs, queries,
                     params={"w": 1})
    with pytest.raises(TypeError, match="SearchParams"):
        IRLIServer(mut, params=5)
    server = IRLIServer(mut, max_wait_ms=1.0)
    try:
        with pytest.raises(TypeError, match="SearchParams"):
            server.submit(queries[0], 5)
    finally:
        server.close()


def test_production_path_rejects_dense(mutated):
    mut, queries = mutated
    s = mut.snapshot
    with pytest.raises(ValueError, match="compact-only"):
        shard_search_local(mut.params, s.members, s.vecs, queries,
                           SearchParams(mode="dense"))


# ------------------------------------------------------------- the server --
def test_bucket_ladder_derives_from_max_batch():
    assert _bucket_ladder(512) == (1, 8, 32, 128, 512)
    assert _bucket_ladder(64) == (1, 8, 32, 64)      # never pads past 64
    assert _bucket_ladder(8) == (1, 8)
    assert _bucket_ladder(1) == (1,)
    assert _bucket_ladder(100) == (1, 8, 32, 100)


def test_full_batch_does_not_pad(mutated):
    """Satellite: with max_batch=64, a 64-request batch must pad to 64 (the
    old class-constant ladder padded it to 128, doubling pad_waste)."""
    mut, queries = mutated
    sp = SearchParams(m=3, k=5, mode="compact", topC=64)
    server = IRLIServer(mut, params=sp, max_batch=64, max_wait_ms=1.0)
    try:
        assert server._bucket(64) == 64 and server._bucket(33) == 64
        qs = np.repeat(queries, 7, axis=0)[:64]
        futs = [Future() for _ in range(64)]
        server._run_batch(list(zip(qs, futs)), sp)     # a full batch
        assert server.stats["pad_waste"] == 0
        server._run_batch(list(zip(qs[:9], futs[:9])), sp)   # 9 -> bucket 32
        assert server.stats["pad_waste"] == 23
        for f in futs:
            assert f.result(timeout=5).ids.shape == (5,)
    finally:
        server.close()


def test_server_batches_compile_once(mutated):
    """Satellite: N same-params batches at one bucket size -> exactly one
    compilation; the cache serves every later batch."""
    mut, queries = mutated
    sp = SearchParams(m=3, k=5, mode="compact", topC=64)
    cache = PipelineCache()
    server = IRLIServer(mut, params=sp, cache=cache, max_batch=8,
                        max_wait_ms=1.0)
    try:
        for _ in range(4):      # 4 batches, same params, same 8-bucket
            server._run_batch([(q, Future()) for q in queries[:4]], sp)
        assert server.stats["batches"] == 4
        assert cache.compiles == 1
        assert cache.misses == 1 and cache.hits == 3
        # a second params interleaved: its own single compile, no eviction
        sp2 = sp.replace(m=4)
        for p in (sp2, sp, sp2, sp):
            server._run_batch([(q, Future()) for q in queries[:4]], p)
        assert cache.compiles == 2
        assert cache.stats()["entries"] == 2
        assert cache.misses == 2 and cache.hits == 6
    finally:
        server.close()


def test_server_two_clients_different_params(mutated):
    """Acceptance: two concurrent clients with different SearchParams get
    correct (per-params) results; groups batch by params; the cache shows
    one miss per (params, bucket) and hits for everything else."""
    mut, queries = mutated
    pa = SearchParams(m=3, k=5, mode="compact", topC=64)
    pb = SearchParams(m=4, k=7, mode="compact", topC=64)
    want_a = np.asarray(mut.search(queries, pa).ids)
    want_b = np.asarray(mut.search(queries, pb).ids)

    cache = PipelineCache()
    server = IRLIServer(mut, params=pa, cache=cache, max_batch=8,
                        max_wait_ms=20.0)
    results = {}

    def client(name, params):
        futs = [server.submit(q, params) for q in queries]
        results[name] = [f.result(timeout=120) for f in futs]

    try:
        ta = threading.Thread(target=client, args=("a", pa))
        tb = threading.Thread(target=client, args=("b", pb))
        ta.start(); tb.start(); ta.join(timeout=300); tb.join(timeout=300)
        assert set(results) == {"a", "b"}
        for i in range(len(queries)):
            ra, rb = results["a"][i], results["b"][i]
            assert ra.ids.shape == (5,) and rb.ids.shape == (7,)
            np.testing.assert_array_equal(np.asarray(ra.ids), want_a[i])
            np.testing.assert_array_equal(np.asarray(rb.ids), want_b[i])
        stats = server.stats
        assert stats["requests"] == 2 * len(queries)
        # interleaved params force >= one group per params
        assert stats["param_groups"] >= 2
        assert stats["param_groups"] == stats["batches"]
        # cache: one miss per (params, bucket) key, hits for the rest —
        # per-request tunability must not mean per-batch compilation
        cs = stats["cache"]
        assert cs["misses"] == cs["entries"] <= 4    # 2 params x <= 2 buckets
        assert cs["hits"] == stats["batches"] - cs["misses"]
    finally:
        server.close()


def test_server_search_timeout_forwarded(mutated):
    """Satellite: the blocking helper's timeout reaches Future.result — a
    slow backend raises instead of hanging the caller forever."""
    class SlowSearcher:
        def search(self, qs, params):
            time.sleep(2.0)
            n = qs.shape[0]
            return SearchResult(ids=np.zeros((n, params.k), np.int32),
                                scores=np.zeros((n, params.k), np.float32),
                                n_candidates=np.zeros(n, np.int32))

    server = IRLIServer(SlowSearcher(), max_wait_ms=1.0)
    try:
        with pytest.raises(FutureTimeoutError):
            server.search(np.zeros(D, np.float32), timeout=0.05)
        # and without expiry the same request completes fine
        res = server.search(np.zeros(D, np.float32), timeout=30)
        assert res.ids.shape == (10,)
    finally:
        server.close()


def test_submit_after_close_fails_fast(mutated):
    """Satellite: submit() on a closed server fails the future IMMEDIATELY
    (fut.done() before any result() wait), covering the in-code comment."""
    mut, _ = mutated
    server = IRLIServer(mut, max_wait_ms=1.0)
    server.close()
    fut = server.submit(np.zeros(D, np.float32))
    assert fut.done()
    with pytest.raises(RuntimeError, match="closed"):
        fut.result(timeout=0)
    with pytest.raises(RuntimeError, match="closed"):
        server.search(np.zeros(D, np.float32), timeout=0)


def test_default_cache_is_shared(frozen):
    """Bare idx.search calls (no explicit cache) share DEFAULT_CACHE: a
    repeat of the same request is a hit, not a new compilation."""
    idx, base, queries = frozen
    sp = SearchParams(m=2, tau=1, k=3, mode="compact", topC=32)
    before = dict(DEFAULT_CACHE.stats())
    idx.search(queries, base, sp)
    idx.search(queries, base, sp)
    after = DEFAULT_CACHE.stats()
    assert after["hits"] >= before["hits"] + 1


# ------------------------------------------------------ kernel resolution --
def test_select_kernels_is_a_resolved_choice():
    """Which Pallas kernels a request runs is decided once, from backend +
    geometry, and recorded in the resolved params and the result."""
    from repro.core import query as Q
    from repro.core.search_api import candidate_width
    assert Q.select_kernels("compact", "int8", 4160, "tpu") == (
        "freq_topc", "quant_rerank")
    # wider than the kernel's packed sort keys: the jnp sort, visibly
    assert Q.select_kernels("compact", "int8", 40000, "tpu") == (
        "quant_rerank",)
    assert Q.select_kernels("compact", "fp32", None, "tpu") == ()
    assert Q.select_kernels("mega", "fp32", 512, "tpu") == ("freq_topc",)
    assert Q.select_kernels("dense", "fp32", 512, "tpu") == ()
    assert Q.select_kernels("compact", "int8", 4160, "cpu") == ()
    idx = _untrained_index()
    assert candidate_width(5, idx.index.members) == (
        R * 5 * idx.index.members.shape[-1])
    base = np.random.default_rng(0).normal(size=(L, D)).astype(np.float32)
    res = idx.search(base[:4], base, SearchParams(mode="compact"))
    assert res.mode == "compact" and res.kernels == ()
    with pytest.raises(ValueError, match="kernels"):
        SearchParams(kernels=("bitonic",))
    with pytest.raises(ValueError, match="dense"):
        SearchParams(mode="dense", kernels=("freq_topc",))


_CACHE_SCRIPT = """
import os, sys
from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache
import jax
got = enable_compile_cache()
print(got, jax.config.jax_compilation_cache_dir, CACHE_DIR)
"""


@pytest.mark.parametrize("env_dir", ["", "/nonexistent/jax-cache"])
def test_compile_cache_dir_is_placed_from_outside(env_dir):
    """JAX_COMPILATION_CACHE_DIR set: the helper sets nothing in code and
    jax keeps that directory. Unset: the fixed <checkout>/.jax_cache."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    got, cfg, fixed = r.stdout.split()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert fixed == os.path.join(repo, ".jax_cache")
    if env_dir:
        assert got == cfg == env_dir
    else:
        assert got == cfg == fixed
