"""Shared toy-config builders for the registered contracts.

Contracts are DECLARED beside the entry points they govern (core/query.py,
fit/engine.py, store/rerank.py, core/distributed.py, each kernel dispatch
site) but their fixtures are built HERE, lazily, at audit time — declaring
modules stay import-cheap and free of cycles (this module imports half the
repo; the declaration sites import only ``repro.analysis.contracts``).

Every builder follows the in-test proof recipes it replaces: DISTINCTIVE
dims (nothing else in the fixture is 4096 or 48), untrained indexes (the
invariants hold for any params, so skip the slow fit), and sizes small
enough that tracing/compiling every contract stays in CI budget.

``np.random.default_rng`` with fixed seeds throughout: fixtures must be
deterministic so an audit failure reproduces.
"""
from __future__ import annotations

import functools

import numpy as np

import repro.core  # noqa: F401  (import core before fit: package cycle order)
from repro.analysis.contracts import Fixture

# kept in sync with the declaration sites, which reference these to build
# their check bounds
QL_Q, QL_L, QL_TOPC = 6, 4096, 32
ST_L, ST_D, ST_Q, ST_C, ST_KP = 4096, 32, 6, 48, 16
FIT_L, FIT_B, FIT_CHUNK, FIT_K = 2048, 48, 256, 4
OL_L, OL_B, OL_CHUNK, OL_K = 1536, 40, 192, 3
OL_CAP, OL_D, OL_ML = 4096, 48, 256
M_PROBE, K_TOP = 4, 5


@functools.lru_cache(maxsize=None)
def _untrained_index(L: int, *, n_buckets: int = 64, d: int = 16,
                     n_reps: int = 2, seed: int = 0):
    """Scorer params + hash partition + inverted index, no training — the
    contracts must hold for ANY params (cached: index build dominates
    fixture cost and several contracts share one)."""
    from repro.core.index import IRLIConfig, IRLIIndex
    cfg = IRLIConfig(d=d, n_labels=L, n_buckets=n_buckets, n_reps=n_reps,
                     d_hidden=32, K=M_PROBE, seed=seed)
    idx = IRLIIndex(cfg)
    idx.build_index()
    return idx


def _corpus(L: int, n_q: int, d: int = 16, seed: int = 5):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(L, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(n_q, d)), jnp.float32))


# ------------------------------------------------------------------ query --
def query_search(mode: str, streaming: bool = False) -> Fixture:
    """QueryPipeline.search over the [Q=6, L=4096] toy — ``mode="compact"``
    is the contract fixture, ``mode="dense"`` its control."""
    import jax.numpy as jnp
    from repro.core.query import QueryPipeline
    idx = _untrained_index(QL_L)
    base, queries = _corpus(QL_L, QL_Q)
    pipe = QueryPipeline(m=M_PROBE, tau=1, k=K_TOP, mode=mode, topC=QL_TOPC)
    if streaming:
        R = idx.cfg.n_reps
        delta = jnp.full((R, idx.cfg.n_buckets, 8), -1, jnp.int32)
        tomb = jnp.zeros((QL_L,), bool).at[:10].set(True)
        fn = lambda p, mem, b, q: pipe.search(p, mem, b, q, delta, tomb)
    else:
        fn = lambda p, mem, b, q: pipe.search(p, mem, b, q)
    return Fixture(fn=fn, args=(idx.params, idx.index.members, base, queries),
                   dims={"Q": QL_Q, "L": QL_L, "C": QL_TOPC, "k": K_TOP})


def local_search_compact(mode: str = "compact") -> Fixture:
    """distributed.local_search (the per-shard path) with live tombstones."""
    import jax.numpy as jnp
    from repro.core.distributed import local_search
    from repro.core.search_api import SearchParams
    idx = _untrained_index(QL_L)
    base, queries = _corpus(QL_L, QL_Q)
    tomb = jnp.zeros((QL_L,), bool).at[:10].set(True)
    sp = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode=mode, topC=QL_TOPC)
    fn = lambda p, mem, b, q: local_search(p, mem, b, q, sp,
                                           tombstone=tomb).ids
    return Fixture(fn=fn, args=(idx.params, idx.index.members, base, queries),
                   dims={"Q": QL_Q, "L": QL_L, "C": QL_TOPC})


def audit_oracle_control() -> Fixture:
    """The shadow-audit oracle (``core.query.exact_topk``): a full-probe
    scan that builds the [Q, L] table BY DESIGN — the tripping control for
    ``query.audit_oracle_off_hot_path`` (proves forbid_dims("Q", "L") would
    see the oracle if it ever leaked into the serve trace)."""
    import jax.numpy as jnp
    from repro.core.query import exact_topk
    base, queries = _corpus(QL_L, QL_Q)
    tomb = jnp.zeros((QL_L,), bool).at[:10].set(True)
    fn = lambda b, q, t: exact_topk(q, b, t, k=K_TOP)
    return Fixture(fn=fn, args=(base, queries, tomb),
                   dims={"Q": QL_Q, "L": QL_L})


# ------------------------------------------------------------------ store --
def store_search(dtype: str) -> Fixture:
    """Quantized-store compact search — ``"int8"`` is the contract fixture
    (no fp32 [L, D] / [Q, C, D]), ``"fp32"`` its control (the full-width
    fp32 gather IS there)."""
    import jax.numpy as jnp
    from repro.core.query import QueryPipeline
    from repro.store import encode
    idx = _untrained_index(ST_L, d=ST_D, seed=7)
    rng = np.random.default_rng(7)
    base = rng.normal(size=(ST_L, ST_D)).astype(np.float32)
    queries = jnp.asarray(rng.normal(size=(ST_Q, ST_D)), jnp.float32)
    store = encode(base, dtype, 16)
    pipe = QueryPipeline(m=M_PROBE, tau=1, k=K_TOP, mode="compact",
                         topC=ST_C, store_dtype=dtype, refine_k=ST_KP)
    fn = lambda p, mem, s, q: pipe.search(p, mem, s, q)
    return Fixture(fn=fn,
                   args=(idx.params, idx.index.members, store, queries),
                   dims={"Q": ST_Q, "L": ST_L, "D": ST_D, "C": ST_C,
                         "kp": ST_KP})


def mega_store_search() -> Fixture:
    """mode="mega" over the SAME int8 toy as store_search — the
    ``query.mega_single_dispatch`` fixture: the whole search must trace as
    one top-level dispatch with the compact memory guarantees inside."""
    import jax.numpy as jnp
    from repro.core.query import QueryPipeline
    from repro.store import encode
    idx = _untrained_index(ST_L, d=ST_D, seed=7)
    rng = np.random.default_rng(7)
    base = rng.normal(size=(ST_L, ST_D)).astype(np.float32)
    queries = jnp.asarray(rng.normal(size=(ST_Q, ST_D)), jnp.float32)
    store = encode(base, "int8", 16)
    pipe = QueryPipeline(m=M_PROBE, tau=1, k=K_TOP, mode="mega",
                        topC=ST_C, store_dtype="int8", refine_k=ST_KP)
    fn = lambda p, mem, s, q: pipe.search(p, mem, s, q)
    return Fixture(fn=fn,
                   args=(idx.params, idx.index.members, store, queries),
                   dims={"Q": ST_Q, "L": ST_L, "D": ST_D, "C": ST_C,
                         "kp": ST_KP})


def mega_split_control() -> Fixture:
    """The SAME search as a per-stage pipeline — six separately-jitted
    stage dispatches (the pre-megakernel hot path, what search_staged
    runs) — MUST trip max_dispatches(1). Also the audit.py seeded
    violation (``--seed-violation split_dispatch``)."""
    import jax.numpy as jnp
    from repro.core import query as Q
    from repro.store import encode
    idx = _untrained_index(ST_L, d=ST_D, seed=7)
    rng = np.random.default_rng(7)
    base = rng.normal(size=(ST_L, ST_D)).astype(np.float32)
    queries = jnp.asarray(rng.normal(size=(ST_Q, ST_D)), jnp.float32)
    store = encode(base, "int8", 16)
    pipe = Q.QueryPipeline(m=M_PROBE, tau=1, k=K_TOP, mode="compact",
                           topC=ST_C, store_dtype="int8", refine_k=ST_KP)

    def fn(p, mem, s, q):
        logits = Q._stage_logits(pipe, p, q)
        bidx, keep = Q._stage_topm(pipe, logits)
        cands = Q._stage_gather(pipe, mem, bidx, keep, None, None)
        cid, cnt, n_cand = Q._stage_freq_topc(pipe, cands)
        cids = Q._stage_quant_coarse(pipe, q, s, cid, cnt)
        ids, scores = Q._stage_quant_refine(pipe, q, s, cids)
        return ids, scores, n_cand

    return Fixture(fn=fn,
                   args=(idx.params, idx.index.members, store, queries),
                   dims={"Q": ST_Q, "L": ST_L, "D": ST_D, "C": ST_C,
                         "kp": ST_KP})


# -------------------------------------------------------------------- fit --
def _fit_parts():
    import jax
    from repro.core.index import IRLIConfig
    from repro.core.network import ScorerConfig, scorer_init
    from repro.fit import FitData, FitEngine, FitState
    cfg = IRLIConfig(d=16, n_labels=FIT_L, n_buckets=FIT_B, n_reps=3,
                     d_hidden=32, K=FIT_K, rounds=2, epochs_per_round=3,
                     batch_size=50, lr=2e-3, affinity_chunk=FIT_CHUNK,
                     seed=0)
    scfg = ScorerConfig(d_in=cfg.d, d_hidden=cfg.d_hidden,
                        n_buckets=cfg.n_buckets, n_reps=cfg.n_reps,
                        loss=cfg.loss)
    params = scorer_init(jax.random.PRNGKey(0), scfg)
    rng = np.random.default_rng(0)
    n = 150
    x = rng.normal(size=(n, cfg.d)).astype(np.float32)
    ids = rng.integers(0, cfg.n_labels, (n, 5)).astype(np.int32)
    lv = rng.normal(size=(cfg.n_labels, cfg.d)).astype(np.float32)
    data = FitData.build(x, ids, label_vecs=lv, n_labels=cfg.n_labels,
                         chunk=cfg.affinity_chunk)
    eng = FitEngine(cfg, scfg)
    state = FitState.create(params, eng.opt.init(params),
                            np.zeros((cfg.n_reps, FIT_L), np.int32),
                            jax.random.PRNGKey(0))
    idx, w = eng.round_batches(n, 0, 0)
    return cfg, eng, params, data, state, idx, w


_FIT_DIMS = {"L": FIT_L, "B": FIT_B, "chunk": FIT_CHUNK, "K": FIT_K}


def fit_round() -> Fixture:
    """The whole compiled train+affinity+re-partition round."""
    _, eng, _, data, state, idx, w = _fit_parts()
    fn = lambda s, i, ww: eng._round_body(s, i, ww, data, None)
    return Fixture(fn=fn, args=(state, idx, w), dims=dict(_FIT_DIMS),
                   donate_argnums=(0,))


def fit_round_dense_control() -> Fixture:
    """The seed-style dense path: full [R, L, B] affinity then repartition —
    MUST trip the [L, B] detector."""
    import jax
    from repro.core import repartition as RP
    cfg, _, params, data, _, _, _ = _fit_parts()
    fn = lambda p, lv: RP.repartition(
        RP.affinity_ann(p, lv, cfg.loss), cfg.K, cfg.n_buckets, "exact",
        jax.random.PRNGKey(0))
    return Fixture(fn=fn, args=(params, data.label_vecs),
                   dims=dict(_FIT_DIMS))


def fit_round_sweep() -> Fixture:
    """make_fit_round called twice with fresh same-structure states — must
    compile exactly once (state 0 is donated, so each call gets its own)."""
    import jax
    import jax.numpy as jnp
    from repro.fit import FitState
    _, eng, params, data, state, idx, w = _fit_parts()
    # fresh COPIES for the second state: the first call donates its state,
    # and the two must not share buffers
    params2 = jax.tree.map(jnp.array, params)
    state2 = FitState.create(params2, eng.opt.init(params2),
                             np.zeros(state.assign.shape, np.int32),
                             jax.random.PRNGKey(0))
    jitted = eng.make_fit_round(data)
    variants = [("first", (state, idx, w)), ("repeat", (state2, idx, w))]
    return Fixture(fn=lambda: jnp.zeros(()), args=(),
                   sweep={"call": lambda v: jax.block_until_ready(
                              jitted(*v)[1]["loss"]),
                          "variants": variants, "jitted": jitted})


def sharded_fit_round() -> Fixture:
    """The (data x rep) mesh round — its collective schedule is the
    contract surface. Needs >= 4 devices (2 x 2 mesh)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.index import IRLIConfig
    from repro.core.network import ScorerConfig, scorer_init
    from repro.fit import FitData, FitEngine, FitState
    cfg = IRLIConfig(d=16, n_labels=FIT_L, n_buckets=FIT_B, n_reps=2,
                     d_hidden=32, K=FIT_K, rounds=2, epochs_per_round=2,
                     batch_size=48, lr=2e-3, affinity_chunk=FIT_CHUNK,
                     seed=0)
    scfg = ScorerConfig(d_in=cfg.d, d_hidden=cfg.d_hidden,
                        n_buckets=cfg.n_buckets, n_reps=cfg.n_reps,
                        loss=cfg.loss)
    params = scorer_init(jax.random.PRNGKey(0), scfg)
    rng = np.random.default_rng(0)
    n = 144
    data = FitData.build(
        rng.normal(size=(n, cfg.d)).astype(np.float32),
        rng.integers(0, cfg.n_labels, (n, 5)).astype(np.int32),
        label_vecs=rng.normal(size=(cfg.n_labels, cfg.d)).astype(np.float32),
        n_labels=cfg.n_labels, chunk=cfg.affinity_chunk)
    eng = FitEngine(cfg, scfg)
    state = FitState.create(params, eng.opt.init(params),
                            np.zeros((cfg.n_reps, FIT_L), np.int32),
                            jax.random.PRNGKey(0))
    idx, w = eng.round_batches(n, 0, 0)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "rep"))
    round_fn = eng._sharded_round(mesh, data, state)

    def fn(s, i, ww):
        ns, m = round_fn(s, i, ww, data)
        return jnp.sum(ns.assign), m["loss"]
    S = idx.shape[0]
    return Fixture(fn=fn, args=(state, idx, w),
                   dims={"L": FIT_L, "B": FIT_B, "steps": S,
                         "P": jax.device_count()})


# ------------------------------------------------------------------ online --
def _online_parts():
    """A refit-round toy built through the SAME helper the OnlineRefitLoop
    uses (online/refit.make_refit_round): drained traffic = 120 queries
    self-labeled with 5 served ids each, label vectors = the live corpus
    [OL_L, d]."""
    import jax
    from repro.core.index import IRLIConfig
    from repro.online.refit import make_refit_round
    cfg = IRLIConfig(d=16, n_labels=OL_L, n_buckets=OL_B, n_reps=2,
                     d_hidden=32, K=OL_K, rounds=1, epochs_per_round=2,
                     batch_size=48, lr=2e-3, affinity_chunk=OL_CHUNK, seed=3)
    from repro.core.network import ScorerConfig, scorer_init
    scfg = ScorerConfig(d_in=cfg.d, d_hidden=cfg.d_hidden,
                        n_buckets=cfg.n_buckets, n_reps=cfg.n_reps,
                        loss=cfg.loss)
    params = scorer_init(jax.random.PRNGKey(3), scfg)
    rng = np.random.default_rng(3)
    nq = 120
    x = rng.normal(size=(nq, cfg.d)).astype(np.float32)
    ids = rng.integers(0, OL_L, (nq, 5)).astype(np.int32)
    mask = np.ones((nq, 5), np.float32)
    lv = rng.normal(size=(OL_L, cfg.d)).astype(np.float32)
    engine, data, state = make_refit_round(
        cfg, params=params, assign=np.zeros((cfg.n_reps, OL_L), np.int32),
        x=x, label_ids=ids, label_mask=mask, label_vecs=lv,
        rng=jax.random.PRNGKey(3), rounds=1)
    idx, w = engine.round_batches(nq, 0, 0)
    return cfg, engine, params, data, state, idx, w


_OL_DIMS = {"L": OL_L, "B": OL_B, "chunk": OL_CHUNK, "K": OL_K}


def online_refit_round() -> Fixture:
    """One compiled incremental refit round over drained serve traffic."""
    _, eng, _, data, state, idx, w = _online_parts()
    fn = lambda s, i, ww: eng._round_body(s, i, ww, data, None)
    return Fixture(fn=fn, args=(state, idx, w), dims=dict(_OL_DIMS),
                   donate_argnums=(0,))


def online_refit_dense_control() -> Fixture:
    """Dense [L, B] affinity + re-partition over the refit dims — MUST
    trip the [L, B] detector."""
    import jax
    from repro.core import repartition as RP
    cfg, _, params, data, _, _, _ = _online_parts()
    fn = lambda p, lv: RP.repartition(
        RP.affinity_ann(p, lv, cfg.loss), cfg.K, cfg.n_buckets, "exact",
        jax.random.PRNGKey(0))
    return Fixture(fn=fn, args=(params, data.label_vecs),
                   dims=dict(_OL_DIMS))


def _swap_args():
    import jax.numpy as jnp
    rng = np.random.default_rng(23)
    R, B = 2, 24
    assign = jnp.asarray(rng.integers(0, B, (R, OL_CAP)), jnp.int32)
    tomb = jnp.zeros((OL_CAP,), bool).at[:100].set(True)
    vecs = jnp.asarray(rng.normal(size=(OL_CAP, OL_D)), jnp.float32)
    return B, assign, tomb, vecs


def online_swap_no_copy() -> Fixture:
    """The artifact swap's device work: rebuild_members over the full-
    capacity assignment, the [cap, d] payload passing through untouched."""
    from repro.artifact import rebuild_members
    B, assign, tomb, vecs = _swap_args()

    def fn(a, t, v):
        members, load = rebuild_members(a, t, B=B, max_load=OL_ML)
        return members, load, v      # payload moves by reference

    return Fixture(fn=fn, args=(assign, tomb, vecs),
                   dims={"cap": OL_CAP, "d": OL_D})


def online_swap_copy_control() -> Fixture:
    """Same rebuild, but the payload is touched (a full [cap, d] copy) —
    MUST trip both the dim detector and the intermediate budget."""
    from repro.artifact import rebuild_members
    B, assign, tomb, vecs = _swap_args()

    def fn(a, t, v):
        members, load = rebuild_members(a, t, B=B, max_load=OL_ML)
        return members, load, v * 1.0     # the copy the contract forbids

    return Fixture(fn=fn, args=(assign, tomb, vecs),
                   dims={"cap": OL_CAP, "d": OL_D})


# ----------------------------------------------------------- search cache --
def pipeline_cache_sweep() -> Fixture:
    """PipelineCache over a SearchParams sweep: 4 distinct resolved keys
    (two param sets, a dense variant, a second batch bucket), each repeated
    — exactly 4 compiles expected."""
    from repro.core.search_api import PipelineCache, SearchParams
    idx = _untrained_index(300, n_buckets=16)
    base, queries = _corpus(300, 8)
    cache = PipelineCache()
    spa = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact", topC=32)
    spb = spa.replace(topC=64)
    spd = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="dense")

    def call(variant):
        sp, qn = variant
        cache.search(sp, idx.params, idx.index.members, base, queries[:qn])

    variants = [("compact-a", (spa, 8)), ("compact-a-again", (spa, 8)),
                ("compact-b", (spb, 8)), ("compact-b-again", (spb, 8)),
                ("dense", (spd, 8)), ("dense-again", (spd, 8)),
                ("compact-a-bucket4", (spa, 4)),
                ("compact-a-bucket4-again", (spa, 4))]
    import jax.numpy as jnp
    return Fixture(fn=lambda: jnp.zeros(()), args=(),
                   sweep={"call": call, "variants": variants,
                          "counter": cache})


# ------------------------------------------------------------ distributed --
def production_search() -> Fixture:
    """make_production_search over every device as a corpus shard. The
    member/base VALUES are tiled from one shard — collective auditing only
    compiles, so content is irrelevant; the schedule is not."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.distributed import make_production_search
    from repro.core.search_api import SearchParams
    P_n = jax.device_count()
    Qn, k = 4, K_TOP
    idx = _untrained_index(256, n_buckets=16)
    base, queries = _corpus(256, Qn)
    members = jnp.broadcast_to(idx.index.members[None],
                               (P_n,) + idx.index.members.shape)
    bases = jnp.broadcast_to(base[None], (P_n,) + base.shape)
    mesh = Mesh(np.array(jax.devices()).reshape(P_n), ("data",))
    search = make_production_search(
        mesh, SearchParams(m=M_PROBE, tau=1, k=k, mode="compact", topC=32))

    def fn(p, mem, b, q):
        r = search(p, mem, b, q)
        return r.ids, r.scores, r.n_candidates
    return Fixture(fn=fn, args=(idx.params, members, bases, queries),
                   dims={"Q": Qn, "k": k, "P": P_n, "L": 256})


# ----------------------------------------------------------------- kernels --
def freq_topc_fixture(dense: bool = False) -> Fixture:
    """frequent_topc dispatch over [Q=6, W] candidates drawn from L=4096
    ids; the dense control builds the [Q, L] histogram the kernel exists to
    avoid."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(11)
    Qn, W, L, C = 6, 96, 4096, 48
    cands = jnp.asarray(rng.integers(0, L, (Qn, W)), jnp.int32)
    if dense:
        def fn(c):
            hist = jnp.zeros((c.shape[0], L), jnp.float32)
            hist = hist.at[jnp.arange(c.shape[0])[:, None], c].add(1.0)
            cnt, ids = jax.lax.top_k(hist, C)
            return ids, cnt
    else:
        from repro.kernels.freq_topc.ops import frequent_topc
        fn = lambda c: frequent_topc(c, C=C)
    return Fixture(fn=fn, args=(cands,), dims={"Q": Qn, "L": L, "C": C})


def quant_rerank_fixture(chunk: int | None = None) -> Fixture:
    """quant_coarse_topk dispatch: the fp32 dequant working set is bounded
    by ``chunk`` rows per query; ``chunk=C`` (the control) dequants the
    full [Q, C, D] width."""
    import jax.numpy as jnp
    from repro.kernels.quant_rerank.ops import quant_coarse_topk
    from repro.store import encode
    rng = np.random.default_rng(13)
    Qn, C, D, L, ch = 6, 80, 32, 320, 16
    store = encode(rng.normal(size=(L, D)).astype(np.float32), "int8", 16)
    queries = jnp.asarray(rng.normal(size=(Qn, D)), jnp.float32)
    cand_ids = jnp.asarray(rng.integers(0, L, (Qn, C)), jnp.int32)
    counts = jnp.ones((Qn, C), jnp.float32)
    use = ch if chunk is None else chunk
    fn = lambda q, cid, cnt: quant_coarse_topk(
        q, store.codes, store.scales, cid, cnt, tau=1, k=8,
        metric="angular", chunk=use)
    return Fixture(fn=fn, args=(queries, cand_ids, counts),
                   dims={"Q": Qn, "C": C, "D": D, "chunk": use})


def distance_topk_fixture(naive: bool = False) -> Fixture:
    """rerank_topk dispatch (masked l2 rerank). The naive control broadcasts
    the [Q, L, D] difference tensor pairwise_sim's expansion form avoids."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(17)
    Qn, L, D, k = 6, 512, 24, K_TOP
    queries = jnp.asarray(rng.normal(size=(Qn, D)), jnp.float32)
    base = jnp.asarray(rng.normal(size=(L, D)), jnp.float32)
    mask = jnp.ones((Qn, L), jnp.float32)
    if naive:
        def fn(q, b, m):
            sim = -jnp.sum((q[:, None, :] - b[None, :, :]) ** 2, axis=-1)
            sim = jnp.where(m > 0, sim, -jnp.inf)
            return jax.lax.top_k(sim, k)
    else:
        from repro.kernels.distance_topk.ops import rerank_topk
        fn = lambda q, b, m: rerank_topk(q, b, m, k=k, metric="l2")
    return Fixture(fn=fn, args=(queries, base, mask),
                   dims={"Q": Qn, "L": L, "D": D})


def irli_topk_fixture(naive: bool = False) -> Fixture:
    """scorer_topk dispatch (fused scoring + top-m). The naive control
    selects via a [Q, m, B] one-hot stack instead of lax.top_k."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(19)
    Qn, H, B, m = 6, 32, 1024, 7
    h = jnp.asarray(rng.normal(size=(Qn, H)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(H, B)), jnp.float32)
    b2 = jnp.asarray(rng.normal(size=(B,)), jnp.float32)
    if naive:
        def fn(hh, ww, bb):
            logits = hh @ ww + bb[None, :]
            _, idx = jax.lax.top_k(logits, m)
            onehot = jax.nn.one_hot(idx, B, dtype=jnp.float32)  # [Q, m, B]
            vals = jnp.sum(onehot * logits[:, None, :], axis=-1)
            return vals, idx
    else:
        from repro.kernels.irli_topk.ops import scorer_topk
        fn = lambda hh, ww, bb: scorer_topk(hh, ww, bb, m=m)
    return Fixture(fn=fn, args=(h, w2, b2),
                   dims={"Q": Qn, "B": B, "m": m})
