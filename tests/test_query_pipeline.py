"""QueryPipeline: dense-vs-compact equivalence across the frozen, streaming
(delta + tombstone), and per-shard serving paths; the compact-mode guarantee
that NO [Q, L] intermediate is ever materialized (checked over the jaxpr);
and the satellite fixes (auto_tau budget guard, rerank -1 padding, pad-safe
recall_at)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import query as Q
from repro.core.distributed import local_search
from repro.core.index import IRLIIndex, IRLIConfig
from repro.core.search_api import SearchParams
from repro.stream import MutableIRLIIndex

D, B, R, M_PROBE, K_TOP = 16, 16, 2, 4, 5


def _untrained_index(L, seed=0, n_buckets=B):
    """Scorer params + hash partition + inverted index, no training — the
    pipelines must agree for ANY params, so skip the slow fit."""
    cfg = IRLIConfig(d=D, n_labels=L, n_buckets=n_buckets, n_reps=R,
                     d_hidden=32, K=M_PROBE, seed=seed)
    idx = IRLIIndex(cfg)
    idx.build_index()
    return idx


def _pipelines(**kw):
    common = dict(m=M_PROBE, tau=kw.pop("tau", 1), k=K_TOP,
                  topC=kw.pop("topC", 1024), **kw)
    return (Q.QueryPipeline(mode="dense", **common),
            Q.QueryPipeline(mode="compact", **common))


def _assert_same_results(ids_d, ids_c, full_rows):
    """Rows with >= k survivors have a unique answer -> exact equality;
    partial rows must agree on the surviving id SET and the -1 padding."""
    ids_d, ids_c = np.asarray(ids_d), np.asarray(ids_c)
    full_rows = np.asarray(full_rows)
    assert full_rows.any(), "fixture produced no fully-served rows"
    np.testing.assert_array_equal(ids_d[full_rows], ids_c[full_rows])
    for a, b in zip(ids_d[~full_rows], ids_c[~full_rows]):
        assert set(a[a >= 0]) == set(b[b >= 0])
        assert (a >= 0).sum() == (b >= 0).sum()


# --------------------------------------------------------------- satellites --
def test_auto_tau_rejects_nonpositive_budget():
    freq = jnp.ones((2, 8))
    for budget in (0, -3):
        with pytest.raises(ValueError, match="budget"):
            Q.auto_tau(freq, budget=budget)


def test_rerank_emits_minus_one_for_empty_rows():
    rng = np.random.default_rng(0)
    base = jnp.asarray(rng.normal(size=(32, D)), jnp.float32)
    queries = jnp.asarray(rng.normal(size=(3, D)), jnp.float32)
    mask = np.ones((3, 32), bool)
    mask[1] = False                      # no surviving candidate at all
    mask[2, 3:] = False                  # fewer survivors than k
    ids = np.asarray(Q.rerank(queries, base, jnp.asarray(mask), k=K_TOP))
    assert (ids[1] == -1).all()
    assert (ids[2, :3] >= 0).all() and (ids[2, 3:] == -1).all()
    # compact analogue: all counts below tau -> all -1
    cid = jnp.asarray(rng.integers(0, 32, (3, 8)), jnp.int32)
    gids, _ = Q.rerank_gathered(queries, base, cid, jnp.zeros((3, 8)),
                                tau=1, k=K_TOP)
    assert (np.asarray(gids) == -1).all()


def test_recall_at_is_pad_safe():
    mask = jnp.zeros((2, 10), bool).at[:, 9].set(True)
    gt = jnp.asarray([[9, -1], [3, -1]], jnp.int32)
    # -1 must be IGNORED, not wrap to column 9 (which would count as a hit)
    assert float(Q.recall_at(mask, gt)) == pytest.approx(0.5)
    assert float(Q.recall_at(mask, jnp.full((2, 2), -1, jnp.int32))) == 0.0


def test_pipeline_mode_selection():
    assert Q.select_mode(1_000) == "dense"
    assert Q.select_mode(100_000_000) == "compact"
    assert Q.QueryPipeline.make(1_000).mode == "dense"
    assert Q.QueryPipeline.make(100_000_000).mode == "compact"
    assert Q.QueryPipeline.make(1_000, mode="compact").mode == "compact"
    # the dense-table budget is per BATCH: a huge batch against a mid-size
    # corpus must flip to compact even though L alone would pick dense
    assert Q.QueryPipeline.make(16_000, q_batch=512).mode == "dense"
    assert Q.QueryPipeline.make(16_000, q_batch=500_000).mode == "compact"
    with pytest.raises(ValueError, match="mode"):
        Q.QueryPipeline(mode="sparse")


# -------------------------------------------------- dense/compact agreement --
@pytest.mark.parametrize("tau", [1, 2])
def test_equivalence_frozen(tau):
    L = 500
    rng = np.random.default_rng(1)
    idx = _untrained_index(L)
    base = jnp.asarray(rng.normal(size=(L, D)), jnp.float32)
    queries = jnp.asarray(rng.normal(size=(12, D)), jnp.float32)
    dense, compact = _pipelines(tau=tau)
    ids_d, _, nc_d = dense.search(idx.params, idx.index.members, base, queries)
    ids_c, _, nc_c = compact.search(idx.params, idx.index.members, base,
                                    queries)
    # topC exceeds the candidate width -> identical survivor counts too
    np.testing.assert_array_equal(np.asarray(nc_d), np.asarray(nc_c))
    _assert_same_results(ids_d, ids_c, np.asarray(nc_d) >= K_TOP)


def _mutated_index(L=400, n_new=60, seed=2):
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(L, D)).astype(np.float32)
    mut = MutableIRLIIndex(_untrained_index(L, seed=seed), base)
    mut.insert(rng.normal(size=(n_new, D)).astype(np.float32))
    mut.delete(rng.choice(L, 40, replace=False))
    return mut, rng.normal(size=(10, D)).astype(np.float32)


@pytest.mark.parametrize("tau", [1, 2])
def test_equivalence_streaming(tau):
    """Streaming path: delta segments unioned, tombstones dropped — both
    modes, via MutableIRLIIndex.search."""
    mut, queries = _mutated_index()
    common = dict(m=M_PROBE, tau=tau, k=K_TOP, topC=1024)
    d = mut.search(queries, SearchParams(mode="dense", **common))
    c = mut.search(queries, SearchParams(mode="compact", **common))
    assert (d.mode, c.mode) == ("dense", "compact")
    np.testing.assert_array_equal(np.asarray(d.n_candidates),
                                  np.asarray(c.n_candidates))
    _assert_same_results(d.ids, c.ids, np.asarray(d.n_candidates) >= K_TOP)
    dead = np.asarray(mut.snapshot.tombstone).nonzero()[0]
    assert not np.isin(np.asarray(c.ids), dead).any()


def test_equivalence_per_shard():
    """distributed.local_search (the per-shard path of the sharded deploy)
    with live delta + tombstone state."""
    mut, queries = _mutated_index(seed=3)
    s = mut.snapshot
    kw = dict(delta_members=s.delta.members, tombstone=s.tombstone)
    common = dict(m=M_PROBE, tau=1, k=K_TOP, topC=1024)
    d = local_search(mut.params, s.members, s.vecs, queries,
                     SearchParams(mode="dense", **common), **kw)
    c = local_search(mut.params, s.members, s.vecs, queries,
                     SearchParams(mode="compact", **common), **kw)
    full = np.isfinite(np.asarray(d.scores)).all(axis=1)
    _assert_same_results(d.ids, c.ids, full)
    np.testing.assert_allclose(np.asarray(d.scores)[full],
                               np.asarray(c.scores)[full],
                               rtol=1e-5, atol=1e-5)


def test_server_serves_compact_pipeline():
    """IRLIServer(mode="compact") end to end over a mutable index: batched
    results equal the direct compact search."""
    from repro.serve.server import IRLIServer
    mut, queries = _mutated_index(seed=4)
    sp = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    want = mut.search(queries, sp)
    server = IRLIServer(mut, params=sp, max_batch=16, max_wait_ms=5.0)
    try:
        futs = [server.submit(q) for q in queries]
        got = [f.result(timeout=120) for f in futs]
    finally:
        server.close()
    np.testing.assert_array_equal(np.asarray(want.ids),
                                  np.stack([r.ids for r in got]))
    assert all(r.mode == "compact" for r in got)


# ----------------------------------------------------- no [Q, L] guarantee --
# The jaxpr proof lives as registered contracts declared beside the
# pipelines they govern (repro.core.query / repro.core.distributed) and is
# audited by `python -m repro.launch.audit`. The old dense positive control
# is now the contract's built-in control: a vacuous detector fails the
# audit itself (control_ok=False), so no separate control test is needed.
from repro import analysis


@pytest.mark.parametrize("cid", ["query.compact_no_dense_table",
                                 "query.compact_streaming_no_dense_table"])
def test_compact_never_materializes_QL(cid):
    """Acceptance: the compact pipeline's traced computation contains NO
    intermediate shaped [Q, L] — the 100M-scale serving guarantee — on both
    the frozen path and the streaming path (delta + tombstone)."""
    analysis.load_all()
    report = analysis.audit(cid)
    assert report.passed, report.to_dict()
    assert report.control_ok, report.control_detail


def test_local_search_compact_never_materializes_QL():
    analysis.load_all()
    report = analysis.audit("distributed.local_search_compact_no_dense_table")
    assert report.passed, report.to_dict()
    assert report.control_ok, report.control_detail


# ------------------------------------------------ on-device exact oracle ----
@pytest.mark.parametrize("metric", ["angular", "l2"])
def test_exact_neighbors_matches_full_width_oracle(metric):
    """The blocked group-max oracle returns exactly exact_topk's neighbour
    sets (it drops only the full-width top-k), including a corpus whose
    size is not a multiple of the group."""
    from repro.data.synthetic import clustered_ann_device
    base, queries, train_q = clustered_ann_device(
        jax.random.PRNGKey(4), 1000, 64, 32, 24, n_clusters=16,
        metric=metric)
    assert base.shape == (1000, 24) and train_q.shape == (32, 24)
    if metric == "angular":
        np.testing.assert_allclose(np.linalg.norm(np.asarray(base), axis=1),
                                   1.0, rtol=1e-5)
    got = Q.exact_neighbors(queries, base, 10, metric=metric, q_block=16,
                            group=128)
    want = Q.exact_topk(queries, base, jnp.zeros(1000, bool), k=10,
                        metric=metric)
    np.testing.assert_array_equal(np.sort(np.asarray(got), 1),
                                  np.sort(np.asarray(want), 1))
    with pytest.raises(ValueError, match="multiple"):
        Q.exact_neighbors(queries[:40], base, 10, q_block=16)
