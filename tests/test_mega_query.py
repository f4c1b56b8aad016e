"""mode="mega" — the single-dispatch megakernel (kernels/mega_query).

The acceptance pin: mega is BITWISE identical to the jitted compact path
(the same reference test_obs_integration uses) on every surface — frozen
pipeline, mutable index with live delta + tombstone + hot-replica state,
and the distributed local_search — across metrics, store dtypes, and the
adaptive-m probe policy. The Pallas kernel itself is parity-tested in
interpret mode against its jnp oracle (mega_query/ref.py), auto-mode
resolution accounts for the kernel's VMEM tile footprint, and the
single-dispatch guarantee is asserted through the registered contract.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import query as Q
from repro.core.index import IRLIConfig, IRLIIndex
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


def _assert_bitwise(got, ref):
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        g, r = np.asarray(g), np.asarray(r)
        assert g.dtype == r.dtype and g.shape == r.shape
        np.testing.assert_array_equal(g.view(np.uint8), r.view(np.uint8))


# ------------------------------------------------ mega == compact (jitted) --
@pytest.mark.parametrize("metric,store_dtype,adaptive", [
    ("angular", "fp32", False),
    ("angular", "fp32", True),
    ("l2", "fp32", False),
    ("angular", "int8", False),
    ("angular", "int8", True),
    ("l2", "int8", False),
    ("l2", "bf16", False),
    ("angular", "bf16", True),
])
def test_mega_bitwise_equals_compact(metric, store_dtype, adaptive):
    """pipe.search with mode="mega" returns the EXACT arrays of the jitted
    compact path (what PipelineCache serves) — dtype x metric x adaptive."""
    idx, base, queries = _fixture()
    if store_dtype != "fp32":
        from repro.store.quantized import encode
        base = encode(base, dtype=store_dtype, block=8,
                      keep_exact=(store_dtype == "int8"))
    pipe = Q.QueryPipeline(
        mode="mega", m=M_PROBE, tau=1, k=K_TOP, topC=64, metric=metric,
        store_dtype=store_dtype,
        refine_k=16 if store_dtype != "fp32" else 0,
        adaptive_m=adaptive, probe_mass=0.6 if adaptive else 1.0)
    compact = dataclasses.replace(pipe, mode="compact")
    ref = jax.jit(type(compact).search, static_argnums=0)(
        compact, idx.params, idx.index.members, base, queries)
    got = pipe.search(idx.params, idx.index.members, base, queries)
    _assert_bitwise(got, ref)


def test_mega_mutable_delta_tombstone():
    """Through MutableIRLIIndex.search with live delta segments and
    tombstones: mega serves the union and masks deletions, bitwise equal
    to compact."""
    idx, base, queries = _fixture(seed=2)
    rng = np.random.default_rng(2)
    mut = MutableIRLIIndex(idx, np.asarray(base))
    mut.insert(rng.normal(size=(50, D)).astype(np.float32))
    dead = rng.choice(400, 30, replace=False)
    mut.delete(dead)
    spm = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="mega")
    a = mut.search(queries, spm)
    b = mut.search(queries, spm.replace(mode="compact"))
    assert a.mode == "mega" and b.mode == "compact"
    _assert_bitwise((a.ids, a.scores, a.n_candidates),
                    (b.ids, b.scores, b.n_candidates))
    assert not np.isin(np.asarray(a.ids), dead).any()


def test_mega_hot_replicas_union_in():
    """An id reachable ONLY through a replica segment is retrieved by
    mode="mega" exactly as by compact (test_online's orphan construction)."""
    from repro.artifact import IndexArtifact, rebuild_members
    idx, base, queries = _fixture(seed=3)
    midx = MutableIRLIIndex(idx, np.asarray(base))
    s = midx.snapshot
    X = 123
    cap_assign = np.asarray(s.assign).copy()
    cap_assign[:, X] = B                 # sentinel: in vecs, in no bucket
    members, load = rebuild_members(
        jnp.asarray(cap_assign, jnp.int32), s.tombstone,
        B=B, max_load=int(s.members.shape[-1]))
    replicas = jnp.full((R, B, 4), -1, jnp.int32).at[:, :, 0].set(X)
    art = dataclasses.replace(
        IndexArtifact.from_mutable(midx, version=midx.epoch + 1),
        assign=jnp.asarray(cap_assign, jnp.int32), members=members,
        load=load, replicas=replicas).reseal()
    midx.install_artifact(art)
    q = np.asarray(base)[X:X + 1]
    spm = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="mega",
                       hot_replicas=True)
    a = midx.search(q, spm)
    b = midx.search(q, spm.replace(mode="compact"))
    _assert_bitwise((a.ids, a.scores, a.n_candidates),
                    (b.ids, b.scores, b.n_candidates))
    assert np.asarray(a.ids)[0, 0] == X  # replica-only id found, rank 1


def test_mega_local_search_matches_compact():
    """The distributed per-shard surface serves mode="mega" identically."""
    from repro.core.distributed import local_search
    idx, base, queries = _fixture(seed=4)
    spm = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="mega")
    a = local_search(idx.params, idx.index.members, base, queries, spm)
    b = local_search(idx.params, idx.index.members, base, queries,
                     spm.replace(mode="compact"))
    _assert_bitwise((a.ids, a.scores, a.n_candidates),
                    (b.ids, b.scores, b.n_candidates))


def test_mega_staged_matches_and_records():
    """search_staged keeps the fused path as ONE stage: bit-identical
    output, a stage="mega" histogram bucket, and the dispatch counter."""
    idx, base, queries = _fixture(seed=5)
    reg = obs.MetricRegistry()
    pipe = Q.QueryPipeline(mode="mega", m=M_PROBE, tau=1, k=K_TOP, topC=64)
    fused = pipe.search(idx.params, idx.index.members, base, queries)
    staged = pipe.search_staged(idx.params, idx.index.members, base,
                                queries, registry=reg)
    _assert_bitwise(staged, fused)
    snap = reg.snapshot()
    key = 'serve_stage_seconds{stage="mega"}'
    assert key in snap and snap[key]["count"] == 1
    assert snap["serve_mega_dispatch_total"]["value"] == 1


# --------------------------------------- interpret-mode kernel vs oracle ----
@pytest.mark.parametrize("kind,metric,adaptive", [
    ("fp32", "angular", False),
    ("int8", "l2", True),
])
def test_kernel_interpret_parity(kind, metric, adaptive):
    """The Pallas megakernel (interpret mode) matches the jnp oracle:
    identical candidate ids (order-free — the kernel's accumulation order
    differs from einsum's) and matching scores/counts."""
    from repro.kernels.mega_query.mega_query import mega_query
    from repro.kernels.mega_query.ref import mega_search_ref
    idx, base, queries = _fixture(L=200, n_q=4, seed=6)
    p = idx.params
    members = idx.index.members
    kw = dict(m=3, tau=1, topC=16, k=4, metric=metric,
              adaptive_m=adaptive, probe_mass=0.6 if adaptive else 1.0)
    if kind == "fp32":
        store = base
        args = (members, base, None, None)
        refine_k = 0
    else:
        from repro.store.quantized import encode
        store = encode(np.asarray(base), "int8", 8, keep_exact=True)
        args = (members, store.codes, store.scales, store.exact)
        refine_k = 8
    ids_k, sc_k, nc_k = mega_query(
        p["w1"], p["b1"], p["w2"], p["b2"], *args, queries,
        refine_k=refine_k, kind=kind,
        block=store.block if kind == "int8" else 1, interpret=True, **kw)
    ids_r, sc_r, nc_r = mega_search_ref(
        p, members, store, queries, refine_k=refine_k, **kw)
    np.testing.assert_array_equal(np.sort(ids_k, axis=1),
                                  np.sort(np.asarray(ids_r), axis=1))
    np.testing.assert_allclose(np.sort(sc_k, axis=1),
                               np.sort(np.asarray(sc_r), axis=1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(nc_k, nc_r)


# ----------------------------------------------- auto mode + VMEM budget ----
def test_auto_mode_picks_mega_when_it_fits():
    """Auto selection never picks mega: its Pallas kernel has not run on a
    chip, and its fused-jit form only repackages compact. A request that
    names mode="mega" keeps it, served by the fused jit, and no request can
    route to the unserved "mega_query" kernel."""
    assert Q.select_mode(100_000_000) == "compact"
    assert SearchParams().resolve(100_000_000).mode == "compact"
    # small fp32 corpus still prefers dense
    assert SearchParams().resolve(1_000).mode == "dense"
    r = SearchParams(mode="mega").resolve(100_000_000)
    assert r.mode == "mega" and r.kernels == ()
    assert "mega_query" not in Q.KERNELS
    for mode in ("mega", "compact"):
        with pytest.raises(ValueError, match="drawn from"):
            SearchParams(mode=mode, kernels=("mega_query",))


def test_auto_mode_legacy_signature_unchanged():
    """No search-shape knobs -> the historic dense/compact resolution."""
    assert Q.select_mode(1_000) == "dense"
    assert Q.select_mode(100_000_000) == "compact"


def test_auto_mode_oversized_shape_falls_back_to_compact():
    """A (m, topC) combo whose padded candidate width exceeds the sort-lane
    cap resolves compact, and the megakernel's fit gate refuses it — on
    the real geometry and the device kind's own VMEM size."""
    from repro.kernels.mega_query.ops import mega_fits, mega_vmem_bytes
    geom = dict(d=128, H=1024, B=4096, R=2, ML=64, D=128, block=32)
    sp = SearchParams(m=512, topC=32768, k=10)
    assert sp.resolve(100_000_000).mode == "compact"
    assert not mega_fits(512, 32768, 0, 10, geom=geom,
                         device_kind="TPU v5 lite")
    # footprint gate (not just the width cap): widen the member lists so
    # the width stays at the cap while the VMEM residents blow the budget
    # of a 16 MiB-VMEM chip
    geom = dict(geom, ML=128)
    assert mega_vmem_bytes(128, 32768, 32768, 10, geom=geom) > \
        mega_vmem_bytes(4, 256, 64, 10, geom=geom)
    assert not mega_fits(128, 32768, 32768, 10, geom=geom,
                         device_kind="TPU v4")
    assert mega_fits(4, 256, 64, 10, geom=geom, device_kind="TPU v4")
    # a device kind without a recorded VMEM size is an error, not a guess
    with pytest.raises(ValueError, match="VMEM"):
        mega_fits(4, 256, 64, 10, geom=geom, device_kind="TPU v99")


def test_single_dispatch_contract_audit():
    """mode="mega" traces to exactly ONE top-level dispatch with no [Q, L]
    table and no fp32 [L, D] decode — proven by the registered contract
    (its control is the six-dispatch staged sequence)."""
    from repro import analysis
    analysis.load_all()
    r = analysis.audit("query.mega_single_dispatch")
    assert r.passed, r.to_dict()
