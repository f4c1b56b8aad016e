"""Quantized tiered vector store (src/repro/store, docs/store.md).

Covers the acceptance criteria of the store subsystem:
  - store_dtype="fp32" is BIT-IDENTICAL to serving the raw base array on
    the compact path (ids AND scores), across frozen + streaming surfaces
  - int8 + exact-tier refine matches the full-fp32 rerank's top-k ids on
    >= 99% of queries; dequant-refine (no exact tier) stays close
  - with store_dtype="int8" the traced search NEVER materializes an fp32
    [L, D] or [Q, topC, D] array (jaxpr walk, with a positive control)
  - quantization error bound: |x - decode(encode(x))| <= scale/2 per
    element (deterministic + hypothesis property test)
  - streaming: insert quantizes into the tier, compaction re-encodes
    atomically, CheckpointManager round-trips codes + scales
  - the satellite rerank fixes (-1 emission on fully-tau-masked rows, and
    on the distance_topk ops dispatch)
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import query as Q
from repro.core.distributed import local_search, make_production_search
from repro.core.index import IRLIIndex, IRLIConfig
from repro.core.search_api import SearchParams
from repro.store import (QuantizedStore, decode, dequant_rows, encode,
                         rerank_two_stage)
from repro.stream import MutableIRLIIndex

D, B, R, M_PROBE, K_TOP = 16, 16, 2, 4, 5
BLOCK = 8


def _untrained_index(L, seed=0, n_buckets=B, d=D):
    cfg = IRLIConfig(d=d, n_labels=L, n_buckets=n_buckets, n_reps=R,
                     d_hidden=32, K=M_PROBE, seed=seed)
    idx = IRLIIndex(cfg)
    idx.build_index()
    return idx


def _corpus(L, n_q=16, seed=0, d=D):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(L, d)).astype(np.float32),
            rng.normal(size=(n_q, d)).astype(np.float32))


# ------------------------------------------------------------ validation ----
def test_search_params_store_knobs_validated():
    with pytest.raises(ValueError, match="store_dtype"):
        SearchParams(store_dtype="int4")
    with pytest.raises(ValueError, match="refine_k"):
        SearchParams(refine_k=-1)
    with pytest.raises(ValueError, match="dense"):
        SearchParams(mode="dense", store_dtype="int8")
    with pytest.raises(ValueError, match="store_dtype"):
        Q.QueryPipeline(store_dtype="fp8")
    with pytest.raises(ValueError, match="dense"):
        Q.QueryPipeline(mode="dense", store_dtype="bf16")


def test_mode_auto_accounts_code_bytes():
    """A quantized store never resolves dense — dense would decode the
    whole [L, D] corpus back to fp32 — even at corpus sizes where fp32
    would pick dense; auto never picks mega, whose kernel has not run
    on a chip."""
    assert Q.select_mode(1_000) == "dense"
    assert Q.select_mode(1_000, store_dtype="int8") == "compact"
    assert SearchParams().resolve(1_000).mode == "dense"
    sp = SearchParams(store_dtype="int8")
    assert sp.resolve(1_000).mode == "compact"
    assert Q.QueryPipeline.make(1_000, store_dtype="int8").mode == "compact"


def test_store_dtype_mismatch_fails_fast():
    base, queries = _corpus(200)
    idx = _untrained_index(200)
    st8 = encode(base, "int8", BLOCK)
    with pytest.raises(ValueError, match="store_dtype"):
        idx.search(queries, st8, SearchParams())          # fp32 params, int8
    with pytest.raises(ValueError, match="QuantizedStore"):
        idx.search(queries, base, SearchParams(store_dtype="int8"))


# ------------------------------------------------------------ round trip ----
def test_roundtrip_error_bounded_by_half_scale():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(64, D)).astype(np.float32)
    x[0] = 0.0                              # all-zero row: exact round trip
    x[1] *= 1e4                             # large dynamic range
    x[2, :BLOCK] = 0.0                      # zero BLOCK next to live blocks
    st = encode(x, "int8", BLOCK)
    err = np.abs(x - np.asarray(decode(st)))
    bound = np.repeat(np.asarray(st.scales), BLOCK, axis=-1) / 2
    assert (err <= bound * (1 + 1e-5) + 1e-7).all()
    assert (np.asarray(decode(st))[0] == 0).all()


def test_roundtrip_error_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(L=st.integers(1, 32), nb=st.integers(1, 4),
           scale=st.floats(1e-3, 1e3), seed=st.integers(0, 1000))
    def check(L, nb, scale, seed):
        rng = np.random.default_rng(seed)
        x = (rng.normal(size=(L, nb * BLOCK)) * scale).astype(np.float32)
        s = encode(x, "int8", BLOCK)
        err = np.abs(x - np.asarray(decode(s)))
        bound = np.repeat(np.asarray(s.scales), BLOCK, axis=-1) / 2
        assert (err <= bound * (1 + 1e-5) + 1e-7).all()

    check()


def test_append_matches_fresh_encode():
    base, _ = _corpus(48)
    extra = np.random.default_rng(9).normal(size=(16, D)).astype(np.float32)
    st = encode(np.concatenate([base, np.zeros_like(extra)]), "int8", BLOCK)
    st2 = st.append(np.arange(48, 64), extra)
    want = encode(np.concatenate([base, extra]), "int8", BLOCK)
    np.testing.assert_array_equal(np.asarray(st2.codes),
                                  np.asarray(want.codes))
    np.testing.assert_array_equal(np.asarray(st2.scales),
                                  np.asarray(want.scales))
    # dequant_rows agrees with full decode on arbitrary gathers
    ids = jnp.asarray([0, 63, 5, 48])
    np.testing.assert_array_equal(np.asarray(dequant_rows(st2, ids)),
                                  np.asarray(decode(st2))[np.asarray(ids)])


# ------------------------------------------------------- result equivalence --
def test_fp32_store_bit_identical():
    """Acceptance: dense/compact/store results are bit-identical for
    store_dtype="fp32" — the store is a pure payload swap."""
    L = 500
    base, queries = _corpus(L, n_q=12, seed=1)
    idx = _untrained_index(L, seed=1)
    sp = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    raw = idx.search(queries, base, sp)
    via_store = idx.search(queries, encode(base, "fp32"), sp)
    np.testing.assert_array_equal(np.asarray(raw.ids),
                                  np.asarray(via_store.ids))
    np.testing.assert_array_equal(np.asarray(raw.scores),
                                  np.asarray(via_store.scores))
    np.testing.assert_array_equal(np.asarray(raw.n_candidates),
                                  np.asarray(via_store.n_candidates))


def _id_set_match(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.mean([set(r[r >= 0]) == set(s[s >= 0]) for r, s in zip(a, b)])


def test_int8_with_exact_refine_matches_fp32():
    """Acceptance: int8 coarse + exact fp32 refine returns the same top-k
    ids as the full-fp32 rerank on >= 99% of queries."""
    L = 2000
    base, queries = _corpus(L, n_q=128, seed=2)
    idx = _untrained_index(L, seed=2)
    sp32 = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    sp8 = sp32.replace(store_dtype="int8", refine_k=64)
    want = idx.search(queries, base, sp32)
    got = idx.search(queries, encode(base, "int8", BLOCK, keep_exact=True),
                     sp8)
    assert _id_set_match(want.ids, got.ids) >= 0.99
    # survivor counts come from the SAME frequency stage: exactly equal
    np.testing.assert_array_equal(np.asarray(want.n_candidates),
                                  np.asarray(got.n_candidates))


def test_int8_dequant_refine_stays_close():
    """No exact tier: refine re-scores on dequantized rows. Rankings may
    flip near ties, but the returned sets stay close to fp32."""
    L = 2000
    base, queries = _corpus(L, n_q=128, seed=4)
    idx = _untrained_index(L, seed=4)
    sp32 = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    want = idx.search(queries, base, sp32)
    got = idx.search(queries, encode(base, "int8", BLOCK),
                     sp32.replace(store_dtype="int8", refine_k=64))
    ids_w, ids_g = np.asarray(want.ids), np.asarray(got.ids)
    overlap = np.mean([len(set(a[a >= 0]) & set(b[b >= 0]))
                       / max(1, (a >= 0).sum())
                       for a, b in zip(ids_w, ids_g)])
    assert overlap >= 0.9, overlap


def test_bf16_store_close_to_fp32():
    L = 800
    base, queries = _corpus(L, n_q=64, seed=5)
    idx = _untrained_index(L, seed=5)
    sp32 = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    want = idx.search(queries, base, sp32)
    got = idx.search(queries, encode(base, "bf16", keep_exact=True),
                     sp32.replace(store_dtype="bf16", refine_k=64))
    assert _id_set_match(want.ids, got.ids) >= 0.99


# ------------------------------------------------------ memory guarantee ----
def test_int8_path_never_materializes_fp32_payload():
    """Acceptance: with store_dtype="int8" the traced search holds NO fp32
    array shaped [L, D] (a full decode) nor [Q, topC, D] (a full-width fp32
    candidate gather) — fp32 appears at most at the [Q, k', D] refine.
    Proven by the contract registered beside repro.store.rerank; the old
    fp32-store positive control is the contract's built-in control."""
    from repro import analysis
    analysis.load_all()
    report = analysis.audit("store.int8_no_fp32_payload")
    assert report.passed, report.to_dict()
    assert report.control_ok, report.control_detail


def test_int8_store_requires_scales():
    """Regression: a hand-built int8 store without scales must fail loudly
    at every serving entry, not silently coarse-rank raw unscaled codes
    (or die inside a trace). (Validation lives at the use sites, not
    __post_init__ — jax reconstructs pytrees with stand-in children.)"""
    rng = np.random.default_rng(23)
    base, queries = _corpus(40, n_q=2, seed=23)
    idx = _untrained_index(40, seed=23)
    bad = QuantizedStore("int8", BLOCK, encode(base, "int8", BLOCK).codes)
    with pytest.raises(ValueError, match="scales"):
        idx.search(queries, bad, SearchParams(store_dtype="int8"))
    with pytest.raises(ValueError, match="scales"):
        rerank_two_stage(jnp.asarray(queries), bad,
                         jnp.zeros((2, 4), jnp.int32), jnp.ones((2, 4)),
                         tau=1, k=2)
    bad_bf16 = QuantizedStore("bf16", BLOCK,
                              jnp.zeros((40, D), jnp.bfloat16),
                              jnp.ones((40, 2)))
    with pytest.raises(ValueError, match="scales"):
        idx.search(queries, bad_bf16, SearchParams(store_dtype="bf16"))


def test_gathered_l2_resolves_near_duplicate_rows():
    """Regression: the gathered/refine l2 path uses the difference form
    -Σ(q-v)² — pairwise_sim's expansion form loses the ordering of
    near-duplicate rows at large norms to fp32 cancellation."""
    q = jnp.asarray([[1000.0, 0.0]])
    vecs = jnp.asarray([[[1000.001, 0.0],      # dist² = 1e-6  (closer)
                         [1000.0, 0.002]]])    # dist² = 4e-6
    sim = np.asarray(Q.gathered_sim(q, vecs, "l2"))[0]
    assert sim[0] > sim[1], sim                # exact order preserved
    # rtol covers fp32 rounding of the INPUT coordinates (1000.001 is not
    # representable); the expansion form would be off by ~0.06 absolute
    np.testing.assert_allclose(sim, [-1e-6, -4e-6], rtol=0.1)
    # and the two-stage refine inherits it (exact tier, l2 metric)
    base = np.asarray(vecs[0], np.float32)
    st = encode(base, "int8", 2, keep_exact=True)
    ids, scores = rerank_two_stage(
        jnp.asarray(q), st, jnp.asarray([[0, 1]], jnp.int32),
        jnp.ones((1, 2)), tau=1, k=2, refine_k=2, metric="l2")
    assert list(np.asarray(ids)[0]) == [0, 1]


def test_two_stage_k_beyond_topC_pads():
    """k larger than the candidate budget: the unservable tail is -1/-inf
    padded (regression — this used to crash inside lax.top_k)."""
    rng = np.random.default_rng(21)
    base = rng.normal(size=(64, D)).astype(np.float32)
    st = encode(base, "int8", BLOCK)
    q = jnp.asarray(rng.normal(size=(3, D)), jnp.float32)
    cid = jnp.asarray(rng.integers(0, 64, (3, 6)), jnp.int32)
    cnt = jnp.ones((3, 6))
    ids, scores = rerank_two_stage(q, st, cid, cnt, tau=1, k=12, refine_k=0)
    assert ids.shape == (3, 12) and scores.shape == (3, 12)
    assert (np.asarray(ids)[:, 6:] == -1).all()
    assert not np.isfinite(np.asarray(scores)[:, 6:]).any()
    assert (np.asarray(ids)[:, :6] >= 0).all()


# ------------------------------------------------------- satellite fixes ----
def test_rerank_gathered_tau_masks_whole_row():
    """Regression: a query row whose candidates ALL fall below tau must
    emit -1 ids (not arbitrary ids), also when other rows are served."""
    rng = np.random.default_rng(0)
    base = jnp.asarray(rng.normal(size=(32, D)), jnp.float32)
    queries = jnp.asarray(rng.normal(size=(2, D)), jnp.float32)
    cid = jnp.asarray(rng.integers(0, 32, (2, 8)), jnp.int32)
    cnt = jnp.asarray([[3.0] * 8, [1.0] * 8])      # row 1 all below tau=2
    ids, scores = Q.rerank_gathered(queries, base, cid, cnt, tau=2, k=K_TOP)
    assert (np.asarray(ids[0]) >= 0).any()
    assert (np.asarray(ids[1]) == -1).all()
    assert not np.isfinite(np.asarray(scores[1])).any()
    # same contract on the two-stage store path
    st = encode(np.asarray(base), "int8", BLOCK)
    ids2, scores2 = rerank_two_stage(queries, st, cid, cnt, tau=2, k=K_TOP,
                                     refine_k=8)
    assert (np.asarray(ids2[1]) == -1).all()
    assert not np.isfinite(np.asarray(scores2[1])).any()
    assert (np.asarray(ids2[0]) >= 0).any()


def test_rerank_topk_ops_emits_minus_one():
    """Regression: the distance_topk dispatch (kernels' fused rerank) now
    pins the -1 contract for starved rows like rerank/rerank_gathered."""
    from repro.kernels.distance_topk.ops import rerank_topk
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(4, D)), jnp.float32)
    base = jnp.asarray(rng.normal(size=(64, D)), jnp.float32)
    mask = np.ones((4, 64), np.float32)
    mask[2] = 0.0                                # fully starved row
    mask[3, 3:] = 0.0                            # fewer survivors than k
    vals, ids = rerank_topk(q, base, jnp.asarray(mask), k=K_TOP)
    ids = np.asarray(ids)
    assert (ids[2] == -1).all()
    assert (ids[3, :3] >= 0).all() and (ids[3, 3:] == -1).all()
    assert (ids[:2] >= 0).all()


def test_distance_topk_ref_uses_pairwise_sim():
    """Metric dedupe: the kernel oracle scores EXACTLY like pairwise_sim
    (the one metric implementation) for both metrics."""
    from repro.kernels.distance_topk.ref import distance_topk_ref
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(4, D)), jnp.float32)
    base = jnp.asarray(rng.normal(size=(32, D)), jnp.float32)
    mask = jnp.ones((4, 32))
    for kernel_metric, query_metric in (("dot", "angular"), ("l2", "l2")):
        vals, _ = distance_topk_ref(q, base, mask, k=3, metric=kernel_metric)
        want = -np.sort(-np.asarray(Q.pairwise_sim(q, base, query_metric)),
                        axis=1)[:, :3]
        np.testing.assert_array_equal(np.asarray(vals), want)


# ---------------------------------------------------------- streaming tier --
def _mutable(store_dtype="int8", L=300, seed=11):
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(L, D)).astype(np.float32)
    idx = _untrained_index(L, seed=seed)
    mut = MutableIRLIIndex(idx, base, store_dtype=store_dtype,
                           store_block=BLOCK)
    return mut, rng


def test_streaming_insert_quantizes_and_serves():
    mut, rng = _mutable()
    sp8 = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact",
                       store_dtype="int8", refine_k=32)
    sp32 = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    new = rng.normal(size=(40, D)).astype(np.float32)
    ids = mut.insert(new)
    mut.delete(rng.choice(300, 30, replace=False))
    # the tier holds codes for the inserted rows (quantized on insert)
    s = mut.snapshot
    np.testing.assert_array_equal(
        np.asarray(s.store.codes)[np.asarray(ids)],
        np.asarray(encode(new, "int8", BLOCK).codes))
    q = rng.normal(size=(24, D)).astype(np.float32)
    r8, r32 = mut.search(q, sp8), mut.search(q, sp32)
    # exact tier == the fp32 buffer, so int8 serving matches fp32 ~always
    assert _id_set_match(r32.ids, r8.ids) >= 0.95
    dead = np.asarray(s.tombstone).nonzero()[0]
    assert not np.isin(np.asarray(r8.ids), dead).any()
    # compaction re-encodes atomically and preserves results exactly
    epoch = mut.epoch
    mut.compact()
    assert mut.epoch == epoch + 1
    r8c = mut.search(q, sp8)
    np.testing.assert_array_equal(np.asarray(r8.ids), np.asarray(r8c.ids))
    np.testing.assert_array_equal(
        np.asarray(mut.snapshot.store.codes),
        np.asarray(encode(np.asarray(mut.snapshot.vecs), "int8",
                          BLOCK).codes))


def test_streaming_without_store_rejects_int8_params():
    mut, rng = _mutable(store_dtype="fp32")
    with pytest.raises(ValueError, match="store_dtype"):
        mut.search(rng.normal(size=(2, D)).astype(np.float32),
                   SearchParams(store_dtype="int8"))


def test_checkpoint_roundtrips_codes_and_scales(tmp_path):
    from repro.checkpoint.checkpointer import CheckpointManager
    mut, rng = _mutable(seed=13)
    new = rng.normal(size=(25, D)).astype(np.float32)
    mut.insert(new)
    mut.delete([1, 2, 3])
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mut.save(mgr, step=1)
    # the npz literally stores int8 codes — the 4x on-disk saving is real
    with np.load(os.path.join(mgr.dir, "step_000000000001",
                              "arrays.npz")) as z:
        assert z["stream/store_codes"].dtype == np.int8
        assert z["stream/store_scales"].dtype == np.float32
    mut2, _ = _mutable(seed=13)          # fresh index, same config
    step, tree, manifest = mgr.restore_latest()
    mut2.load_state(tree, manifest["extra"])
    s1, s2 = mut.snapshot, mut2.snapshot
    np.testing.assert_array_equal(np.asarray(s1.store.codes),
                                  np.asarray(s2.store.codes))
    np.testing.assert_array_equal(np.asarray(s1.store.scales),
                                  np.asarray(s2.store.scales))
    q = rng.normal(size=(8, D)).astype(np.float32)
    sp8 = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact",
                       store_dtype="int8")
    np.testing.assert_array_equal(np.asarray(mut.search(q, sp8).ids),
                                  np.asarray(mut2.search(q, sp8).ids))
    # restoring a quantized checkpoint into an fp32-built index fails fast
    mut3, _ = _mutable(store_dtype="fp32", seed=13)
    with pytest.raises(ValueError, match="store_dtype"):
        mut3.load_state(tree, manifest["extra"])


# ----------------------------------------------------------- distributed ----
def test_local_search_serves_store():
    L = 600
    base, queries = _corpus(L, n_q=10, seed=17)
    idx = _untrained_index(L, seed=17)
    sp32 = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact")
    w = local_search(idx.params, idx.index.members, jnp.asarray(base),
                     queries, sp32)
    g = local_search(idx.params, idx.index.members,
                     encode(base, "int8", BLOCK, keep_exact=True), queries,
                     sp32.replace(store_dtype="int8", refine_k=64))
    assert _id_set_match(w.ids, g.ids) >= 0.99
    np.testing.assert_array_equal(np.asarray(w.n_candidates),
                                  np.asarray(g.n_candidates))


def test_production_search_store_pytree_specs():
    """make_production_search accepts a QuantizedStore as the sharded base
    (per-leaf specs + block-dim strip) — exercised on a 1-device mesh."""
    L = 256
    base, queries = _corpus(L, n_q=8, seed=19)
    idx = _untrained_index(L, seed=19)
    mesh = jax.make_mesh((1,), ("data",))
    sp8 = SearchParams(m=M_PROBE, tau=1, k=K_TOP, mode="compact",
                       store_dtype="int8", refine_k=32)
    search = make_production_search(mesh, sp8)
    st = encode(base, "int8", BLOCK)
    sharded_store = jax.tree.map(lambda x: x[None], st)  # [P=1, ...] leaves
    res = search(idx.params, idx.index.members[None],
                 sharded_store, queries)
    want = idx.search(queries, st, sp8)
    np.testing.assert_array_equal(np.asarray(want.ids),
                                  np.asarray(res.ids))


# ------------------------------------------------------- byte accounting ----
def test_deep1b_serve_store_accounting():
    from repro.configs.irli_deep1b import (D as D1B, N_CORPUS,
                                           N_SCALE_BLOCKS, serve_store_bytes)
    from repro.launch.dryrun import check_store_accounting
    acct = serve_store_bytes(512)
    l_loc = N_CORPUS // 512
    assert acct["fp32_per_shard"] == l_loc * D1B * 4
    assert acct["int8_per_shard"] == l_loc * (D1B + 4 * N_SCALE_BLOCKS)
    assert acct["fp32_per_shard"] / acct["int8_per_shard"] > 3
    # a compiled record whose args fit the int8 budget passes...
    rec = {"argument_size_in_bytes":
           512 * (acct["int8_per_shard"] + acct["members_per_shard"])}
    check_store_accounting(rec, 512)
    assert rec["store_accounting"]["fp32_over_int8"] > 3
    # ...one carrying fp32 vectors is rejected
    bad = {"argument_size_in_bytes": 512 * (acct["fp32_per_shard"]
                                            + acct["members_per_shard"])}
    with pytest.raises(AssertionError, match="fp32"):
        check_store_accounting(bad, 512)
