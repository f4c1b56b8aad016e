"""Per-kernel allclose vs the pure-jnp oracle: shape/dtype sweeps in
interpret mode (this container is CPU; kernels target TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.irli_topk.irli_topk import irli_topk
from repro.kernels.irli_topk.ref import irli_topk_ref
from repro.kernels.distance_topk.distance_topk import distance_topk
from repro.kernels.distance_topk.ref import distance_topk_ref
from repro.kernels.embedding_bag.embedding_bag import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.bce_logits.bce_logits import bce_logits
from repro.kernels.bce_logits.ref import bce_logits_ref


@pytest.mark.parametrize("Q,H,B,m,tq,tb", [
    (64, 64, 512, 5, 32, 128),
    (128, 128, 1024, 10, 128, 256),
    (32, 96, 640, 3, 32, 320),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_irli_topk_sweep(Q, H, B, m, tq, tb, dtype):
    k = jax.random.PRNGKey(Q + B)
    h = jax.random.normal(k, (Q, H), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (H, B), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(2), (B,), jnp.float32).astype(dtype)
    v1, i1 = irli_topk(h, w, b, m=m, tq=tq, tb=tb, interpret=True)
    v2, i2 = irli_topk_ref(h, w, b, m=m)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-3)
    # discrete boundary: indices may swap on near-ties; check top-set overlap
    overlap = np.mean([len(set(a) & set(b)) / m
                       for a, b in zip(np.asarray(i1), np.asarray(i2))])
    assert overlap > 0.95, overlap


@pytest.mark.parametrize("metric", ["dot", "l2"])
@pytest.mark.parametrize("Q,L,d,k", [(32, 512, 16, 8), (64, 1024, 32, 10)])
def test_distance_topk_sweep(metric, Q, L, d, k):
    kk = jax.random.PRNGKey(Q + L)
    q = jax.random.normal(kk, (Q, d), jnp.float32)
    base = jax.random.normal(jax.random.PRNGKey(3), (L, d), jnp.float32)
    mask = (jax.random.uniform(jax.random.PRNGKey(4), (Q, L)) > 0.4).astype(jnp.float32)
    v1, i1 = distance_topk(q, base, mask, k=k, tq=Q // 2, tl=L // 4,
                           metric=metric, interpret=True)
    v2, i2 = distance_topk_ref(q, base, mask, k=k, metric=metric)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("N,P,V,D", [(128, 4, 300, 32), (256, 8, 1000, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_sweep(N, P, V, D, dtype):
    k = jax.random.PRNGKey(N)
    ids = jax.random.randint(k, (N, P), -1, V).astype(jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(5), (N, P))
    tbl = jax.random.normal(jax.random.PRNGKey(6), (V, D), jnp.float32).astype(dtype)
    o1 = embedding_bag(ids, w, tbl, tb=N // 2, interpret=True)
    o2 = embedding_bag_ref(ids, w, tbl)
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-2)


@pytest.mark.parametrize("N,B,tn,tb", [(128, 512, 64, 256), (64, 1024, 32, 512)])
def test_bce_logits_sweep(N, B, tn, tb):
    k = jax.random.PRNGKey(N + B)
    lg = jax.random.normal(k, (N, B)) * 4
    tg = (jax.random.uniform(jax.random.PRNGKey(7), (N, B)) > 0.9).astype(jnp.float32)
    l1, g1 = bce_logits(lg, tg, tn=tn, tb=tb, interpret=True)
    l2, g2 = bce_logits_ref(lg, tg)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1e-6)


def test_bce_matches_autodiff():
    """The kernel's analytic grad == jax.grad of the reference loss."""
    k = jax.random.PRNGKey(0)
    lg = jax.random.normal(k, (32, 128))
    tg = (jax.random.uniform(jax.random.PRNGKey(1), (32, 128)) > 0.8).astype(jnp.float32)
    _, g_kernel = bce_logits(lg, tg, tn=32, tb=128, interpret=True)
    g_auto = jax.grad(lambda x: bce_logits_ref(x, tg)[0])(lg)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_auto),
                               rtol=1e-4, atol=1e-6)


# ------------------------------------------------------------ freq_topc ----
from repro.kernels.freq_topc.freq_topc import freq_topc
from repro.kernels.freq_topc.ref import freq_topc_ref


def _freq_case(case):
    """-> (cands [Q, C0] int32, C, tq, id_bound, max_count) for one case of
    the kernel's exact-equality test; the bounds hold for the rows."""
    rng = np.random.default_rng(FREQ_CASES.index(case))
    if case in ("dup", "distinct", "c_over_c0"):   # the original three
        Q, C0, V, C, tq = {"dup": (8, 96, 40, 16, 4),          # heavy dups
                           "distinct": (7, 120, 500, 64, 4),   # 7 % 4 != 0
                           "c_over_c0": (4, 100, 30, 160, 2)}[case]
        rng = np.random.default_rng(Q + C0)
        cands = rng.integers(-1, V, (Q, C0)).astype(np.int32)
        cands[0, : C0 // 2] = -1                     # heavily padded row
        cands[-1] = -1                               # zero-candidate row
        return cands, C, tq, V, C0
    if case == "ties_at_cut":
        # 300 distinct ids per row, counts 1-3 so the 100th place falls
        # inside a long run of equal counts; width 1024 > C' = 128, so the
        # halving rounds run three times
        Q, C, V = 8, 100, 5000
        rows = []
        for _ in range(Q):
            ids = rng.choice(V, 300, replace=False)
            reps = rng.integers(1, 4, 300)
            row = np.repeat(ids, reps)
            rows.append(np.pad(rng.permutation(row), (0, 1000 - len(row)),
                               constant_values=-1))
        return np.stack(rows).astype(np.int32), C, 8, V, 3
    if case == "bound_edges":
        # an id at max_count, ids 0 and id_bound - 1, with (max_count + 1)
        # * 2^bits(id_bound - 1) exactly 2^31: the largest key is INT32_MAX
        id_bound, max_count, C0 = 1 << 21, 1023, 1100
        cands = rng.integers(0, id_bound, (8, C0)).astype(np.int32)
        cands[0, :max_count] = 0
        cands[1, :max_count] = id_bound - 1
        cands[2, :7] = id_bound - 1
        cands[2, 7:14] = 0
        cands[3] = -1                                # all-pad row
        cands[4, 5:] = -1                            # fewer ids than C
        return cands, 256, 8, id_bound, max_count
    if case == "past_int32":
        # (1024 + 1) << 21 > 2^31: given bounds, the network still takes the
        # lane form; ids near the bound, 600 slots > C' = 128
        id_bound, max_count = 1 << 21, 1024
        cands = rng.integers(id_bound - 200, id_bound, (4, 600))
        return cands.astype(np.int32), 64, 4, id_bound, max_count
    raise ValueError(case)


FREQ_CASES = ["dup", "distinct", "c_over_c0", "ties_at_cut", "bound_edges",
              "past_int32"]


@pytest.mark.parametrize("bounds", ["given", "none"])
@pytest.mark.parametrize("case", FREQ_CASES)
def test_freq_topc_matches_ref_exactly(case, bounds):
    """Bit for bit the jnp oracle's ids and counts (count desc, id asc on
    ties, -1/0 pads), in either key form of the top-C network: the id form
    where the bounds are given and fit int32, the lane form otherwise."""
    from repro.kernels.freq_topc.freq_topc import key_form
    cands, C, tq, id_bound, max_count = _freq_case(case)
    kw = {"id_bound": id_bound, "max_count": max_count} \
        if bounds == "given" else {}
    form = "id" if kw and case != "past_int32" else "lane"
    assert key_form(kw.get("id_bound"), kw.get("max_count")) == form
    cj = jnp.asarray(cands)
    ids_k, cnt_k = freq_topc(cj, C=C, tq=tq, interpret=True, **kw)
    ids_r, cnt_r = freq_topc_ref(cj, C=C)
    np.testing.assert_array_equal(np.asarray(ids_k), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    empty = (cands == -1).all(axis=1)
    assert (np.asarray(ids_k)[empty] == -1).all()   # empty rows stay empty


def test_freq_topc_key_form_at_deep1b_widths():
    """A Deep1B shard (L = 2,040,320 rows, R = 32, m = 5, member width 204)
    takes the (count, id) form: 160 member lists a row and 21-bit ids; a
    2^26-row shard's 26-bit ids do not fit beside the counts."""
    from repro.core.query import QueryPipeline
    from repro.kernels.freq_topc.freq_topc import key_form
    R, m, ML, L = 32, 5, 204, 2_040_320
    assert key_form(L, R * m) == "id"
    assert key_form(1 << 26, R * m) == "lane"
    assert key_form(1 << 21, 1023) == "id"       # (1023 + 1) << 21 == 2^31
    assert key_form(1 << 21, 1024) == "lane"
    pipe = QueryPipeline(m=m, kernels=("freq_topc",))
    members = jax.ShapeDtypeStruct((R, 20000, ML), jnp.int32)
    rows = lambda n: jax.ShapeDtypeStruct((n, 96), jnp.int8)
    bounds = pipe.freq_bounds(members, rows(L))
    assert bounds == {"id_bound": L, "max_count": R * m}
    assert key_form(**bounds) == "id"
    assert key_form(**pipe.freq_bounds(members, rows(1 << 26))) == "lane"
    # delta segments double the lists a row gathers; still the id form
    delta = jax.ShapeDtypeStruct((R, 20000, 16), jnp.int32)
    bounds = pipe.freq_bounds(members, rows(L), delta)
    assert bounds["max_count"] == 2 * R * m and key_form(**bounds) == "id"


def test_freq_topc_compiles_counted_by_key_form():
    """PipelineCache counts each compile of a pipeline that runs the kernel
    into ``freq_topc_compiles_total{keys=...}`` of its registry: one per
    form here (traced only, at abstract shapes), none for a cache hit."""
    from repro import obs
    from repro.core.search_api import PipelineCache, SearchParams
    reg = obs.MetricRegistry()
    cache = PipelineCache(registry=reg)
    params = SearchParams(mode="compact", kernels=("freq_topc",), topC=256)
    R, B, ML, d, Qn = 4, 64, 32, 16, 8
    sds = jax.ShapeDtypeStruct
    scorer = {"w1": sds((R, d, 32), jnp.float32),
              "b1": sds((R, 32), jnp.float32),
              "w2": sds((R, 32, B), jnp.float32),
              "b2": sds((R, B), jnp.float32)}
    members = sds((R, B, ML), jnp.int32)
    queries = sds((Qn, d), jnp.float32)

    def compiles(form):
        return reg.counter("freq_topc_compiles_total", {"keys": form}).value

    for L in (4096, 1 << 29, 4096):
        base = sds((L, d), jnp.float32)
        jax.eval_shape(cache.get(params, L, Qn), scorer, members, base,
                       queries, None, None)
    assert (compiles("id"), compiles("lane")) == (1, 1)
    assert cache.compiles == 2


def test_freq_topc_ref_matches_core_sorted_path():
    """The kernel's oracle and core/query.sorted_frequency_topC are the SAME
    contract — the compact QueryPipeline may take either."""
    from repro.core.query import sorted_frequency_topC
    rng = np.random.default_rng(0)
    cands = jnp.asarray(rng.integers(-1, 60, (6, 160)).astype(np.int32))
    ids_r, cnt_r = freq_topc_ref(cands, C=32)
    ids_s, cnt_s = sorted_frequency_topC(cands, 32)
    np.testing.assert_array_equal(np.asarray(ids_r), np.asarray(ids_s))
    np.testing.assert_array_equal(np.asarray(cnt_r), np.asarray(cnt_s))


# ----------------------------------------------------------- quant_rerank ---
from repro.kernels.quant_rerank.quant_rerank import quant_rerank
from repro.kernels.quant_rerank.ops import _coarse_chunked
from repro.kernels.quant_rerank.ref import quant_rerank_ref


@pytest.mark.parametrize("metric", ["angular", "l2"])
@pytest.mark.parametrize("Q,L,D,C,k,blk,tq", [
    (8, 200, 32, 24, 8, 16, 4),
    (7, 500, 48, 40, 12, 16, 4),     # row padding (7 % 4 != 0)
    (4, 100, 16, 12, 20, 8, 2),      # k > C: clamped to C
])
def test_quant_rerank_matches_ref(metric, Q, L, D, C, k, blk, tq):
    """Fused gather+dequant+score+top-k' kernel vs the jnp oracle: ids are
    EXACTLY equal (shared tie-break: smaller candidate position first, -1
    where no candidate survived), coarse scores to fp tolerance."""
    from repro.store import encode
    rng = np.random.default_rng(Q + L)
    st = encode(rng.normal(size=(L, D)).astype(np.float32), "int8", blk)
    q = jnp.asarray(rng.normal(size=(Q, D)), jnp.float32)
    cid = jnp.asarray(rng.integers(-1, L, (Q, C)), jnp.int32)
    cnt = jnp.asarray(rng.integers(0, 4, (Q, C)), jnp.float32)
    cid = cid.at[-1].set(-1)                     # zero-candidate row
    i_k, v_k = quant_rerank(q, st.codes, st.scales, cid, cnt, tau=1, k=k,
                            metric=metric, tq=tq, interpret=True)
    i_r, v_r = quant_rerank_ref(q, st.codes, st.scales, cid, cnt, tau=1,
                                k=k, metric=metric)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(i_k)[-1] == -1).all()     # empty row stays empty


@pytest.mark.parametrize("metric", ["angular", "l2"])
def test_quant_rerank_bf16_matches_ref(metric):
    """bf16 codes through the Pallas kernel (bf16 ANY-space loads, unit
    scales with one block spanning D) vs the scale-less oracle path."""
    rng = np.random.default_rng(11)
    L, D, Q, C = 150, 32, 6, 20
    codes = jnp.asarray(rng.normal(size=(L, D)), jnp.float32) \
        .astype(jnp.bfloat16)
    ones = jnp.ones((L, 1), jnp.float32)     # what ops fabricates on TPU
    q = jnp.asarray(rng.normal(size=(Q, D)), jnp.float32)
    cid = jnp.asarray(rng.integers(-1, L, (Q, C)), jnp.int32)
    cnt = jnp.asarray(rng.integers(0, 3, (Q, C)), jnp.float32)
    i_k, v_k = quant_rerank(q, codes, ones, cid, cnt, tau=1, k=8,
                            metric=metric, tq=2, interpret=True)
    i_r, v_r = quant_rerank_ref(q, codes, None, cid, cnt, tau=1, k=8,
                                metric=metric)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r),
                               rtol=1e-5, atol=1e-5)
    i_c, v_c = _coarse_chunked(q, codes, None, cid, cnt, tau=1, k=8,
                               metric=metric, chunk=8)
    np.testing.assert_array_equal(np.asarray(i_c), np.asarray(i_r))


@pytest.mark.parametrize("metric", ["angular", "l2"])
def test_quant_coarse_chunked_matches_ref(metric):
    """The memory-bounded jnp fallback (candidate chunking) returns the
    oracle's exact ids — chunking changes memory, never results."""
    from repro.store import encode
    rng = np.random.default_rng(5)
    st = encode(rng.normal(size=(300, 32)).astype(np.float32), "int8", 16)
    q = jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)
    cid = jnp.asarray(rng.integers(-1, 300, (6, 50)), jnp.int32)
    cnt = jnp.asarray(rng.integers(0, 3, (6, 50)), jnp.float32)
    i_r, v_r = quant_rerank_ref(q, st.codes, st.scales, cid, cnt, tau=1,
                                k=16, metric=metric)
    for chunk in (7, 16, 50, 128):               # incl. non-divisors, > C
        i_c, v_c = _coarse_chunked(q, st.codes, st.scales, cid, cnt, tau=1,
                                   k=16, metric=metric, chunk=chunk)
        np.testing.assert_array_equal(np.asarray(i_c), np.asarray(i_r))
        np.testing.assert_allclose(np.asarray(v_c), np.asarray(v_r),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- flash attention ----
from repro.kernels.flash_attn.flash_attn import flash_attention
from repro.kernels.flash_attn.ref import flash_attention_ref


@pytest.mark.parametrize("B,H,S,D,tq,tk", [
    (2, 3, 128, 32, 32, 32),
    (1, 4, 256, 64, 64, 128),
    (2, 2, 64, 16, 64, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, S, D, tq, tk, dtype):
    k0 = jax.random.PRNGKey(B * S)
    q = jax.random.normal(k0, (B, H, S, D), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D),
                          jnp.float32).astype(dtype)
    o1 = flash_attention(q, k, v, tq=tq, tk=tk, interpret=True)
    o2 = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 2e-4, atol=3e-2)
