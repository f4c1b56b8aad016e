"""Property-based tests (hypothesis) for the partition/repartition invariants
— the system's core data structure guarantees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep — skip, don't error
from hypothesis import given, settings, strategies as st

from repro.core import partition as PT
from repro.core.repartition import kchoice_exact, kchoice_parallel


@settings(max_examples=20, deadline=None)
@given(L=st.integers(10, 300), B=st.integers(2, 32), R=st.integers(1, 4),
       seed=st.integers(0, 10_000))
def test_hash_init_is_valid_partition(L, B, R, seed):
    a = np.asarray(PT.hash_init(L, B, R, seed))
    assert a.shape == (R, L)
    assert a.min() >= 0 and a.max() < B


@settings(max_examples=15, deadline=None)
@given(L=st.integers(10, 200), B=st.integers(2, 16), seed=st.integers(0, 99))
def test_inverted_index_roundtrip(L, B, seed):
    """Every label appears in exactly its assigned bucket's member list."""
    assign = PT.hash_init(L, B, 2, seed)
    idx = PT.build_inverted_index(assign, B)
    members = np.asarray(idx.members)
    a = np.asarray(assign)
    for r in range(2):
        seen = {}
        for b in range(B):
            for l in members[r, b]:
                if l >= 0:
                    assert l not in seen, "duplicate member"
                    seen[int(l)] = b
        for l in range(L):
            assert seen.get(l) == a[r, l], (l, seen.get(l), a[r, l])


@settings(max_examples=15, deadline=None)
@given(L=st.integers(10, 200), B=st.integers(2, 16),
       width=st.integers(1, 8), seed=st.integers(0, 99))
def test_inverted_index_overflow_counted(L, B, width, seed):
    """A member width below the observed load keeps each bucket's first
    ``width`` labels (by id) — never overwrites a kept one — and
    ``dropped`` counts exactly the labels left out."""
    assign = PT.hash_init(L, B, 2, seed)
    idx = PT.build_inverted_index(assign, B, width)
    members, a = np.asarray(idx.members), np.asarray(assign)
    for r in range(2):
        for b in range(B):
            want = np.flatnonzero(a[r] == b)[:width]
            got = members[r, b]
            np.testing.assert_array_equal(got[:len(want)], want)
            assert (got[len(want):] == -1).all()
    over = np.maximum(np.asarray(idx.load) - width, 0).sum()
    assert idx.dropped == over == 2 * L - (members >= 0).sum()


@settings(max_examples=15, deadline=None)
@given(L=st.integers(20, 400), B=st.integers(4, 32), K=st.integers(2, 8),
       seed=st.integers(0, 99))
def test_kchoice_exact_load_bound(L, B, K, seed):
    """Power-of-K max load <= greedy bound: inserting into the least loaded
    of K RANDOM choices can never exceed ceil(L/B) + ... we assert the weaker
    invariant that max load <= max(ceil(L/B), load of pure-greedy K=B) * 3
    and that EVERY label lands in one of its top-K buckets."""
    rng = np.random.default_rng(seed)
    aff = rng.random((L, B)).astype(np.float32)
    topk = jnp.asarray(np.argsort(-aff, 1)[:, :K].copy())
    assign = np.asarray(kchoice_exact(topk, B, jax.random.PRNGKey(seed)))
    # membership in own top-K
    tk = np.asarray(topk)
    for l in range(L):
        assert assign[l] in tk[l]
    load = np.bincount(assign, minlength=B)
    assert load.max() <= int(np.ceil(L / B)) * 3 + K


@settings(max_examples=15, deadline=None)
@given(L=st.integers(20, 300), B=st.integers(4, 32), K=st.integers(2, 8),
       slack=st.floats(1.05, 2.0), seed=st.integers(0, 99))
def test_kchoice_parallel_capacity(L, B, K, slack, seed):
    """Parallel variant: load never exceeds cap except via the final
    stragglers fallback; assignment always valid bucket ids."""
    rng = np.random.default_rng(seed)
    aff = rng.random((L, B)).astype(np.float32)
    order = np.argsort(-aff, 1)[:, :K]
    vals = np.take_along_axis(aff, order, 1)
    assign = np.asarray(kchoice_parallel(jnp.asarray(vals.copy()),
                                         jnp.asarray(order.copy()), B, slack))
    assert assign.min() >= 0 and assign.max() < B
    cap = int(np.ceil(slack * L / B))
    load = np.bincount(assign, minlength=B)
    # stragglers may exceed cap, but only by the number of overflow labels
    assert (np.minimum(load, cap).sum() >= L - K * cap)


@settings(max_examples=10, deadline=None)
@given(N=st.integers(2, 50), k=st.integers(1, 5), B=st.integers(2, 16),
       R=st.integers(1, 3), seed=st.integers(0, 99))
def test_bucket_targets_match_bruteforce(N, k, B, R, seed):
    rng = np.random.default_rng(seed)
    L = 64
    assign = PT.hash_init(L, B, R, seed)
    ids = rng.integers(0, L, (N, k)).astype(np.int32)
    mask = (rng.random((N, k)) > 0.3).astype(np.float32)
    t = np.asarray(PT.bucket_targets(assign, jnp.asarray(ids),
                                     jnp.asarray(mask), B))
    a = np.asarray(assign)
    for r in range(R):
        for n in range(N):
            expect = np.zeros(B)
            for j in range(k):
                if mask[n, j] > 0:
                    expect[a[r, ids[n, j]]] = 1
            np.testing.assert_array_equal(t[r, n], expect)


@settings(max_examples=10, deadline=None)
@given(Q=st.integers(1, 8), C0=st.integers(4, 64), L=st.integers(8, 64),
       C=st.integers(2, 16), seed=st.integers(0, 99))
def test_sorted_frequency_matches_dense(Q, C0, L, C, seed):
    """sorted_frequency_topC counts == dense bincount for the ids it keeps."""
    from repro.core.query import sorted_frequency_topC
    rng = np.random.default_rng(seed)
    cands = rng.integers(-1, L, (Q, C0)).astype(np.int32)
    ids, counts = sorted_frequency_topC(jnp.asarray(cands), C)
    ids, counts = np.asarray(ids), np.asarray(counts)
    for q in range(Q):
        dense = np.bincount(cands[q][cands[q] >= 0], minlength=L)
        for i, c in zip(ids[q], counts[q]):
            if i >= 0:
                assert dense[i] == c, (q, i, c, dense[i])
        # top-C by count: kept counts >= best dropped count
        kept = set(int(i) for i in ids[q] if i >= 0)
        if kept:
            dropped = [dense[j] for j in range(L) if dense[j] > 0 and j not in kept]
            if dropped:
                assert min(counts[q][ids[q] >= 0]) >= max(dropped) - 1e-6
