"""IRLI query path (Alg. 2): score -> top-m buckets per rep -> gather
inverted-index members -> per-candidate frequency across the m·R probed
buckets -> threshold filter -> (optional) true-distance re-rank.

Two frequency/rerank backends, unified behind :class:`QueryPipeline`:

dense  — frequency via one-hot segment_sum into a [Q, L] count table and a
         full [Q, L] similarity matrix for the rerank. TPU-friendly (no
         sort) but memory O(Q·L): only viable while the per-shard corpus is
         small (~1e6).
compact— per-query sort of the gathered candidate ids + run-length count +
         top-C frequent (``frequency_topC``), then a gathered rerank over
         just those C rows. O(C) per query, NO [Q, L] table ever exists.
         This is the 100M-scale path; every serving surface (core/index,
         core/distributed, serve/server, stream/mutable_index) routes
         through it via QueryPipeline.

``QueryPipeline.make(L, mode="auto")`` picks the backend from the corpus
size and a dense-table memory budget. Both backends return identical top-k
ids at matched candidate budgets (tests/test_query_pipeline.py).

The rerank's vector payload is pluggable: ``base`` may be a raw fp32
[L, d] array or a ``repro.store.QuantizedStore`` (int8/bf16 block-scaled
codes + optional exact fp32 tier, docs/store.md) — with a quantized store
the compact rerank runs coarse-on-codes + exact refine of the top
``refine_k`` survivors and never materializes an fp32 [L, d] or
[Q, topC, d] array.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.network import scorer_logits, scorer_probs
from repro.core.partition import InvertedIndex


def top_buckets(params, queries, m: int, loss_kind: str = "softmax_bce"):
    """queries [Q, d] -> (scores [R, Q, m], idx [R, Q, m])."""
    probs = scorer_probs(params, queries, loss_kind)
    return jax.lax.top_k(probs, m)


def _flip(bits):
    """Float bits (as signed ints) <-> integers ordered as ``lax.top_k``
    orders the floats: -NaN < -inf < ... < -0.0 < +0.0 < ... < +inf < NaN.
    The map is its own inverse."""
    return bits ^ ((bits >> (bits.dtype.itemsize * 8 - 1))
                   & jnp.iinfo(bits.dtype).max)


def _best(a, b):
    """The better of two (key, id) pairs: the larger key, then the smaller
    id."""
    (ka, ia), (kb, ib) = a, b
    take_a = (ka > kb) | ((ka == kb) & (ia < ib))
    return jnp.where(take_a, ka, kb), jnp.where(take_a, ia, ib)


def top_m(logits: jnp.ndarray, m: int):
    """``jax.lax.top_k(logits, m)`` over the last (bucket) axis, exactly —
    the same values, int32 indices and order (descending, ties to the
    smaller index) — without the sort of every (value, index) pair that the
    TPU lowers ``top_k`` to.

    m rounds of max-and-mask: each round is one reduction to the best
    (value, index) pair among those ranked below the previous round's pick,
    the rest masked out of its input, so the logits are read m times and
    never sorted. Values compare by the total order ``top_k`` uses on the
    float bits (-0.0 below +0.0, NaN above +inf) and come back as the
    original floats. A row of fewer than m buckets goes to ``top_k``, which
    refuses it."""
    if logits.shape[-1] < m:
        return jax.lax.top_k(logits, m)
    with jax.named_scope("max_and_mask"):
        keys = _flip(jax.lax.bitcast_convert_type(
            logits, jnp.dtype(f"int{logits.dtype.itemsize * 8}")))
        ids = jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1)
        low = jnp.array(jnp.iinfo(keys.dtype).min, keys.dtype)
        none = jnp.array(jnp.iinfo(jnp.int32).max, jnp.int32)
        k, i = keys, ids
        out_k, out_i = [], []
        for _ in range(m):
            if out_i:
                kp, ip = out_k[-1][..., None], out_i[-1][..., None]
                below = (keys < kp) | ((keys == kp) & (ids > ip))
                k, i = jnp.where(below, keys, low), jnp.where(below, ids, none)
            kr, ir = jax.lax.reduce((k, i), (low, none), _best,
                                    (keys.ndim - 1,))
            out_k.append(kr)
            out_i.append(ir)
        vals = jax.lax.bitcast_convert_type(_flip(jnp.stack(out_k, -1)),
                                            logits.dtype)
        return vals, jnp.stack(out_i, -1)


def gather_members(members: jnp.ndarray, bucket_idx: jnp.ndarray,
                   delta_members: jnp.ndarray | None = None,
                   probe_keep: jnp.ndarray | None = None):
    """Gather probed-bucket member lists from raw member matrices.

    members [R, B, ML], bucket_idx [R, Q, m], optional delta_members
    [R, B, DL] (the streaming delta segments — appended per probed bucket so
    freshly-inserted items are found immediately), optional probe_keep
    [R, Q, m] bool (the adaptive-m(q) policy: candidates from a masked-out
    probe become -1 pads, so shapes stay static while easy queries
    contribute fewer candidates).
    Returns candidate ids [Q, R·m·(ML[+DL])] (pad -1).
    """
    R, Q, m = bucket_idx.shape

    def per_rep(members_r, idx_r):          # [B, ML], [Q, m]
        return members_r[idx_r]             # [Q, m, ML]

    cands = jax.vmap(per_rep)(members, bucket_idx)         # [R, Q, m, ML]
    if delta_members is not None:
        dcands = jax.vmap(per_rep)(delta_members, bucket_idx)  # [R, Q, m, DL]
        cands = jnp.concatenate([cands, dcands], axis=-1)
    if probe_keep is not None:
        cands = jnp.where(probe_keep[..., None], cands, -1)
    return jnp.moveaxis(cands, 0, 1).reshape(Q, -1)


def probe_keep_mask(logits: jnp.ndarray, top_vals: jnp.ndarray,
                    probe_mass: float) -> jnp.ndarray:
    """The per-query probe-count policy m(q) (LIRA, PAPERS.md): keep probe
    j of a rep iff the softmax mass of the probes BEFORE it is still short
    of ``probe_mass`` — confident queries stop after 1–2 buckets, ambiguous
    ones keep all m. The scorer trunk's own softmax is the predictor (no
    separate head to train, and it can never disagree with the router that
    picked the buckets). logits [R, Q, B], top_vals [R, Q, m] (top-m
    logits, descending) -> bool [R, Q, m]; probe 0 is always kept.
    """
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)     # [R, Q, 1]
    p = jnp.exp(top_vals - lse)                                # [R, Q, m]
    mass_before = jnp.cumsum(p, axis=-1) - p
    return mass_before < probe_mass


@partial(jax.jit, static_argnames=("m",))
def predicted_probe_counts(params, queries, m: int, probe_mass: float):
    """Effective probes per (rep, query) under the adaptive-m policy:
    [R, Q] int32 in [1, m]. Telemetry companion of the serving path (the
    refit loop and obs smoke record its distribution) — shares
    probe_keep_mask with the pipeline, so the histogram is exactly what
    serving does."""
    logits = scorer_logits(params, queries)
    vals, _ = jax.lax.top_k(logits, m)
    keep = probe_keep_mask(logits, vals, probe_mass)
    return jnp.sum(keep.astype(jnp.int32), axis=-1)


def gather_candidates(index: InvertedIndex, bucket_idx: jnp.ndarray,
                      delta_members: jnp.ndarray | None = None):
    """bucket_idx [R, Q, m] -> candidate ids [Q, R·m·max_load] (pad -1)."""
    return gather_members(index.members, bucket_idx, delta_members)


def mask_tombstones(cands: jnp.ndarray, tombstone: jnp.ndarray) -> jnp.ndarray:
    """Replace tombstoned candidate ids with -1 (pad) BEFORE frequency
    counting, so deleted items can never survive the frequency filter.
    cands [Q, C] (pad -1), tombstone [L_cap] bool."""
    dead = tombstone[jnp.maximum(cands, 0)] & (cands >= 0)
    return jnp.where(dead, -1, cands)


def candidate_frequencies_dense(cands: jnp.ndarray, L: int) -> jnp.ndarray:
    """[Q, C] padded candidate ids -> [Q, L] occurrence counts."""
    valid = cands >= 0
    safe = jnp.where(valid, cands, 0)

    def one(c, v):
        return jax.ops.segment_sum(v.astype(jnp.float32), c, num_segments=L)

    return jax.vmap(one)(safe, valid)


def frequency_filter(freq: jnp.ndarray, tau: int):
    """Keep candidates with count >= tau. Returns boolean mask [Q, L]."""
    return freq >= tau


def auto_tau(freq: jnp.ndarray, budget: int) -> jnp.ndarray:
    """Beyond-paper: choose per-query tau so ~budget candidates survive.
    freq [Q, L] -> tau [Q] (smallest tau with |{freq>=tau}| <= budget)."""
    if budget <= 0:
        # without the guard, budget=0 indexes column -1 via wraparound and
        # silently returns the MINIMUM frequency (i.e. keeps everything)
        raise ValueError(f"auto_tau: budget must be >= 1, got {budget}")
    Q, L = freq.shape
    kth = -jnp.sort(-freq, axis=1)[:, min(budget, L) - 1]
    return jnp.maximum(kth, 1.0)


def sorted_frequency_topC(cands: jnp.ndarray, C: int):
    """Scalable FrequentOnes: per-query sort + run-length count, keep the C
    most frequent candidates. cands [Q, C0] padded with -1.

    Returns (ids [Q, C], counts [Q, C]) — ids are -1 where fewer than C
    distinct candidates exist. O(C0 log C0) per query, no [Q, L] table: this
    is the 100M-scale path (dense counting is fine up to L ~ 1e6 per shard).
    """
    C_eff = min(C, cands.shape[1])   # can't keep more than C0 candidates

    def one(c):
        s = jnp.sort(c)                                        # [-1 pads first]
        is_start = jnp.concatenate([jnp.ones(1, bool), s[1:] != s[:-1]])
        run_id = jnp.cumsum(is_start) - 1                       # [C0]
        counts = jax.ops.segment_sum(jnp.ones_like(s, jnp.float32), run_id,
                                     num_segments=s.shape[0])
        cnt_pos = counts[run_id]
        score = jnp.where(is_start & (s >= 0), cnt_pos, -1.0)   # runs only
        top_cnt, top_pos = jax.lax.top_k(score, C_eff)
        ids = jnp.where(top_cnt > 0, s[top_pos], -1)
        if C_eff < C:                                           # pad to C
            ids = jnp.concatenate([ids, jnp.full(C - C_eff, -1, ids.dtype)])
            top_cnt = jnp.concatenate([top_cnt, jnp.zeros(C - C_eff)])
        return ids.astype(jnp.int32), jnp.maximum(top_cnt, 0.0)

    return jax.vmap(one)(cands)


def frequency_topC(cands: jnp.ndarray, C: int, kernel: bool = False, *,
                   id_bound: int | None = None,
                   max_count: int | None = None):
    """FrequentOnes over gathered candidates -> compact (ids, counts) [Q, C].

    Dispatches through kernels/freq_topc/ops (the ONE dispatch site): the
    fused Pallas kernel — per-query bitonic sort + run-length count, then a
    truncated bitonic top-C network, VMEM-resident — when ``kernel`` is set
    (the resolved choice, see :func:`select_kernels`), the jnp sorted path
    otherwise. The network sorts ``(count, id)`` keys where the static
    bounds ``id_bound`` (every id is below it) and ``max_count`` (no id
    occurs more often in a row) make them fit int32, else ``(count,
    lane)`` keys carrying the id (``freq_topc.key_form``); the jnp path
    ignores the bounds. All produce identical output (count desc, id asc on
    ties; -1/0 padding past the distinct-candidate count)."""
    from repro.kernels.freq_topc.ops import frequent_topc
    return frequent_topc(cands, C=C, kernel=kernel, id_bound=id_bound,
                         max_count=max_count)


def pairwise_sim(queries, base, metric: str = "angular"):
    """Similarity of every query against every base row: [Q, d]×[L, d] ->
    [Q, L] fp32 (dot product for angular, negative squared L2 otherwise).
    The ONE implementation of the metric: every rerank path — full-matrix
    (dense), gathered (compact, via :func:`gathered_sim`), the store's
    exact refine stage, and the distance_topk kernel oracle — routes here
    so numerics can't diverge."""
    hi = jax.lax.Precision.HIGHEST     # the chip's default is one bf16 pass
    if metric == "angular":
        return jnp.einsum("qd,ld->ql", queries, base, precision=hi,
                          preferred_element_type=jnp.float32)
    return -(jnp.sum(queries ** 2, 1, keepdims=True)
             - 2 * jnp.matmul(queries, base.T, precision=hi)
             + jnp.sum(base ** 2, 1)[None, :])


def gathered_sim(queries, vecs, metric: str = "angular"):
    """The metric for PER-QUERY gathered rows: queries [Q, d], vecs
    [Q, C, d] -> [Q, C] fp32 — the single implementation behind every
    gathered rerank (compact path, store refine), defined HERE next to
    pairwise_sim so the two can't drift.

    angular is a vmap of pairwise_sim. l2 uses the direct difference form
    -Σ(q-v)²: pairwise_sim's expansion (|q|² - 2q·v + |v|²) is forced by
    its full-matrix shape but cancels catastrophically at large norms
    (fp32 ulp of |q|² can exceed the distance gap between near-duplicate
    rows) — the gathered stage is the EXACT final rerank and must resolve
    those ties correctly."""
    if metric == "l2":
        return -jnp.sum((queries[:, None, :] - vecs.astype(jnp.float32)) ** 2,
                        axis=-1)
    return jax.vmap(lambda q, v: pairwise_sim(q[None], v, metric)[0])(
        queries, vecs)


def rerank_gathered(queries, base, cand_ids, cand_counts, tau: int, k: int,
                    metric: str = "angular"):
    """Re-rank a COMPACT candidate list: gather base rows by id and score.

    queries [Q,d], base [L,d], cand_ids [Q,C] (-1 pad), cand_counts [Q,C].
    Returns (ids [Q,k], scores [Q,k]). Never materializes [Q, L].
    """
    valid = (cand_ids >= 0) & (cand_counts >= tau)
    safe = jnp.maximum(cand_ids, 0)
    vecs = base[safe]                                           # [Q, C, d]
    sim = jnp.where(valid, gathered_sim(queries, vecs, metric), -jnp.inf)
    scores, pos = jax.lax.top_k(sim, k)
    ids = jnp.take_along_axis(cand_ids, pos, axis=1)
    # a -inf slot means NO candidate survived there — whether the slot was
    # empty (id -1) or a whole row fell below tau — emit -1, never an
    # arbitrary (possibly tombstoned) id
    return jnp.where(jnp.isfinite(scores), ids, -1), scores


def rerank(queries, base, cand_mask, k: int, metric: str = "angular"):
    """True-distance re-rank of surviving candidates.

    queries [Q, d], base [L, d], cand_mask [Q, L] -> top-k ids [Q, k], with
    -1 where fewer than k candidates survived (same contract as
    distributed.local_search — callers must treat -1 as padding). Masked
    entries get -inf score. (The Pallas distance_topk kernel is the fused
    TPU analogue; this is the jnp path.)
    """
    sim = jnp.where(cand_mask, pairwise_sim(queries, base, metric), -jnp.inf)
    scores, idx = jax.lax.top_k(sim, k)
    return jnp.where(jnp.isfinite(scores), idx, -1)


@partial(jax.jit, static_argnames=("k", "metric"))
def exact_topk(queries, base, tombstone, *, k: int, metric: str = "angular"):
    """Full-probe exact top-k over the fp32 tier — the shadow-audit oracle.

    queries [Q, d], base [L, d], tombstone [L] bool -> ids [Q, k] (-1 where
    fewer than k live rows exist). Deliberately builds the whole [Q, L]
    similarity table rerank's masking avoids — this is the ground truth the
    ShadowAuditor (repro.obs.quality) scores served ids against, and it
    must only ever run off the hot path, on the sampled audit window
    (contract ``query.audit_oracle_off_hot_path``).
    """
    return rerank(queries, base, ~tombstone[None, :], k, metric)


def check_kernels(kernels, mode: str) -> None:
    """Validate a ``kernels`` tuple against the pipeline mode."""
    if not isinstance(kernels, tuple) or any(k not in KERNELS
                                             for k in kernels):
        raise ValueError(f"kernels must be a tuple drawn from {KERNELS}, "
                         f"got {kernels!r}")
    if kernels and mode == "dense":
        raise ValueError("mode='dense' runs no Pallas kernel")


@partial(jax.jit, static_argnames=("k", "metric", "group"))
def _exact_block(queries, base, *, k: int, metric: str, group: int):
    sim = pairwise_sim(queries, base, metric)                   # [q, L]
    Qb, L = sim.shape
    pad = (-L) % group
    sim = jnp.pad(sim, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    gmax = jnp.max(sim.reshape(Qb, -1, group), axis=-1)         # [q, L/g]
    _, gid = jax.lax.top_k(gmax, min(k, gmax.shape[1]))         # [q, k]
    cand = (gid[:, :, None] * group
            + jnp.arange(group, dtype=gid.dtype)).reshape(Qb, -1)
    vals, pos = jax.lax.top_k(jnp.take_along_axis(sim, cand, axis=1), k)
    ids = jnp.take_along_axis(cand, pos, axis=1)
    return jnp.where(jnp.isfinite(vals), ids, -1)


def exact_neighbors(queries, base, k: int, *, metric: str = "angular",
                    q_block: int = 256, group: int = 128):
    """Exact top-k ids [Q, k] of every query over the whole corpus — the
    ground truth for recall and the training labels of an ANN fit, computed
    on the device in blocks of ``q_block`` queries.

    Each block scores all L rows (:func:`pairwise_sim`, the metric every
    rerank shares), keeps the k groups of ``group`` consecutive rows with
    the largest maxima, and takes the top-k of those k·group scores. That
    is exact: each of the k best rows lies in a group whose maximum is at
    least the k-th best score, and at most k groups have such a maximum. It
    avoids a top-k over the full row width, which the chip sorts slowly.
    ``Q`` must be a multiple of ``q_block`` (or smaller than it)."""
    Q = queries.shape[0]
    q_block = min(q_block, Q)
    if Q % q_block:
        raise ValueError(f"{Q} queries are not a multiple of the "
                         f"{q_block}-query block")
    out = [_exact_block(queries[s:s + q_block], base, k=k, metric=metric,
                        group=group) for s in range(0, Q, q_block)]
    return jnp.concatenate(out, axis=0)


# ------------------------------------------------------------ pipeline ------
DENSE_TABLE_BUDGET_BYTES = 64 << 20   # default cap on the [Q, L] fp32 tables


def select_mode(L: int, q_batch: int = 512,
                budget_bytes: int = DENSE_TABLE_BUDGET_BYTES,
                store_dtype: str = "fp32") -> str:
    """Pick the frequency/rerank backend from the per-shard corpus size.

    dense materializes two [q_batch, L] fp32 tables (counts + similarities);
    compact's intermediates are O(q_batch · C0). Returns "dense" while the
    tables fit the budget, else "compact". "mega" is never picked: its
    Pallas kernel has not run on a chip, and its fused-jit form only
    repackages compact (a request may still name it).

    The dense accounting is CODE bytes, not fp32 bytes: a quantized store
    (``store_dtype`` != "fp32") holds int8/bf16 codes, and dense's
    full-matrix rerank would have to decode the whole [L, D] corpus back
    to fp32 — exactly the array the store exists to never materialize —
    so auto never resolves dense for quantized stores."""
    dense_fits = (store_dtype == "fp32"
                  and 2 * q_batch * L * 4 <= budget_bytes)
    return "dense" if dense_fits else "compact"


#: The Pallas kernels a pipeline can route its stages through.
KERNELS = ("freq_topc", "quant_rerank")


def select_kernels(mode: str, store_dtype: str, width: int | None,
                   backend: str) -> tuple:
    """The Pallas kernels a resolved pipeline runs — decided ONCE, where the
    request's params resolve, and never revisited in dispatch.

    Kernels run only on a "tpu" ``backend`` and only in the compact stage
    sequence (mode "compact", or "mega"'s fused jit of it):

      * "freq_topc" while the padded candidate width (``width`` = R·m·(ML
        [+ DL]) slots per query) fits the kernel's packed sort keys
        (``MAX_WIDTH``); an unknown or wider width resolves to the jnp
        sort, and the resolved ``kernels`` tuple says so;
      * "quant_rerank" for int8/bf16 stores (the coarse stage).
    """
    if backend != "tpu" or mode == "dense":
        return ()
    from repro.kernels.freq_topc.freq_topc import MAX_WIDTH, pow2_width
    out = []
    if width is not None and pow2_width(width) <= MAX_WIDTH:
        out.append("freq_topc")
    if store_dtype != "fp32":
        out.append("quant_rerank")
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class QueryPipeline:
    """One query-serving configuration: probe width, frequency threshold,
    rerank depth, and the frequency/rerank backend (``mode``).

    Frozen + hashable so it can be a jit static argument; every serving
    surface (IRLIIndex.search, distributed.local_search, IRLIServer,
    MutableIRLIIndex.search) builds one of these and calls :meth:`search`.

    mode="compact" guarantees NO [Q, L] intermediate exists anywhere in the
    traced computation (asserted by tests/test_query_pipeline.py over the
    jaxpr) — candidates stay [Q, topC] from frequency counting to the final
    top-k. n_candidates is therefore capped at ``topC`` in compact mode,
    while dense counts every survivor.

    mode="mega" is the compact path as ONE fused dispatch
    (kernels/mega_query, docs/query_paths.md): a single jit of the verbatim
    compact op sequence — so results are bit-identical to mode="compact" on
    every surface, including streaming delta/tombstone state and
    ``adaptive_m`` (contract ``query.mega_single_dispatch``; parity pinned
    by tests/test_mega_query.py).

    ``kernels`` lists the Pallas kernels the stages run (see
    :func:`select_kernels`, which fills it when params resolve); the
    default () runs every stage as jnp.

    ``store_dtype`` selects the vector-payload tier (docs/store.md): "fp32"
    reranks gathered raw rows (bit-identical whether ``base`` is an array
    or a fp32 QuantizedStore); "int8"/"bf16" run the tiered two-stage
    rerank — coarse on gathered CODE rows, exact fp32 refine of the top
    ``refine_k`` survivors (0 = auto: max(4k, 32)) — and additionally
    guarantee no fp32 [L, D] or [Q, topC, D] intermediate exists
    (tests/test_store.py walks the jaxpr).
    """
    m: int = 5
    tau: int = 1
    k: int = 10
    mode: str = "compact"          # "dense" | "compact" | "mega"
    topC: int = 1024               # compact candidate budget per query
    metric: str = "angular"
    store_dtype: str = "fp32"      # "fp32" | "int8" | "bf16" (docs/store.md)
    refine_k: int = 0              # exact-refine depth k' (0 = auto)
    adaptive_m: bool = False       # per-query m(q): see probe_keep_mask
    probe_mass: float = 1.0        # mass target; 1.0 == keep every probe
    #                                (probe_mass=1.0 takes the EXACT
    #                                non-adaptive trace, so toggling
    #                                adaptive_m alone changes nothing)
    kernels: tuple = ()            # Pallas kernels the stages run (KERNELS)
    # no loss_kind: bucket selection works on raw logits, which give the
    # same top-m as softmax OR sigmoid probabilities (both monotone) — the
    # training loss is irrelevant at serve time

    def __post_init__(self):
        if self.mode not in ("dense", "compact", "mega"):
            raise ValueError(f"unknown pipeline mode {self.mode!r} "
                             "(use 'dense', 'compact', 'mega', or "
                             "make(mode='auto'))")
        if self.store_dtype not in ("fp32", "int8", "bf16"):
            raise ValueError(f"unknown store_dtype {self.store_dtype!r} "
                             "(use 'fp32', 'int8', or 'bf16')")
        if not 0.0 < self.probe_mass <= 1.0:
            raise ValueError(f"probe_mass must be in (0, 1], got "
                             f"{self.probe_mass!r}")
        if self.mode == "dense" and self.store_dtype != "fp32":
            raise ValueError(
                "mode='dense' requires store_dtype='fp32' — the dense "
                "rerank would decode the whole [L, D] store back to fp32")
        check_kernels(self.kernels, self.mode)

    @classmethod
    def make(cls, L: int, *, mode: str = "auto", q_batch: int = 512,
             budget_bytes: int = DENSE_TABLE_BUDGET_BYTES, **kw):
        """Build a pipeline, resolving mode="auto" from L and the memory
        budget (see :func:`select_mode`; quantized stores always compact)."""
        if mode == "auto":
            mode = select_mode(L, q_batch, budget_bytes,
                               kw.get("store_dtype", "fp32"))
        return cls(mode=mode, **kw)

    # -------------------------------------------------------------- stages --
    def candidates(self, params, members, queries, delta_members=None,
                   tombstone=None):
        """Probe + gather: top-m buckets per rep -> flat candidate ids
        [Q, R·m·(ML[+DL])] (pad -1), with streaming delta union, tombstone
        masking, and (``adaptive_m``) per-query probe truncation. Bucket
        selection uses raw logits — the top-m set matches scorer_probs
        under any loss while skipping a full [R, Q, B] normalize."""
        with jax.named_scope("scorer_logits"):
            logits = scorer_logits(params, queries)
        with jax.named_scope("top_m"):
            vals, bidx = top_m(logits, self.m)
            keep = (probe_keep_mask(logits, vals, self.probe_mass)
                    if self.adaptive_m and self.probe_mass < 1.0 else None)
        with jax.named_scope("gather"):
            cands = gather_members(members, bidx, delta_members,
                                   probe_keep=keep)
            if tombstone is not None:
                cands = mask_tombstones(cands, tombstone)
        return cands

    def freq_bounds(self, members, base, delta_members=None) -> dict:
        """Static bounds on the frequency stage's candidate rows, from
        shapes alone: every id is a row of ``base`` (``id_bound``), and
        ``gather_members`` puts an id at most once in each of the R·m
        member lists it gathers, and in each delta segment (``max_count``)
        — with tombstones and ``adaptive_m`` too, which only mask."""
        segments = 1 if delta_members is None else 2
        return {"id_bound": int(base.shape[0]),
                "max_count": members.shape[0] * self.m * segments}

    def resolve_store(self, base):
        """Validate ``base`` against ``store_dtype`` and return the
        QuantizedStore if one was passed (else None). Shared by the fused
        :meth:`search` and the staged debug path :meth:`search_staged`."""
        from repro.store.quantized import QuantizedStore
        store = base if isinstance(base, QuantizedStore) else None
        if store is not None and store.dtype != self.store_dtype:
            raise ValueError(
                f"pipeline store_dtype={self.store_dtype!r} but the passed "
                f"store holds {store.dtype!r} codes")
        if store is None and self.store_dtype != "fp32":
            raise ValueError(    # never silently "measure" fp32 as quantized
                f"pipeline store_dtype={self.store_dtype!r} needs a "
                "QuantizedStore base, got a raw array — encode it first "
                "(repro.store.encode)")
        return store

    def search(self, params, members, base, queries, delta_members=None,
               tombstone=None):
        """Full serving path -> (ids [Q, k] with -1 pad, scores [Q, k],
        n_candidates [Q]). base rows are indexed by the member ids — a raw
        [L, d] array (corpus shard / streaming vector buffer) or a
        :class:`~repro.store.quantized.QuantizedStore` over the same rows.
        """
        store = self.resolve_store(base)
        if self.mode == "mega":
            # the ONE fused dispatch (kernels/mega_query): a single jit of
            # the compact sequence
            from repro.kernels.mega_query.ops import mega_search
            return mega_search(self, params, members, base, queries,
                               delta_members, tombstone)
        cands = self.candidates(params, members, queries, delta_members,
                                tombstone)
        if self.mode == "compact":
            with jax.named_scope("freq_topc"):
                cid, cnt = frequency_topC(
                    cands, self.topC, kernel="freq_topc" in self.kernels,
                    **self.freq_bounds(members, base, delta_members))
            if store is not None and store.dtype != "fp32":
                from repro.store.rerank import coarse_stage, refine_stage
                with jax.named_scope("quant_rerank"):
                    cids = coarse_stage(
                        queries, store, cid, cnt, tau=self.tau, k=self.k,
                        refine_k=self.refine_k, metric=self.metric,
                        kernel="quant_rerank" in self.kernels)
                with jax.named_scope("refine"):
                    ids, scores = refine_stage(queries, store, cids,
                                               k=self.k, metric=self.metric)
            else:
                rows = store.codes if store is not None else base
                with jax.named_scope("rerank"):
                    ids, scores = rerank_gathered(queries, rows, cid, cnt,
                                                  self.tau, self.k,
                                                  self.metric)
            n_cand = jnp.sum((cid >= 0) & (cnt >= self.tau), axis=1)
            return ids, scores, n_cand
        if store is not None and store.dtype != "fp32":   # guarded twice:
            raise ValueError(                 # __post_init__ catches the
                "dense mode cannot serve a quantized store")  # config path
        rows = store.codes if store is not None else base
        L = rows.shape[0]
        with jax.named_scope("freq_dense"):
            freq = candidate_frequencies_dense(cands, L)
        with jax.named_scope("rerank"):
            mask = freq >= self.tau
            sim = jnp.where(mask, pairwise_sim(queries, rows, self.metric),
                            -jnp.inf)
            scores, ids = jax.lax.top_k(sim, self.k)
            ids = jnp.where(jnp.isfinite(scores), ids, -1)
        return ids, scores, jnp.sum(mask, axis=1)

    def search_staged(self, params, members, base, queries,
                      delta_members=None, tombstone=None, *, registry=None):
        """Per-stage debug mode of :meth:`search`: the SAME primitive
        sequence, but each serving stage is a separately-jitted call fenced
        with ``jax.block_until_ready`` and timed into the
        ``serve_stage_seconds{stage=...}`` histogram of ``registry``
        (repro.obs; default registry when None). Returns bit-identical
        (ids, scores, n_candidates) — pinned by
        tests/test_obs_integration.py — at the cost of losing cross-stage
        fusion and async dispatch, so it is a diagnosis tool, not the
        serving path.
        """
        from repro import obs
        reg = obs.get_registry(registry)
        store = self.resolve_store(base)

        def run(stage, fn, *args, **static):
            with obs.trace(reg, "serve_stage_seconds", stage=stage) as sp:
                return sp.fence(fn(self, *args, **static))

        if self.mode == "mega":
            # the whole fused search IS the stage: one dispatch, one timing
            # bucket, plus a dispatch counter the obs smoke asserts on
            out = run("mega", _stage_mega, params, members, base, queries,
                      delta_members, tombstone)
            reg.counter("serve_mega_dispatch_total").inc()
            return out

        logits = run("scorer_logits", _stage_logits, params, queries)
        bidx, keep = run("top_m", _stage_topm, logits)
        cands = run("gather", _stage_gather, members, bidx, keep,
                    delta_members, tombstone)
        if self.mode == "compact":
            cid, cnt, n_cand = run(
                "freq_topc", _stage_freq_topc, cands,
                **self.freq_bounds(members, base, delta_members))
            if store is not None and store.dtype != "fp32":
                cids = run("quant_rerank", _stage_quant_coarse, queries,
                           store, cid, cnt)
                ids, scores = run("refine", _stage_quant_refine, queries,
                                  store, cids)
            else:
                rows = store.codes if store is not None else base
                ids, scores = run("rerank", _stage_rerank_gathered, queries,
                                  rows, cid, cnt)
            return ids, scores, n_cand
        rows = store.codes if store is not None else base
        freq = run("freq_dense", _stage_freq_dense, cands, rows)
        return run("rerank", _stage_rerank_dense, queries, rows, freq)


# ------------------------------------------------- staged-mode jit units ----
# One jitted function per serving stage, with the (frozen, hashable)
# QueryPipeline as a static arg so each (pipeline, shapes) pair compiles
# once and re-traces never. Module-level — NOT closures inside
# search_staged — so jit caches persist across calls. Each body is the
# verbatim slice of the fused search()/candidates() code it mirrors: the
# staged-vs-fused bit-identity pin (acceptance criterion) rests on the op
# sequences being the same.

@partial(jax.jit, static_argnames=("pipe",))
def _stage_mega(pipe: QueryPipeline, params, members, base, queries,
                delta_members, tombstone):
    """mode="mega" as one staged unit: the same ops.mega_search dispatch
    the fused path makes, so the staged path stays bit-identical to it
    (the test_obs_integration pin)."""
    from repro.kernels.mega_query.ops import mega_search
    return mega_search(pipe, params, members, base, queries, delta_members,
                       tombstone)


@partial(jax.jit, static_argnames=("pipe",))
def _stage_logits(pipe: QueryPipeline, params, queries):
    del pipe                                      # uniform (pipe, *args) ABI
    return scorer_logits(params, queries)


@partial(jax.jit, static_argnames=("pipe",))
def _stage_topm(pipe: QueryPipeline, logits):
    vals, bidx = top_m(logits, pipe.m)
    if pipe.adaptive_m and pipe.probe_mass < 1.0:
        return bidx, probe_keep_mask(logits, vals, pipe.probe_mass)
    return bidx, None


@partial(jax.jit, static_argnames=("pipe",))
def _stage_gather(pipe: QueryPipeline, members, bidx, probe_keep,
                  delta_members, tombstone):
    del pipe
    cands = gather_members(members, bidx, delta_members,
                           probe_keep=probe_keep)
    if tombstone is not None:
        cands = mask_tombstones(cands, tombstone)
    return cands


@partial(jax.jit, static_argnames=("pipe", "id_bound", "max_count"))
def _stage_freq_topc(pipe: QueryPipeline, cands, *, id_bound=None,
                     max_count=None):
    cid, cnt = frequency_topC(cands, pipe.topC,
                              kernel="freq_topc" in pipe.kernels,
                              id_bound=id_bound, max_count=max_count)
    n_cand = jnp.sum((cid >= 0) & (cnt >= pipe.tau), axis=1)
    return cid, cnt, n_cand


@partial(jax.jit, static_argnames=("pipe",))
def _stage_rerank_gathered(pipe: QueryPipeline, queries, rows, cid, cnt):
    return rerank_gathered(queries, rows, cid, cnt, pipe.tau, pipe.k,
                           pipe.metric)


@partial(jax.jit, static_argnames=("pipe",))
def _stage_quant_coarse(pipe: QueryPipeline, queries, store, cid, cnt):
    from repro.store.rerank import coarse_stage
    return coarse_stage(queries, store, cid, cnt, tau=pipe.tau, k=pipe.k,
                        refine_k=pipe.refine_k, metric=pipe.metric,
                        kernel="quant_rerank" in pipe.kernels)


@partial(jax.jit, static_argnames=("pipe",))
def _stage_quant_refine(pipe: QueryPipeline, queries, store, cids):
    from repro.store.rerank import refine_stage
    return refine_stage(queries, store, cids, k=pipe.k, metric=pipe.metric)


@partial(jax.jit, static_argnames=("pipe",))
def _stage_freq_dense(pipe: QueryPipeline, cands, rows):
    del pipe
    return candidate_frequencies_dense(cands, rows.shape[0])


@partial(jax.jit, static_argnames=("pipe",))
def _stage_rerank_dense(pipe: QueryPipeline, queries, rows, freq):
    mask = freq >= pipe.tau
    sim = jnp.where(mask, pairwise_sim(queries, rows, pipe.metric),
                    -jnp.inf)
    scores, ids = jax.lax.top_k(sim, pipe.k)
    ids = jnp.where(jnp.isfinite(scores), ids, -1)
    return ids, scores, jnp.sum(mask, axis=1)


@partial(jax.jit, static_argnames=("m",))
def probe_buckets(params, queries, m: int):
    """Just the probe: top-m bucket ids [R, Q, m] per query. The serve-time
    load-observability hook (and the LIRA access-frequency prerequisite):
    IRLIServer feeds these ids into its per-bucket probe-frequency
    VectorCounter without re-running the full pipeline. Its ops run under
    the ``probe_stats`` scope, apart from the search's own ``top_m``."""
    with jax.named_scope("probe_stats"):
        logits = scorer_logits(params, queries)
        return top_m(logits, m)[1]


def query_members(params, members: jnp.ndarray, queries, *, m: int, tau: int,
                  L: int, loss_kind: str = "softmax_bce",
                  delta_members: jnp.ndarray | None = None,
                  tombstone: jnp.ndarray | None = None):
    """Full query path over RAW member matrices
    -> (cand_mask [Q, L], freq [Q, L], n_candidates [Q]).

    The single implementation behind both the frozen path (query_index) and
    the streaming path (stream/mutable_index): ``delta_members`` unions the
    live delta segments into the candidate gather and ``tombstone`` masks
    deleted ids out before counting.
    """
    _, bidx = top_buckets(params, queries, m, loss_kind)
    cands = gather_members(members, bidx, delta_members)
    if tombstone is not None:
        cands = mask_tombstones(cands, tombstone)
    freq = candidate_frequencies_dense(cands, L)
    mask = frequency_filter(freq, tau)
    return mask, freq, jnp.sum(mask, axis=1)


def query_index(params, index: InvertedIndex, queries, *, m: int, tau: int,
                L: int, loss_kind: str = "softmax_bce",
                delta_members: jnp.ndarray | None = None,
                tombstone: jnp.ndarray | None = None):
    """query_members over an InvertedIndex's member matrix."""
    return query_members(params, index.members, queries, m=m, tau=tau, L=L,
                         loss_kind=loss_kind, delta_members=delta_members,
                         tombstone=tombstone)


def recall_at(cand_mask: jnp.ndarray, gt: jnp.ndarray) -> jnp.ndarray:
    """recall k@k (paper's R10@10): fraction of gt rows present in the
    candidate set (candidates ⊇ gt-member ⟺ true-distance rerank keeps it).
    Pad-safe: gt entries < 0 (e.g. rerank's "no candidate" -1) are ignored
    instead of wrapping around to index L-1."""
    valid = gt >= 0
    hits = jnp.take_along_axis(cand_mask, jnp.maximum(gt, 0), axis=1)
    hits = hits.astype(jnp.float32) * valid.astype(jnp.float32)
    return jnp.sum(hits) / jnp.maximum(jnp.sum(valid), 1)


def precision_at(scores_mask, freq, queries, label_vecs, gt_labels, ks=(1, 3, 5)):
    """XML P@k given candidate mask + per-candidate frequency as relevance."""
    out = {}
    for k in ks:
        _, top = jax.lax.top_k(jnp.where(scores_mask, freq, -jnp.inf), k)
        hit = (top[..., None] == gt_labels[:, None, :]).any(-1)
        out[f"P@{k}"] = jnp.mean(hit.astype(jnp.float32))
    return out


# ------------------------------------------------------- static contracts --
# The compact path's scalability claim, as registered invariants: proven by
# `python -m repro.launch.audit` (and tests/test_query_pipeline.py asserts
# the same contract ids). Declared here, beside the entry point; the toy
# fixtures live in repro.analysis.fixtures and build lazily at audit time.
from repro.analysis import contracts as _C  # noqa: E402


def _compact_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.query_search("compact")


def _compact_streaming_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.query_search("compact", streaming=True)


def _dense_control():
    from repro.analysis import fixtures as _FX
    return _FX.query_search("dense")


_C.register(_C.Contract(
    id="query.compact_no_dense_table",
    site="repro.core.query.QueryPipeline.search",
    description="compact mode never materializes the [Q, L] count table "
                "(the 100M-scale serving guarantee); dense mode is the "
                "control that MUST build it",
    fixture=_compact_fixture,
    checks=[_C.forbid_dims("Q", "L"), _C.require_dims("Q", "C")],
    control=_dense_control,
))

_C.register(_C.Contract(
    id="query.compact_streaming_no_dense_table",
    site="repro.core.query.QueryPipeline.search (delta + tombstone)",
    description="the streaming path (delta segments unioned, tombstones "
                "dropped) keeps the same no-[Q, L] guarantee",
    fixture=_compact_streaming_fixture,
    checks=[_C.forbid_dims("Q", "L"), _C.require_dims("Q", "C")],
    control=_dense_control,
))


def _audit_oracle_control():
    from repro.analysis import fixtures as _FX
    return _FX.audit_oracle_control()


_C.register(_C.Contract(
    id="query.audit_oracle_off_hot_path",
    site="repro.core.query.exact_topk (ShadowAuditor ground truth)",
    description="the compiled serve pipeline contains no [Q, L] full-probe "
                "table — the exact audit oracle runs strictly off the hot "
                "path, on the sampled shadow window; the oracle's own trace "
                "is the control that MUST build the table",
    fixture=_compact_fixture,
    checks=[_C.forbid_dims("Q", "L")],
    control=_audit_oracle_control,
))
