"""Distributed IRLI (paper §5.3): corpus sharded over P nodes, R_local models
per node — expressed as a JAX mesh program instead of P processes.

Mapping (DESIGN §5):
  paper "node" p ∈ [P]      -> mesh axis "data" (and "pod" outer axis)
  per-node R_local reps     -> local leading axis of the stacked scorer params
                               (optionally sharded over "model")
  per-node inverted index   -> member matrix sharded over "data" (each shard
                               holds only its 1/P of the corpus)
  candidate union           -> per-shard local top-k true-distance rerank,
                               then all_gather of the tiny [k] winners + final
                               top-k merge (exactly the paper's CPU merge).

All four entry points speak the typed API (core/search_api): a
``SearchParams`` in, a ``SearchResult`` out — ``n_candidates`` is psum'd
across shards so the response reports the GLOBAL survivor count. The old
``m=/tau=/k=`` kwargs remain as deprecated shims returning the old
``(ids, scores)`` tuples.

``make_distributed_search`` is written with shard_map so the collective
schedule is explicit (one all_gather of k floats + ids and one [Q] psum per
query — nothing else crosses shards). The same function lowers on the
512-device production mesh in launch/dryrun.py (arch id: the paper's own
"irli-deep1b" config).

Every surface accepts the per-shard ``base`` as either the raw fp32
[L_loc, d] corpus or a ``repro.store.QuantizedStore`` over the same rows
(docs/store.md): each shard then scores gathered CODE rows and refines the
k' coarse survivors at fp32 BEFORE the psum'd merge — the int8 tier is what
lets the deep1b corpus (2^27 × 96-d) fit per-shard HBM at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import search_api as SA
from repro.core.search_api import SearchParams, SearchResult

def _resolve(params: SearchParams, L_loc: int, q_batch: int, members,
             delta_members=None, *, force_compact: bool = False
             ) -> SearchParams:
    """Resolve mode and kernels against the PER-SHARD corpus size and member
    geometry. The production path (shard_search_local /
    make_production_search) exists for corpora where dense would OOM, so it
    pins compact regardless of auto-resolution."""
    if force_compact:
        if params.mode == "dense":
            raise ValueError("the production sharded path is compact-only "
                             "(dense would materialize [Q, L_loc] per shard)")
        params = params.replace(mode="compact")
    return params.resolve(
        L_loc, q_batch,
        width=SA.candidate_width(params.m, members, delta_members))


def _local_arrays(scorer_params, members, base_shard, queries,
                  params: SearchParams, delta_members, tombstone,
                  cache: SA.PipelineCache | None):
    """Shard-local search -> raw (ids, scores, n_cand) arrays. ``params``
    must already be resolved; ``base_shard`` is this shard's raw [L_loc, d]
    corpus or a QuantizedStore over it (so each shard scores CODE rows
    before the merge). Usable inside shard_map/lax.map traces (the cached
    jitted fn inlines)."""
    cache = cache if cache is not None else SA.DEFAULT_CACHE
    SA.check_store("distributed search", params, base_shard)
    fn = cache.get(params, base_shard.shape[0], queries.shape[0])
    return fn(scorer_params, members, base_shard, queries, delta_members,
              tombstone)


def _strip_block(tree):
    """Drop the size-1 shard-leading block dim shard_map leaves on sharded
    inputs — works for raw arrays and QuantizedStore pytrees alike."""
    return jax.tree.map(lambda x: x[0], tree)


def _base_specs(base, axes):
    """Per-leaf PartitionSpecs sharding ``base`` (array or QuantizedStore)
    over ``axes``: every leaf's LEADING (corpus) dim is sharded over the
    joint axes, the rest replicated."""
    axes = tuple(axes)
    dim0 = axes[0] if len(axes) == 1 else axes
    return jax.tree.map(lambda x: P(dim0, *((None,) * (x.ndim - 1))), base)


def local_search(scorer_params, members, base_shard, queries,
                 params: SearchParams | None = None, *,
                 delta_members=None, tombstone=None, epoch: int = 0,
                 cache: SA.PipelineCache | None = None,
                 m=None, tau=None, k=None, loss_kind=None, metric=None,
                 mode=None, topC=None):
    """Single-shard IRLI search: queries [Q, d] vs this shard's corpus.

    members: [R, B, ML] local inverted index (ids into base_shard)
    base_shard: [L_loc, d]
    delta_members [R, B, DL] / tombstone [L_loc] (optional): this shard's
    streaming delta segments and deletion mask — candidates are unioned from
    base + delta and tombstoned ids are dropped before counting, so each
    shard of a distributed deployment can take online updates independently.
    ``epoch`` names the artifact version these members/params came from
    (docs/online.md) and is echoed on the ``SearchResult`` so distributed
    responses carry the same provenance as the mutable serving path.

    Typed path -> :class:`SearchResult` with LOCAL ids (-1 where no
    candidate survived). ``params.mode="auto"`` resolves from L_loc and the
    query batch; "compact" counts + reranks the per-query top-``topC``
    frequent candidates without ever building a [Q, L_loc] table. The bare
    kwargs are a deprecated shim returning the old ``(ids, scores)`` tuple
    (loss_kind was always serving-inert: bucket selection on raw logits
    matches any monotone loss).
    """
    if params is None:
        del loss_kind                           # accepted, always inert
        params = SA.params_from_legacy_kwargs(
            "distributed.local_search", m=m, tau=tau, k=k, metric=metric,
            mode=mode, topC=topC)
        r = _resolve(params, base_shard.shape[0], queries.shape[0], members,
                     delta_members)
        ids, scores, _ = _local_arrays(scorer_params, members, base_shard,
                                       queries, r, delta_members, tombstone,
                                       cache)
        return ids, scores
    SA.check_params("distributed.local_search", params)
    if any(v is not None for v in (m, tau, k, loss_kind, metric, mode, topC)):
        raise TypeError("pass either SearchParams or legacy kwargs, not both")
    r = _resolve(params, base_shard.shape[0], queries.shape[0], members,
                 delta_members)
    ids, scores, n_cand = _local_arrays(scorer_params, members, base_shard,
                                        queries, r, delta_members, tombstone,
                                        cache)
    return SearchResult(ids=ids, scores=scores, n_candidates=n_cand,
                        epoch=epoch, mode=r.mode, kernels=r.kernels)


def _merge_across_shards(ids, scores, n_cand, k: int, axes):
    """all_gather the tiny [Q, k] per-shard winners (ids already globalized
    by the caller), take the global top-k, psum the survivor counts."""
    all_scores = jax.lax.all_gather(scores, axes, axis=1)     # [Q, P, k]
    all_ids = jax.lax.all_gather(ids, axes, axis=1)
    Qn = scores.shape[0]
    best, pos = jax.lax.top_k(all_scores.reshape(Qn, -1), k)
    merged = jnp.take_along_axis(all_ids.reshape(Qn, -1), pos, axis=1)
    return merged, best, jax.lax.psum(n_cand, axes)


def make_distributed_search(mesh: Mesh, params: SearchParams | None = None, *,
                            corpus_axes=("data",), epoch: int = 0,
                            cache: SA.PipelineCache | None = None,
                            m=None, tau=None, k=None, loss_kind=None,
                            metric=None, mode=None, topC=None):
    """Build the sharded search fn. Per-shard params (scorers differ per
    corpus shard, as in the paper: 8 nodes × R=4 distinct models).

    Typed path: ``make_distributed_search(mesh, SearchParams(...))`` returns
    ``search(scorer_params, members, base, queries) -> SearchResult`` with
    GLOBAL ids and shard-summed n_candidates. The legacy kwarg form returns
    the old ``(ids, scores)``-tuple function.
    """
    legacy = params is None
    if legacy:
        del loss_kind
        params = SA.params_from_legacy_kwargs(
            "distributed.make_distributed_search", m=m, tau=tau, k=k,
            metric=metric, mode=mode, topC=topC)
    elif any(v is not None
             for v in (m, tau, k, loss_kind, metric, mode, topC)):
        raise TypeError("pass either SearchParams or legacy kwargs, not both")
    else:
        SA.check_params("distributed.make_distributed_search", params)
    sp = params

    def sharded(scorer_params, members, base, queries):
        # strip the size-1 shard-leading block dim shard_map leaves on the
        # sharded inputs (params [1,R,...], members [1,R,B,ML], base
        # [1,L_loc,d] — or the same leading dim on every QuantizedStore
        # leaf); queries are replicated and arrive full
        scorer_params = _strip_block(scorer_params)
        members = members[0]
        base = _strip_block(base)
        # shard-local search (compact mode keeps the per-shard work O(topC)
        # per query ahead of the tiny all_gather merge)
        r = _resolve(sp, base.shape[0], queries.shape[0], members)
        ids, scores, n_cand = _local_arrays(scorer_params, members, base,
                                            queries, r, None, None, cache)
        # globalize ids: offset by shard start (-1 "no candidate" stays -1)
        axis_index = jax.lax.axis_index(corpus_axes)
        gids = jnp.where(ids >= 0, ids + axis_index * base.shape[0], -1)
        return _merge_across_shards(gids, scores, n_cand, sp.k, corpus_axes)

    def search(scorer_params, members, base, queries):
        # in_specs depend on the base payload's pytree structure (raw array
        # vs QuantizedStore leaves), so the shard_map is built per call —
        # the jit cache downstream still keys on structure, not identity
        mapped = jax.shard_map(
            sharded, mesh=mesh,
            in_specs=(P(*(corpus_axes + (None,))),  # params leading shard axis
                      P(*(corpus_axes + (None, None, None))),  # members
                      _base_specs(base, corpus_axes),
                      P()),                                # queries replicated
            out_specs=(P(), P(), P()),
            check_vma=False)
        ids, scores, n_cand = mapped(scorer_params, members, base, queries)
        if legacy:
            return ids, scores
        L_loc = base.shape[1]
        resolved = _resolve(sp, L_loc, queries.shape[0], members[0])
        return SearchResult(ids=ids, scores=scores, n_candidates=n_cand,
                            epoch=epoch, mode=resolved.mode,
                            kernels=resolved.kernels)

    return search


def shard_corpus(base, n_shards: int):
    """Host-side: split [L, d] corpus into [n_shards, L/n_shards, d]."""
    L = base.shape[0]
    per = L // n_shards
    return base[: per * n_shards].reshape(n_shards, per, -1)


# -------------------------------------------------- production-scale path ---
def shard_search_local(scorer_params, members, base_shard, queries,
                       params: SearchParams | None = None, *,
                       q_chunk: int = 512, delta_members=None, tombstone=None,
                       epoch: int = 0,
                       cache: SA.PipelineCache | None = None,
                       m=None, tau=None, k=None, topC=None, loss_kind=None,
                       metric=None):
    """100M-scale per-shard search: compact pipeline + query chunking.

    Every chip is one of the paper's "nodes": it owns base_shard [L_loc, d]
    (raw fp32 or a QuantizedStore — with ``params.store_dtype="int8"`` the
    shard reranks on code rows and never holds fp32 vectors) and a full
    R-rep inverted index over those L_loc vectors. No [Q, L]
    table is ever built — candidates stay compact:
      scorer top-m -> member gather [Q, R*m*ML] -> sort+run-length count
      -> top-C frequent -> gather vectors -> true-distance top-k.
    Queries processed in chunks of q_chunk to bound the [Qc, C, d] gather.
    Like local_search, optional delta_members/tombstone serve a shard that
    takes streaming updates.

    Typed path -> :class:`SearchResult` (LOCAL ids); compact-only —
    ``params.mode="dense"`` raises. Legacy kwargs -> old ``(ids, scores)``.
    """
    legacy = params is None
    if legacy:
        del loss_kind                   # serving is loss-agnostic (see above)
        params = SA.params_from_legacy_kwargs(
            "distributed.shard_search_local", m=m, tau=tau, k=k,
            metric=metric, mode="compact", topC=topC)
    elif any(v is not None for v in (m, tau, k, topC, loss_kind, metric)):
        raise TypeError("pass either SearchParams or legacy kwargs, not both")
    else:
        SA.check_params("distributed.shard_search_local", params)
    Qn = queries.shape[0]
    chunked = not (Qn <= q_chunk or Qn % q_chunk != 0)
    r = _resolve(params, base_shard.shape[0], q_chunk if chunked else Qn,
                 members, delta_members, force_compact=True)

    def chunk(qs):
        return _local_arrays(scorer_params, members, base_shard, qs,
                             r, delta_members, tombstone, cache)

    if not chunked:
        ids, scores, n_cand = chunk(queries)
    else:
        qs = queries.reshape(Qn // q_chunk, q_chunk, -1)
        ids, scores, n_cand = jax.lax.map(chunk, qs)
        ids = ids.reshape(Qn, r.k)
        scores = scores.reshape(Qn, r.k)
        n_cand = n_cand.reshape(Qn)
    if legacy:
        return ids, scores
    return SearchResult(ids=ids, scores=scores, n_candidates=n_cand,
                        epoch=epoch, mode="compact", kernels=r.kernels)


def make_production_search(mesh: Mesh, params: SearchParams | None = None, *,
                           epoch: int = 0,
                           cache: SA.PipelineCache | None = None,
                           m=None, tau=None, k=None, topC=None,
                           loss_kind=None, metric=None):
    """shard_map search over EVERY chip as a corpus shard (paper §5.3 with
    P = n_devices "nodes"). Inputs (global shapes):

      scorer_params: replicated stacked R-rep scorer
      members: [P, R, B, ML] per-shard inverted indexes (P = mesh size)
      base:    [P, L_loc, d] per-shard corpora
      queries: [Q, d] replicated

    Typed path: ``make_production_search(mesh, SearchParams(...))`` returns
    ``search(...) -> SearchResult`` with GLOBAL ids merged across shards and
    shard-summed n_candidates; compact-only. Legacy kwargs return the old
    ``(ids, scores)``-tuple function.
    """
    legacy = params is None
    if legacy:
        del loss_kind
        params = SA.params_from_legacy_kwargs(
            "distributed.make_production_search", m=m, tau=tau, k=k,
            metric=metric, mode="compact", topC=topC)
    elif any(v is not None for v in (m, tau, k, topC, loss_kind, metric)):
        raise TypeError("pass either SearchParams or legacy kwargs, not both")
    else:
        SA.check_params("distributed.make_production_search", params)
    sp = params
    axes = tuple(mesh.axis_names)

    def local(scorer_params, members, base, queries):
        members = members[0]          # strip the shard-leading dim
        base = _strip_block(base)     # raw array or QuantizedStore leaves
        r = _resolve(sp, base.shape[0], queries.shape[0], members,
                     force_compact=True)
        ids, scores, n_cand = _local_arrays(scorer_params, members, base,
                                            queries, r, None, None, cache)
        # globalize ids and merge
        shard = jax.lax.axis_index(axes)
        gids = jnp.where(ids >= 0, ids + shard * base.shape[0], -1)
        return _merge_across_shards(gids, scores, n_cand, sp.k, axes)

    def search(scorer_params, members, base, queries):
        mapped = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(axes, None, None, None),
                      _base_specs(base, axes), P()),
            out_specs=(P(), P(), P()),
            check_vma=False)
        ids, scores, n_cand = mapped(scorer_params, members, base, queries)
        if legacy:
            return ids, scores
        r = _resolve(sp, base.shape[1], queries.shape[0], members[0],
                     force_compact=True)
        return SearchResult(ids=ids, scores=scores, n_candidates=n_cand,
                            epoch=epoch, mode="compact", kernels=r.kernels)

    return search


# ------------------------------------------------------- static contracts --
# The collective schedule documented above ("one all_gather of k floats +
# ids and one [Q] psum per query — nothing else crosses shards") as a
# registered, byte-bounded invariant, plus the per-shard no-[Q, L] proof.
from repro.analysis import contracts as _C


def _local_compact_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.local_search_compact("compact")


def _local_dense_control():
    from repro.analysis import fixtures as _FX
    return _FX.local_search_compact("dense")


def _production_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.production_search()


_C.register(_C.Contract(
    id="distributed.local_search_compact_no_dense_table",
    site="repro.core.distributed.local_search",
    description="the per-shard serving path in compact mode never builds a "
                "[Q, L_loc] table (dense mode is the control)",
    fixture=_local_compact_fixture,
    checks=[_C.forbid_dims("Q", "L"), _C.require_dims("Q", "C")],
    control=_local_dense_control,
))

_C.register(_C.Contract(
    id="distributed.production_merge_collectives",
    site="repro.core.distributed.make_production_search",
    description="the sharded merge moves ONLY the tiny per-shard winners: "
                "one all-gather of [Q, P, k] scores (f32) + ids (s32) and "
                "one [Q] psum of survivor counts — byte-exact bound, no "
                "other collective kind",
    fixture=_production_fixture,
    checks=[_C.allowed_collectives({
        # scores f32 + ids s32, each [Q, P, k] per device
        "all-gather": lambda fx: 2 * fx.dims["Q"] * fx.dims["P"]
        * fx.dims["k"] * 4,
        # the [Q] s32 psum of n_candidates (headroom for an int64 lowering)
        "all-reduce": lambda fx: 8 * fx.dims["Q"],
    })],
    min_devices=2,
))
