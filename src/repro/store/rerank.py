"""Two-stage rerank over a QuantizedStore: coarse-on-codes, exact-on-k'.

Stage 1 (coarse) scores the compact candidate list [Q, C] on gathered
QUANTIZED code rows — dispatched through kernels/quant_rerank/ops (the fused
Pallas kernel when ``kernel`` is set, candidate-chunked jnp otherwise) — and keeps the k'
best per query. Stage 2 (refine) gathers ONLY those k' rows at fp32 (from
the exact tier when the store keeps one, on-the-fly dequant otherwise) and
re-scores them with core/query.pairwise_sim — the single metric
implementation every rerank path shares — so the final top-k ordering is
exact over the surviving set.

Memory contract (asserted over the jaxpr in tests/test_store.py): with
``store_dtype="int8"`` no fp32 array of shape [L, D] or [Q, C, D] is ever
materialized — the coarse stage's fp32 working set is [Q, k', D] (the jnp
path chunks candidates by k'; the kernel holds one row) and the refine
gather is [Q, k', D] by construction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.query import gathered_sim
from repro.store.quantized import QuantizedStore, check_scales, refine_rows


def resolve_refine_k(refine_k: int, k: int, topC: int) -> int:
    """Materialize the k' knob: 0 means auto (4k, at least 32); always at
    least k and never more than the candidate budget."""
    kp = refine_k if refine_k > 0 else max(4 * k, 32)
    return max(k, min(kp, topC))


def coarse_stage(queries, store: QuantizedStore, cand_ids, cand_counts, *,
                 tau: int, k: int, refine_k: int = 0,
                 metric: str = "angular", kernel: bool = False):
    """Stage 1 alone: coarse top-k' survivor ids [Q, k'] (-1 pads) on
    gathered quantized code rows. Exposed separately so the pipeline's
    ``staged=True`` debug mode can fence and time it apart from the refine
    (core/query.QueryPipeline.search_staged)."""
    # lazy: the dispatch module imports store.quantized, so a module-level
    # import here would cycle through the package __init__ (same idiom as
    # core/query.frequency_topC's kernel dispatch)
    from repro.kernels.quant_rerank.ops import quant_coarse_topk
    check_scales(store)
    kp = resolve_refine_k(refine_k, k, cand_ids.shape[1])
    cids, _ = quant_coarse_topk(queries, store.codes, store.scales,
                                cand_ids, cand_counts, tau=tau, k=kp,
                                metric=metric, chunk=kp, kernel=kernel)
    return cids


def rerank_two_stage(queries, store: QuantizedStore, cand_ids, cand_counts,
                     *, tau: int, k: int, refine_k: int = 0,
                     metric: str = "angular", kernel: bool = False):
    """queries [Q, d], cand_ids/cand_counts [Q, C] (the frequency_topC
    output) -> (ids [Q, k] with -1 where no candidate survived,
    scores [Q, k] EXACT similarities, -inf on pads). Same contract as
    core/query.rerank_gathered, which is the fp32 single-stage analogue."""
    cids = coarse_stage(queries, store, cand_ids, cand_counts, tau=tau,
                        k=k, refine_k=refine_k, metric=metric, kernel=kernel)
    return refine_stage(queries, store, cids, k=k, metric=metric)


def refine_stage(queries, store: QuantizedStore, cids, *, k: int,
                 metric: str = "angular"):
    """Stage 2 alone: exact fp32 re-score of the k' coarse survivors ->
    (ids [Q, k], scores [Q, k])."""
    safe = jnp.maximum(cids, 0)
    # the refine runs even without an exact tier (dequant rows score the
    # same VALUES the coarse stage saw): coarse then only SELECTS the k'
    # set, and the final scores always come from this one gathered_sim
    # call — identical across the coarse backends (Pallas kernel vs
    # chunked jnp), whose fp32 reduction orders differ
    vecs = refine_rows(store, safe)                           # [Q, k', D] f32
    sim = jnp.where(cids >= 0, gathered_sim(queries, vecs, metric), -jnp.inf)
    scores, pos = jax.lax.top_k(sim, min(k, cids.shape[1]))
    ids = jnp.take_along_axis(cids, pos, axis=1)
    ids = jnp.where(jnp.isfinite(scores), ids, -1)
    if scores.shape[1] < k:             # k > topC: pad the unservable tail
        pad = k - scores.shape[1]
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        scores = jnp.pad(scores, ((0, 0), (0, pad)),
                         constant_values=-jnp.inf)
    return ids, scores


# ------------------------------------------------------- static contracts --
# The memory contract in this module's docstring, as a registered invariant
# (audited by repro.launch.audit; tests/test_store.py asserts the same id).
from repro.analysis import contracts as _C


def _int8_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.store_search("int8")


def _fp32_control():
    from repro.analysis import fixtures as _FX
    return _FX.store_search("fp32")


_C.register(_C.Contract(
    id="store.int8_no_fp32_payload",
    site="repro.store.rerank.rerank_two_stage",
    description="with store_dtype='int8' the traced search holds no fp32 "
                "[L, D] (full decode) and no fp32 [Q, C, D] (full-width "
                "gather); fp32 appears only at the [Q, k', D] refine. The "
                "fp32 store is the control that DOES gather full width",
    fixture=_int8_fixture,
    checks=[
        _C.require_dtype_free("float32", "L", "D"),
        _C.forbid_dims("Q", "C", "D", dtype="float32"),
        _C.require_dims("Q", "kp", "D", dtype="float32"),
    ],
    control=_fp32_control,
))
