"""Pallas TPU kernel: fused block dequant + similarity + running top-k' over
gathered quantized code rows.

The coarse stage of the tiered store's two-stage rerank
(store/rerank.rerank_two_stage): for each query, score its compact candidate
list against int8 (or bf16) block-scaled code rows and keep the k' best for
the exact fp32 refine. The wrapper gathers the candidates' CODE rows with
one XLA gather (1 byte per element for int8 — the fp32 [Q, C, D] never
exists) and the kernel keeps one query tile VMEM-resident through:

  1. dequant + score — per query, the [C, D] code tile meets the query on
     the MXU: angular is Σ_b scale_b · (q masked to block b) · codes, l2 is
     |q|² - 2 q·deq + |deq|² with |deq|² from the same block sums. Dots run
     at HIGHEST precision, so int8 codes and fp32 scales enter exactly.
  2. top-k' — k' rounds of max + smallest-position argmax over the [TQ, C]
     score tile (ties toward the smaller candidate POSITION — exactly
     jax.lax.top_k's stability, so ids match the jnp oracle ref.py).

Slots with no surviving candidate (id < 0 or count < tau) score -inf and
emit id -1 — the same contract as core/query.rerank_gathered.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))                 # contract both minor dims


def _dot_nt(a, b):
    """a [M, D] · b [N, D]ᵀ -> [M, N] fp32 on the MXU at full precision."""
    return jax.lax.dot_general(a, b, _NT, precision=_HI,
                               preferred_element_type=jnp.float32)


def _kernel(q_ref, rows_ref, sc_ref, cid_ref, cnt_ref, ids_ref, val_ref, *,
            k: int, tau: int, block: int, metric: str):
    tq, C, D = rows_ref.shape
    nb = sc_ref.shape[1]
    # blk[b, d] = 1 iff lane d belongs to scale block b
    lane_blk = jax.lax.broadcasted_iota(jnp.int32, (nb, D), 1) // block
    blk = (lane_blk == jax.lax.broadcasted_iota(jnp.int32, (nb, D), 0)
           ).astype(jnp.float32)
    rows = []
    for i in range(tq):                        # static: row writes stay static
        codes = rows_ref[i].astype(jnp.float32)          # [C, D]
        scale = sc_ref[i]                                # [nb, C]
        q = q_ref[i:i + 1, :]                            # [1, D]
        qd = jnp.sum(scale * _dot_nt(blk * q, codes), axis=0, keepdims=True)
        if metric == "l2":
            dd = jnp.sum(scale * scale * _dot_nt(blk, codes * codes),
                         axis=0, keepdims=True)
            s = 2.0 * qd - dd - jnp.sum(q * q, axis=1, keepdims=True)
        else:
            s = qd
        rows.append(s)                                   # [1, C]
    sc = jnp.concatenate(rows, axis=0)                   # [TQ, C]
    cid = cid_ref[...]
    valid = (cid >= 0) & (cnt_ref[...] >= tau)
    sc = jnp.where(valid, sc, -jnp.inf)

    col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (tq, k), 1)
    vals = jnp.full((tq, k), -jnp.inf, jnp.float32)
    ids = jnp.full((tq, k), -1, jnp.int32)
    for t in range(k):
        best = jnp.max(sc, axis=1, keepdims=True)                # [TQ, 1]
        pos = jnp.min(jnp.where(sc == best, col, C), axis=1, keepdims=True)
        hit = col == pos
        rid = jnp.max(jnp.where(hit, cid, -1), axis=1, keepdims=True)
        vals = jnp.where(out_col == t, best, vals)
        ids = jnp.where(out_col == t, rid, ids)
        sc = jnp.where(hit, -jnp.inf, sc)
    ids_ref[...] = jnp.where(vals > -jnp.inf, ids, -1)
    val_ref[...] = vals


@functools.partial(jax.jit,
                   static_argnames=("tau", "k", "metric", "tq", "interpret"))
def quant_rerank(queries, codes, scales, cand_ids, cand_counts, *, tau: int,
                 k: int, metric: str = "angular", tq: int = 8,
                 interpret: bool = False):
    """queries [Q, D] f32, codes [L, D] int8|bf16, scales [L, D/block] f32
    (None for scale-less bf16 codes), cand_ids [Q, C] i32 (pad -1),
    cand_counts [Q, C] f32 -> (ids [Q, k] i32 with -1 where no survivor,
    scores [Q, k] f32 coarse similarities, -inf on the -1 slots)."""
    Q, C = cand_ids.shape
    D = codes.shape[1]
    k = min(k, C)
    safe = jnp.maximum(cand_ids, 0)
    rows = codes[safe]                                   # [Q, C, D] codes
    if scales is None:
        block = D
        sc_t = jnp.ones((Q, 1, C), jnp.float32)
    else:
        block = D // scales.shape[1]
        sc_t = jnp.swapaxes(scales[safe], 1, 2)          # [Q, nb, C]
    nb = sc_t.shape[1]

    tq = min(tq, Q)
    Qp = ((Q + tq - 1) // tq) * tq
    pad = Qp - Q
    if pad:
        queries = jnp.pad(queries, ((0, pad), (0, 0)))
        rows = jnp.pad(rows, ((0, pad), (0, 0), (0, 0)))
        sc_t = jnp.pad(sc_t, ((0, pad), (0, 0), (0, 0)))
        cand_ids = jnp.pad(cand_ids, ((0, pad), (0, 0)), constant_values=-1)
        cand_counts = jnp.pad(cand_counts, ((0, pad), (0, 0)))

    ids, vals = pl.pallas_call(
        functools.partial(_kernel, k=k, tau=tau, block=block, metric=metric),
        grid=(Qp // tq,),
        in_specs=[
            pl.BlockSpec((tq, D), lambda i: (i, 0)),
            pl.BlockSpec((tq, C, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((tq, nb, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((tq, C), lambda i: (i, 0)),
            pl.BlockSpec((tq, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tq, k), lambda i: (i, 0)),
            pl.BlockSpec((tq, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
        ],
        interpret=interpret,
        name="quant_rerank",   # the trace's op name: pinned, not derived
    )(queries, rows, sc_t, cand_ids, cand_counts)
    return ids[:Q], vals[:Q]
