"""Plain reference of one IRLI serving shard (paper Alg. 2), for the
``serve_shard`` configurations. It imports nothing of the program.

For each query: score the R scorer nets, probe the top-m buckets of every
rep, gather those buckets' members (each bucket holds its labels in
ascending id order, at most ``member_width`` of them), count how often each
label was gathered, keep the ``topC`` most frequent (count descending, id
ascending), drop those counted fewer than ``tau`` times, score the rest on
their int8 block-scaled codes, keep the best ``refine_k``, score those again
and return the best ``k``. Both scores are the dot product of the query with
the dequantised row (the store keeps no fp32 tier).

Precisions, as the configuration states them:

  * the scorer runs at the platform's default matmul precision — on a TPU
    one pass of bf16 operands with fp32 accumulation, as the served program
    runs it — and the probe is the top-m of its fp32 logits;
  * int8 codes and scales are made on the device, with the jnp expression
    the configuration states (scale = max|x| / 127 per 32-wide block,
    code = round(x / scale)), so that division rounds as it does in the
    served program;
  * the two rerank scores are computed here in float64, above the served
    program's fp32 at the highest matmul precision.

``precision="control"`` computes the same pipeline one step lower, as the
step that would tempt a later change: fp8 (e4m3) scorer operands, and both
rerank scores with three bf16 passes (``high``) in place of fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("m", "operand"))
def _probe(w, x, *, m: int, operand: str):
    """Top-m bucket ids [R, n, m] of every query and rep."""
    def cast(a):
        if operand == "fp8":
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return a

    h = jnp.einsum("nd,rdh->rnh", cast(x), cast(w["w1"]),
                   preferred_element_type=jnp.float32)
    h = jnp.maximum(h + w["b1"][:, None, :], 0.0)
    logits = jnp.einsum("rnh,rhb->rnb", cast(h), cast(w["w2"]),
                        preferred_element_type=jnp.float32)
    return jax.lax.top_k(logits + w["b2"][:, None, :], m)[1]


@functools.partial(jax.jit, static_argnames=("block",))
def _encode(x, *, block: int):
    """int8 codes [L, D] and fp32 scales [L, D/block]."""
    L, D = x.shape
    xb = x.reshape(L, D // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scales = jnp.where(amax > 0, amax, 1.0) / 127.0
    codes = jnp.round(xb / scales[..., None]).astype(jnp.int8)
    return codes.reshape(L, D), scales


def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


def _dots(q, rows, precision: str):
    """q [D] . rows [n, D]: float64, or three bf16 passes for the control."""
    if precision != "control":
        return rows.astype(np.float64) @ q.astype(np.float64)
    qh, rh = _bf16(q), _bf16(rows)
    ql, rl = _bf16(q - qh), _bf16(rows - rh)
    return (rh @ qh + rl @ qh + rh @ ql).astype(np.float64)


class ShardReference:
    """The reference over one shard: scorer weights, corpus rows [L, D] and
    the partition ``assign`` [R, L], all as the benchmark made them."""

    def __init__(self, cfg: dict, weights, base, assign):
        self.cfg = cfg
        self.w = weights
        self.base = base
        codes, scales = _encode(base, block=cfg["store_block"])
        self.codes = np.asarray(codes)
        self.scales = np.asarray(scales)
        a = np.asarray(assign)
        width = cfg["member_width"]
        self.members = []                  # per rep: (sorted labels, starts)
        for r in range(a.shape[0]):
            order = np.argsort(a[r], kind="stable")      # ascending ids
            counts = np.bincount(a[r], minlength=cfg["buckets"])
            starts = np.concatenate([[0], np.cumsum(counts)])
            self.members.append((order, starts, np.minimum(counts, width)))

    def deq(self, ids):
        """Dequantised rows [n, D] float32 of labels ``ids``."""
        b = self.cfg["store_block"]
        return (self.codes[ids].astype(np.float32)
                * np.repeat(self.scales[ids], b, axis=-1))

    def score(self, q, ids, precision: str = "stated"):
        return _dots(np.asarray(q, np.float32), self.deq(ids), precision)

    def probes(self, queries, precision: str = "stated", block: int = 128):
        """[n, R, m] probed buckets."""
        op = "fp8" if precision == "control" else "default"
        out = []
        for s in range(0, queries.shape[0], block):
            out.append(np.asarray(_probe(self.w, jnp.asarray(
                queries[s:s + block]), m=self.cfg["m"], operand=op)))
        return np.concatenate(out, axis=1).transpose(1, 0, 2)

    def search(self, queries, precision: str = "stated"):
        """(ids [n, k] with -1 pads, scores [n, k] float64 with -inf)."""
        cfg = self.cfg
        k, tau, topc = cfg["k"], cfg["tau"], cfg["topC"]
        keep = cfg["refine_k"] or max(4 * k, 32)
        keep = max(k, min(keep, topc))
        probes = self.probes(queries, precision)
        ids = np.full((len(queries), k), -1, np.int64)
        scores = np.full((len(queries), k), -np.inf)
        for i, q in enumerate(np.asarray(queries, np.float32)):
            got = [order[starts[b]:starts[b] + width[b]]
                   for (order, starts, width), bs in zip(self.members,
                                                         probes[i])
                   for b in bs]
            cand, count = np.unique(np.concatenate(got), return_counts=True)
            top = np.lexsort((cand, -count))[:topc]     # count desc, id asc
            cand = cand[top][count[top] >= tau]
            if cand.size == 0:
                continue
            coarse = self.score(q, cand, precision)
            pos = np.argsort(-coarse, kind="stable")[:keep]
            cand = cand[pos]
            fine = self.score(q, cand, precision)
            pos = np.argsort(-fine, kind="stable")[:k]
            ids[i, :pos.size] = cand[pos]
            scores[i, :pos.size] = fine[pos]
        return ids, scores


def compare(ref: ShardReference, queries, served_ids, served_scores,
            ref_ids) -> dict:
    """The numbers that decide ``correct`` for a sample of served requests:

    * ``id_miss``: share of the reference's top-k ids missing from what was
      served, and of served ids the reference does not return (symmetric
      difference over 2·n·k slots);
    * ``score_err``: the widest gap between a served score and the
      reference's score of the id served beside it (a served id that is
      out of range, or an id on a -inf slot, reads as infinite)."""
    served_ids = np.asarray(served_ids)
    served_scores = np.asarray(served_scores, np.float64)
    n, k = served_ids.shape
    diff, err = 0, 0.0
    L = ref.codes.shape[0]
    for i in range(n):
        got = served_ids[i]
        want = set(int(x) for x in ref_ids[i] if x >= 0)
        have = set(int(x) for x in got if x >= 0)
        diff += len(want ^ have)
        ok = got >= 0
        if np.any(got >= L) or not np.all(np.isfinite(served_scores[i][ok])):
            err = np.inf
            continue
        if ok.any():
            true = ref.score(queries[i], got[ok])
            err = max(err, float(np.max(np.abs(true - served_scores[i][ok]))))
    return {"id_miss": diff / (2.0 * n * k), "score_err": err}
