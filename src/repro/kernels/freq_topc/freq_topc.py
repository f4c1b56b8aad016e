"""Pallas TPU kernel: fused FrequentOnes — per-query sort + run-length count
+ top-C most-frequent candidates.

The compact query pipeline's hot loop (core/query.frequency_topC): gathered
candidate ids [Q, C0] (C0 = R·m·max_load, pad -1) -> the C most frequent ids
per query with their occurrence counts. The jnp path round-trips a [Q, C0]
sort, a segment_sum, and a top_k through HBM; this kernel keeps one query
tile VMEM-resident end to end:

  1. bitonic sort of the candidate row (pads mapped to INT32_MAX so they
     sort last) — pure vector min/max + lane rotations, no lax.sort needed
  2. run-length count via boundary detection + a log-doubling suffix-min
     (next-boundary position minus own position = run length)
  3. top-C by count via a second bitonic pass over packed
     (count, position) keys carrying the candidate id as payload — ties
     break toward the smaller id, matching jax.lax.top_k's stability in the
     jnp oracle exactly.

Outputs match ref.freq_topc_ref (and core/query.sorted_frequency_topC)
bit-for-bit: ids [Q, C] int32 (-1 past the distinct-candidate count),
counts [Q, C] float32 (0 there).

Grid: one program per tile of ``tq`` query rows; all stages vectorized over
the tile. The candidate axis is padded to a power of two (the bitonic
network's requirement), capped at 32768 so packed keys fit int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_SENT = jnp.iinfo(jnp.int32).max   # pads sort last

# widest candidate axis whose packed (count·n + lane) keys fit int32;
# ops.frequent_topc falls back to the jnp oracle beyond this
MAX_WIDTH = 32768


def pow2_width(W: int) -> int:
    """The bitonic candidate-axis width for W raw candidate slots: the
    first power of two >= max(W, 128)."""
    n = 128
    while n < W:
        n *= 2
    return n


def _lanes_from(x, s):
    """y[:, i] = x[:, (i + s) mod n]: a lane rotation by a static or traced
    offset ``s`` — one vector op on the chip, no relayout."""
    n = x.shape[-1]
    return pltpu.roll(x, (n - s) % n, x.ndim - 1)


def _bitonic_sort(key, payload=None):
    """Ascending bitonic sort along the last axis (length power of two),
    optionally permuting ``payload`` identically. Vector min/max + lane
    rotations only — compare-exchange partners (i ^ j) are fetched with a
    rotation, so nothing needs a dynamic gather. The log2(n)·(log2(n)+1)/2
    exchange stages run as two nested loops over traced (k, j), so the
    kernel's code size does not grow with the width.

    With a payload, ties in ``key`` would make the exchange ambiguous —
    callers pass keys made unique by packing the lane index in."""
    n = key.shape[-1]
    log_n = n.bit_length() - 1
    idx = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)

    def exchange(lk, lj, key, payload):
        j = jnp.left_shift(1, lj)
        is_lower = (idx & j) == 0                        # partner at i + j
        partner = jnp.where(is_lower, _lanes_from(key, j),
                            _lanes_from(key, n - j))
        # keep the min iff the block sorts ascending (bit k clear) exactly
        # when the partner is above (bit j clear): compared as int bits,
        # since Mosaic cannot select or compare i1 vectors
        keep_min = ((idx >> lk) & 1) == ((idx >> lj) & 1)
        new_key = jnp.where(keep_min, jnp.minimum(key, partner),
                            jnp.maximum(key, partner))
        if payload is not None:                          # keys are unique
            p_partner = jnp.where(is_lower, _lanes_from(payload, j),
                                  _lanes_from(payload, n - j))
            payload = jnp.where(new_key != key, p_partner, payload)
        return new_key, payload

    def merge(lk, carry):                                # k = 2^lk blocks
        def step(t, carry):
            return exchange(lk, lk - 1 - t, *carry)
        return jax.lax.fori_loop(0, lk, step, carry)

    return jax.lax.fori_loop(1, log_n + 1, merge, (key, payload))


def freq_topc_tile(x, *, n: int, C: int):
    """The FrequentOnes tile body: candidates [TQ, n] int32 (pad -1, n a
    power of two) -> (ids [TQ, C] int32 with -1 pads, counts [TQ, C] f32).
    Pure vector ops over one VMEM-resident tile — shared by this kernel and
    the fused mega-query pipeline (kernels/mega_query), whose frequency
    stage must count EXACTLY like the standalone dispatch."""
    x = jnp.where(x < 0, _SENT, x)
    s, _ = _bitonic_sort(x)                              # ascending, pads last

    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    prev = jnp.where(idx == 0, -1, _lanes_from(s, n - 1))    # s[i-1]
    boundary = (idx == 0) | (s != prev)                  # run (or pad-region) start

    # next boundary after i via suffix-min doubling; run length = next - own
    b = jnp.where(boundary, idx, n)
    sm = jnp.where(idx < n - 1, _lanes_from(b, 1), n)
    d = 1
    while d < n:
        sm = jnp.minimum(sm, jnp.where(idx < n - d, _lanes_from(sm, d), n))
        d *= 2
    cnt = jnp.where(boundary & (s != _SENT), sm - idx, 0)   # [TQ, n]

    # top-C by count: pack (count, lane) so keys are unique and ties break
    # toward the smaller position == smaller candidate id (top_k stability)
    key = cnt * n + (n - 1 - idx)
    skey, sval = _bitonic_sort(-key, payload=s)          # ascending(-key) = desc
    top_cnt = (-skey[:, :C]) // n
    top_ids = sval[:, :C]
    return (jnp.where(top_cnt > 0, top_ids, -1),
            jnp.maximum(top_cnt, 0).astype(jnp.float32))


def _kernel(cands_ref, ids_ref, cnt_ref, *, n: int, C: int):
    ids, cnt = freq_topc_tile(cands_ref[...], n=n, C=C)
    ids_ref[...] = ids
    cnt_ref[...] = cnt


@functools.partial(jax.jit, static_argnames=("C", "tq", "interpret"))
def freq_topc(cands, *, C: int, tq: int = 8, interpret: bool = False):
    """cands [Q, C0] int32 (pad -1) -> (ids [Q, C] int32, counts [Q, C] f32):
    the C most frequent candidate ids per row, count-descending (ties:
    smaller id first); -1/0 past the distinct-candidate count."""
    Q, C0 = cands.shape
    n = pow2_width(C0)
    if n > MAX_WIDTH:    # not an assert: -O must not turn this into silent
        raise ValueError(  # int32 key overflow and wrong top-C ids
            f"candidate width {C0} overflows int32 packed keys "
            f"(max {MAX_WIDTH}); use the jnp path (ops.frequent_topc)")
    C_eff = min(C, C0)

    tq = min(tq, Q)
    Qp = ((Q + tq - 1) // tq) * tq
    padded = jnp.pad(cands, ((0, Qp - Q), (0, n - C0)), constant_values=-1)

    ids, cnt = pl.pallas_call(
        functools.partial(_kernel, n=n, C=C_eff),
        grid=(Qp // tq,),
        in_specs=[pl.BlockSpec((tq, n), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tq, C_eff), lambda i: (i, 0)),
            pl.BlockSpec((tq, C_eff), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, C_eff), jnp.int32),
            jax.ShapeDtypeStruct((Qp, C_eff), jnp.float32),
        ],
        interpret=interpret,
        name="freq_topc",   # the trace's op name: pinned, not derived
    )(padded)
    ids, cnt = ids[:Q], cnt[:Q]
    if C_eff < C:                                        # pad to requested C
        ids = jnp.pad(ids, ((0, 0), (0, C - C_eff)), constant_values=-1)
        cnt = jnp.pad(cnt, ((0, 0), (0, C - C_eff)))
    return ids, cnt
