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
  3. top-C by count via a truncated bitonic network: sort blocks of
     C' = pow2_width(C) lanes, then halve the width until it is C' (the
     elementwise max of the two halves keeps each block pair's best C' as
     a bitonic block, which a log2(C')-stage merge sorts again): ~55 + 10
     full-width stage-equivalents at C' = 1024 against the 120 of a full
     sort. Its keys, chosen from static bounds (:func:`key_form`):
       * (count, id) — ``count · 2^b + (2^b - 1 - id)`` with no payload,
         where the caller bounds every id below ``id_bound`` (b its bit
         width) and every count by ``max_count`` so the keys fit int32;
       * (count, position) — unique, carrying the candidate id as payload,
         otherwise.
     Ties break toward the smaller id either way (stage 1 put the ids in
     lane order), matching jax.lax.top_k's stability in the jnp oracle
     exactly.

Outputs match ref.freq_topc_ref (and core/query.sorted_frequency_topC)
bit-for-bit: ids [Q, C] int32 (-1 past the distinct-candidate count),
counts [Q, C] float32 (0 there).

Grid: one program per tile of ``tq`` query rows; all stages vectorized over
the tile. The candidate axis is padded to a power of two (the bitonic
network's requirement), capped at 32768 so (count, position) keys fit
int32.
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


def _exchange(key, payload, idx, lj, down):
    """One compare-exchange stage at the traced lane distance 2^lj: lane i
    meets lane i ^ 2^lj, fetched with a rotation, so nothing needs a
    dynamic gather. ``down`` (int, 1 where the lane's block sorts
    descending) gives each lane its block's direction. With a payload the
    keys must be unique, so a changed key says the pair swapped."""
    n = key.shape[-1]
    j = jnp.left_shift(1, lj)
    upper = (idx >> lj) & 1                             # partner at i - j
    partner = jnp.where(upper == 0, _lanes_from(key, j),
                        _lanes_from(key, n - j))
    # keep the min iff the block ascends exactly when the partner is
    # above: compared as int bits, since Mosaic cannot select or compare
    # i1 vectors
    keep_min = upper == down
    new_key = jnp.where(keep_min, jnp.minimum(key, partner),
                        jnp.maximum(key, partner))
    if payload is not None:
        p_partner = jnp.where(upper == 0, _lanes_from(payload, j),
                              _lanes_from(payload, n - j))
        payload = jnp.where(new_key != key, p_partner, payload)
    return new_key, payload


def _merge(key, payload, idx, lk, down):
    """Bitonic merge of every 2^lk-lane block: exchange distances 2^(lk-1)
    down to 1, as a loop over traced distances, so the kernel's code size
    does not grow with the width."""
    def step(t, carry):
        return _exchange(*carry, idx, lk - 1 - t, down)
    return jax.lax.fori_loop(0, lk, step, (key, payload))


def _sort_blocks(key, payload, log_b: int, down):
    """Bitonic sort of every 2^log_b-lane block along the last axis, each
    block in the direction ``down`` gives its lanes (1 = descending).
    log_b·(log_b+1)/2 exchange stages; log_b = log2(width) sorts the row."""
    idx = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)

    def level(lk, carry):          # sub-blocks alternate; the last level
        d = jnp.where(lk == log_b, down, (idx >> lk) & 1)  # takes ``down``
        return _merge(*carry, idx, lk, d)

    return jax.lax.fori_loop(1, log_b + 1, level, (key, payload))


def _top_blocks(key, payload, b: int):
    """The best ``b`` (a power of two, >= 128) keys of each row, descending,
    by a truncated bitonic network: sort b-lane blocks, then halve the width
    until it is b: the elementwise max of the two halves (blocks ascending
    in the lower half, descending in the upper) is a bitonic block holding
    each pair's best b, which a log2(b)-stage merge sorts again. Keys must
    be unique when a payload rides along. Static halves at multiples of
    128 lanes."""
    log_b = b.bit_length() - 1

    def down(w):                   # the next halving's lower half ascends;
        idx = jax.lax.broadcasted_iota(jnp.int32, (key.shape[0], w), 1)
        return (idx >> (w.bit_length() - 2)) & 1 if w > b else \
            jnp.ones_like(idx)     # the last round descends

    w = key.shape[-1]
    key, payload = _sort_blocks(key, payload, log_b, down(w))
    while w > b:
        w //= 2
        lo, hi = key[:, :w], key[:, w:]
        key = jnp.maximum(lo, hi)
        if payload is not None:
            payload = jnp.where(hi > lo, payload[:, w:], payload[:, :w])
        idx = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
        key, payload = _merge(key, payload, idx, log_b, down(w))
    return key, payload


def _id_bits(id_bound: int | None, max_count: int | None) -> int | None:
    """The id field's width when ``(count, id)`` keys fit int32: every id
    below ``id_bound`` and no id more than ``max_count`` times in a row.
    None (the ``(count, lane)`` keys with the id as payload) where a bound
    is missing or the keys would overflow."""
    if id_bound is None or max_count is None:
        return None
    bits = max(id_bound - 1, 0).bit_length()
    return bits if (max_count + 1) << bits <= 1 << 31 else None


def key_form(id_bound: int | None, max_count: int | None) -> str:
    """Which keys the top-C network sorts: "id" or "lane"."""
    return "lane" if _id_bits(id_bound, max_count) is None else "id"


def freq_topc_tile(x, *, n: int, C: int, id_bound: int | None = None,
                   max_count: int | None = None):
    """The FrequentOnes tile body: candidates [TQ, n] int32 (pad -1, n a
    power of two) -> (ids [TQ, C] int32 with -1 pads, counts [TQ, C] f32).
    Pure vector ops over one VMEM-resident tile — shared by this kernel and
    the fused mega-query pipeline (kernels/mega_query), whose frequency
    stage must count EXACTLY like the standalone dispatch. ``id_bound`` and
    ``max_count`` are static bounds on the input (see :func:`key_form`)."""
    x = jnp.where(x < 0, _SENT, x)
    s, _ = _sort_blocks(x, None, n.bit_length() - 1,
                        jnp.zeros_like(x))               # ascending, pads last

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

    # top-C by count, ties toward the smaller id (top_k's stability in the
    # jnp oracle): the stage-1 sort put ids in lane order, so either key
    # breaks ties the same way
    top = pow2_width(C)
    bits = _id_bits(id_bound, max_count)
    if bits is not None:           # (count, id): the id reads back off the key
        low = (1 << bits) - 1
        key = jnp.where(cnt > 0, (cnt << bits) | (low - s), 0)
        key, _ = _top_blocks(key, None, top)
        top_cnt = key[:, :C] >> bits
        top_ids = low - (key[:, :C] & low)
    else:                          # (count, lane), unique, the id as payload
        key = cnt * n + (n - 1 - idx)
        key, val = _top_blocks(key, s, top)
        top_cnt = key[:, :C] // n
        top_ids = val[:, :C]
    return (jnp.where(top_cnt > 0, top_ids, -1),
            top_cnt.astype(jnp.float32))


def _kernel(cands_ref, ids_ref, cnt_ref, **static):
    ids, cnt = freq_topc_tile(cands_ref[...], **static)
    ids_ref[...] = ids
    cnt_ref[...] = cnt


@functools.partial(jax.jit, static_argnames=("C", "tq", "interpret",
                                             "id_bound", "max_count"))
def freq_topc(cands, *, C: int, tq: int = 8, interpret: bool = False,
              id_bound: int | None = None, max_count: int | None = None):
    """cands [Q, C0] int32 (pad -1) -> (ids [Q, C] int32, counts [Q, C] f32):
    the C most frequent candidate ids per row, count-descending (ties:
    smaller id first); -1/0 past the distinct-candidate count.

    ``id_bound`` (every id is below it) and ``max_count`` (no id occurs
    more often in a row) are optional static bounds; where both are given
    and ``(count, id)`` keys fit int32 the top-C network sorts those, with
    no payload (:func:`key_form`)."""
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
        functools.partial(_kernel, n=n, C=C_eff, id_bound=id_bound,
                          max_count=max_count),
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
