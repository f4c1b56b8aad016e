"""Pallas TPU megakernel: the ENTIRE compact query path in one launch.

One program per tile of ``tq`` query rows runs the full Alg. 2 serve
sequence without ever writing an intermediate to HBM:

  1. scorer logits + top-m — per rep, the [tq, H] hidden activations are
     one MXU dot; the [H, B] output weights are streamed through VMEM in
     ``tb``-wide tiles (ref slices of the compiler-placed table) with a
     running top-m merge (the irli_topk accumulator), so the [tq, B]
     logits row block never exists at once. The adaptive-m(q) keep mask
     (core/query.probe_keep_mask) is computed from a streaming logsumexp
     carried across the same tiles.
  2. member gather — the just-selected bucket rows are fetched from the
     HBM-resident member table by DOUBLE-BUFFERED async-copy DMA
     (pltpu.make_async_copy, two VMEM row slots + two DMA semaphores:
     row i+1 is in flight while row i is consumed) into the VMEM-resident
     candidate scratch [tq, n].
  3. frequency top-C — freq_topc's bitonic tile body (freq_topc_tile)
     over the candidate scratch, in place.
  4. coarse rerank — per-candidate code rows (int8 block-scaled, bf16, or
     raw fp32) stream through VMEM one row at a time (the quant_rerank
     gather-dequant-dot loop) into a [tq, C] score tile; running top-k'
     merge.
  5. refine epilogue (quantized stores) — the k' coarse survivors are
     re-scored on the exact fp32 tier (or on-the-fly dequant when the
     store keeps none) and merged to the final top-k.

Tie-breaking everywhere uses the smaller-POSITION rule of _topk_merge =
jax.lax.top_k's stability, so outputs match ref.mega_search_ref (the
compact-mode op sequence) — pinned by tests/test_mega_query.py under
interpret mode.

Tile geometry is NOT hardcoded: callers derive ``tb`` and check the
resident footprint via :func:`kernel_vmem_bytes` against the VMEM budget of
the device kind (``repro.kernels.vmem_bytes``, see ops.mega_fits), and the
compiled kernel is capped with kernels.vmem_limit_bytes.

The kernel has not been compiled for a TPU yet, and no serving path routes
to it (ops.mega_search runs the compact stages as one jit instead).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ANY, vmem_limit_bytes
from repro.kernels.freq_topc.freq_topc import (MAX_WIDTH, freq_topc_tile,
                                              pow2_width)
from repro.kernels.irli_topk.irli_topk import _topk_merge


def kernel_vmem_bytes(*, tq: int, d: int, H: int, B: int, R: int, ML: int,
                      m: int, n: int, C: int, kp: int, k: int, tb: int,
                      D: int, block: int) -> int:
    """Resident VMEM footprint model of one megakernel program, in bytes.

    Counts everything that coexists at the widest point: the scorer
    weights held resident (w1/b1/b2 — w2 is streamed, only one [tb, H]
    tile is in flight), the candidate scratch plus the bitonic sort's
    working copies (key, payload, and the shifted partner/compare arrays
    — ~6 live [tq, n] i32 vectors at the deepest exchange), the DMA row
    buffers, and the rerank score tiles. Used by ops.mega_fits to decide
    auto-mode eligibility BEFORE lowering, so oversized (m, topC, k')
    combos fall back to compact instead of failing in the compiler.
    """
    f32 = 4
    weights = (R * d * H + R * H + R * B) * f32          # w1 + b1 + b2
    w2_tile = tb * H * f32                               # one streamed slab
    logits_tile = tq * tb * f32
    hidden = tq * H * f32
    q_tile = tq * d * f32
    cand = tq * n * 4                                    # i32 scratch
    sort_work = 6 * tq * n * 4                           # bitonic live set
    dma = 2 * ML * 4 + 2 * 32                            # row slots + sems
    score = tq * C * f32
    rerank = tq * (2 * kp + 2 * k) * f32 + 2 * D * f32   # survivors + rows
    return (weights + w2_tile + logits_tile + hidden + q_tile + cand
            + sort_work + dma + score + rerank)


def _dma_gather_rows(tab_ref, flat, cand_ref, col0, buf, sem, *, tq: int,
                     ML: int, keep_col=None):
    """Double-buffered DMA gather: rows ``flat`` [tq] of the HBM-resident
    ``tab_ref`` [N, ML] land in cand_ref[:, col0:col0+ML]. Row i+1's copy
    is started before row i's wait, so the fetch of the next member list
    overlaps the store of the current one. ``keep_col`` [tq] bool masks a
    row to -1 (the adaptive-m(q) dropped-probe contract)."""

    def start(i, slot):
        pltpu.make_async_copy(tab_ref.at[pl.dslice(flat[i], 1)],
                              buf.at[slot], sem.at[slot]).start()

    start(0, 0)

    def body(i, c):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < tq)
        def _prefetch():
            start(i + 1, 1 - slot)

        # wait on THIS slot's semaphore (the source slice in the wait
        # descriptor only fixes shapes, any row of tab_ref matches)
        pltpu.make_async_copy(tab_ref.at[pl.dslice(0, 1)],
                              buf.at[slot], sem.at[slot]).wait()
        row = buf[slot, 0]                               # [ML] i32
        if keep_col is not None:
            row = jnp.where(keep_col[i], row, -1)
        cand_ref[pl.ds(i, 1), pl.ds(col0, ML)] = row[None, :]
        return c

    jax.lax.fori_loop(0, tq, body, 0)


def _score_slots(q, cid, valid, load_row, *, metric: str):
    """The quant_rerank gather-score loop: one fp32 row at a time through
    ``load_row`` into a [tq, C'] score tile; invalid slots -> -inf."""
    tq, Cw = cid.shape

    def slot(j, sc):
        def row(i, sc):
            rid = jnp.maximum(cid[i, j], 0)
            v = load_row(rid)                            # [D] f32
            if metric == "l2":
                s = -jnp.sum((q[i] - v) ** 2)
            else:
                s = jnp.sum(q[i] * v)
            return sc.at[i, j].set(s)

        return jax.lax.fori_loop(0, tq, row, sc)

    sc = jax.lax.fori_loop(0, Cw, slot, jnp.zeros((tq, Cw), jnp.float32))
    return jnp.where(valid, sc, -jnp.inf)


def _take_topk(sc, cid, k: int):
    """Top-k of a score tile with ids drawn from ``cid`` — the _topk_merge
    seed/concat idiom shared with quant_rerank (-1 id on -inf slots)."""
    tq = sc.shape[0]
    seed_v = jnp.full((tq, k), -jnp.inf, jnp.float32)
    seed_i = jnp.full((tq, k), -1, jnp.int32)
    vals, pos, _ = _topk_merge(sc, seed_v, seed_i, k)
    ids = jnp.take_along_axis(jnp.concatenate([seed_i, cid], axis=1), pos,
                              axis=1)
    return jnp.where(jnp.isfinite(vals), ids, -1), vals


def _kernel(q_ref, w1_ref, b1_ref, b2_ref, w2_ref, members_ref, rows_ref,
            scales_ref, exact_ref, ids_ref, val_ref, nc_ref, cand_ref, buf,
            sem, *, R: int, B: int, H: int, ML: int, m: int, n: int, C: int,
            kp: int, k: int, tau: int, tb: int, block: int, metric: str,
            kind: str, has_exact: bool, adaptive: bool, probe_mass: float):
    tq = q_ref.shape[0]
    q = q_ref[...].astype(jnp.float32)                   # [tq, d]
    cand_ref[...] = jnp.full_like(cand_ref, -1)
    nb = B // tb

    # ---- stage 1+2: per-rep logits -> top-m -> member DMA ----------------
    for r in range(R):
        h = jax.lax.dot_general(q, w1_ref[r], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = jax.nn.relu(h + b1_ref[r][None, :].astype(jnp.float32))
        b2r = b2_ref[r].astype(jnp.float32)              # [B]

        def tile(bi, carry, h=h, b2r=b2r, r=r):
            vals, idxs, mx, se = carry
            # w2 arrives [B, R*H]; one [tb, H] slab per step
            w2t = w2_ref[pl.ds(bi * tb, tb), r * H:(r + 1) * H]
            lg = jax.lax.dot_general(h, w2t, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            lg = lg + jax.lax.dynamic_slice(b2r, (bi * tb,), (tb,))[None, :]
            tile_ids = bi * tb + jax.lax.broadcasted_iota(
                jnp.int32, lg.shape, 1)
            merged_ids = jnp.concatenate([idxs, tile_ids], axis=1)
            new_vals, new_pos, _ = _topk_merge(lg, vals, idxs, m)
            new_idxs = jnp.take_along_axis(merged_ids, new_pos, axis=1)
            if adaptive:                                 # streaming lse
                tmx = jnp.max(lg, axis=1)
                nmx = jnp.maximum(mx, tmx)
                se = se * jnp.exp(mx - nmx) \
                    + jnp.sum(jnp.exp(lg - nmx[:, None]), axis=1)
                mx = nmx
            return new_vals, new_idxs, mx, se

        vals, bidx, mx, se = jax.lax.fori_loop(
            0, nb, tile,
            (jnp.full((tq, m), -jnp.inf, jnp.float32),
             jnp.zeros((tq, m), jnp.int32),
             jnp.full((tq,), -jnp.inf, jnp.float32),
             jnp.zeros((tq,), jnp.float32)))

        keep = None
        if adaptive:                                     # probe_keep_mask
            lse = mx + jnp.log(se)
            p = jnp.exp(vals - lse[:, None])
            keep = (jnp.cumsum(p, axis=1) - p) < probe_mass

        for j in range(m):
            _dma_gather_rows(
                members_ref, r * B + bidx[:, j], cand_ref,
                (r * m + j) * ML, buf, sem, tq=tq, ML=ML,
                keep_col=None if keep is None else keep[:, j])

    # ---- stage 3: frequency top-C over the VMEM candidate scratch --------
    cid, cnt = freq_topc_tile(cand_ref[...], n=n, C=C)
    valid = (cid >= 0) & (cnt >= tau)
    nc_ref[...] = jnp.sum(valid, axis=1, dtype=jnp.int32)[:, None]

    # ---- stage 4: coarse rerank on streamed code rows --------------------
    def load_coarse(rid):
        crow = rows_ref[pl.ds(rid, 1), :][0]
        if kind == "int8":
            srow = scales_ref[pl.ds(rid, 1), :][0]
            return crow.astype(jnp.float32) * jnp.repeat(srow, block, axis=0)
        return crow.astype(jnp.float32)

    sc = _score_slots(q, cid, valid, load_coarse, metric=metric)

    if kind == "fp32":                                   # single-stage
        ids, vals = _take_topk(sc, cid, k)
        ids_ref[...] = ids
        val_ref[...] = vals
        return

    # ---- stage 5: fused refine epilogue (quantized stores) ---------------
    cids, _ = _take_topk(sc, cid, kp)                    # coarse k' survivors

    def load_refine(rid):
        if has_exact:
            return exact_ref[pl.ds(rid, 1), :][0]
        return load_coarse(rid)

    sc2 = _score_slots(q, cids, cids >= 0, load_refine, metric=metric)
    ids, vals = _take_topk(sc2, cids, k)
    ids_ref[...] = ids
    val_ref[...] = vals


def mega_query(w1, b1, w2, b2, members, rows, scales, exact, queries, *,
               m: int, tau: int, topC: int, k: int, refine_k: int,
               metric: str = "angular", kind: str = "fp32", block: int = 1,
               adaptive_m: bool = False, probe_mass: float = 1.0,
               tq: int = 8, tb: int = 512, vmem_budget: int | None = None,
               interpret: bool = False):
    """One fused dispatch: scorer params (w1 [R,d,H], b1 [R,H], w2 [R,H,B],
    b2 [R,B]), members [R, B, ML] i32, code rows [L, D'] (+ scales/exact
    per ``kind``), queries [Q, d] -> (ids [Q, k], scores [Q, k] f32,
    n_candidates [Q] i32), matching ref.mega_search_ref.

    Call through ops.mega_search — eligibility (backend, VMEM fit, no
    delta/tombstone) lives there; this wrapper only pads, launches, and
    unpads. ``interpret`` runs the kernel in Pallas interpret mode
    (the parity-test path on CPU).
    """
    R, d, H = w1.shape
    B = w2.shape[2]
    ML = members.shape[2]
    D = rows.shape[1]
    Q = queries.shape[0]

    W = R * m * ML
    n = pow2_width(W)
    if n > MAX_WIDTH:
        raise ValueError(
            f"candidate width {W} overflows the freq_topc packed keys "
            f"(max {MAX_WIDTH}); use mode='compact' (ops.mega_fits gates "
            "auto selection on this)")
    C = min(topC, W)
    k_eff = min(k, C)
    from repro.store.rerank import resolve_refine_k
    kp = min(resolve_refine_k(refine_k, k, topC), C)
    tb = min(tb, B)
    while B % tb:                                        # tb must divide B
        tb -= 1

    tq = min(tq, Q)
    Qp = ((Q + tq - 1) // tq) * tq
    qpad = jnp.pad(queries, ((0, Qp - Q), (0, 0)))

    members_flat = members.reshape(R * B, ML)
    w2_bt = jnp.transpose(w2, (2, 0, 1)).reshape(B, R * H)
    scales_in = (scales if scales is not None
                 else jnp.zeros((1, 1), jnp.float32))
    exact_in = exact if exact is not None else jnp.zeros((1, 1), jnp.float32)

    call_kwargs = {}
    if not interpret and vmem_budget:
        call_kwargs["compiler_params"] = vmem_limit_bytes(int(vmem_budget))

    ids, vals, nc = pl.pallas_call(
        functools.partial(
            _kernel, R=R, B=B, H=H, ML=ML, m=m, n=n, C=C, kp=kp, k=k_eff,
            tau=tau, tb=tb, block=block, metric=metric, kind=kind,
            has_exact=exact is not None, adaptive=adaptive_m,
            probe_mass=probe_mass),
        grid=(Qp // tq,),
        in_specs=[
            pl.BlockSpec((tq, d), lambda i: (i, 0)),
            pl.BlockSpec((R, d, H), lambda i: (0, 0, 0)),
            pl.BlockSpec((R, H), lambda i: (0, 0)),
            pl.BlockSpec((R, B), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=ANY),              # w2 [B, R*H]
            pl.BlockSpec(memory_space=ANY),              # members [R*B, ML]
            pl.BlockSpec(memory_space=ANY),              # code rows [L, D]
            pl.BlockSpec(memory_space=ANY),              # scales
            pl.BlockSpec(memory_space=ANY),              # exact tier
        ],
        out_specs=[
            pl.BlockSpec((tq, k_eff), lambda i: (i, 0)),
            pl.BlockSpec((tq, k_eff), lambda i: (i, 0)),
            pl.BlockSpec((tq, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k_eff), jnp.int32),
            jax.ShapeDtypeStruct((Qp, k_eff), jnp.float32),
            jax.ShapeDtypeStruct((Qp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, n), jnp.int32),              # candidate set
            pltpu.VMEM((2, 1, ML), jnp.int32),           # DMA double buffer
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        **call_kwargs,
    )(qpad, w1, b1, b2, w2_bt, members_flat, rows, scales_in, exact_in)

    ids, vals, nc = ids[:Q], vals[:Q], nc[:Q, 0]
    if k_eff < k:                                        # pad unservable tail
        pad = k - k_eff
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    return ids, vals, nc
