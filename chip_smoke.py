"""Chip smoke test: IRLI's main path on a TPU, through its user entry points.

    python3 chip_smoke.py               # one chip: fit -> int8 store -> serve
    python3 chip_smoke.py --chips 4     # four chips: sharded fit + search

One chip: ``IRLIIndex.fit`` (FitEngine rounds) over a synthetic Deep1B-style
corpus made on the device, an int8 ``QuantizedStore``, and an ``IRLIServer``
answering a few hundred requests with ``SearchParams`` — at the
per-repetition widths of ``repro.configs.irli_deep1b`` (d=96, hidden 1024,
B=20000 buckets, power-of-K with K=10, int8 block 32, serve m=5, tau=2,
k=10). Scale is cut to one chip; every cut is printed on a ``reduced:``
line. The run fails unless recall@10 against an exact on-chip oracle is at
least 0.5, at least 99% of the served id rows equal the same pipeline run
through the jnp references (``SearchParams.kernels=()``), and the compiled
serve program holds at least one Pallas kernel (``tpu_custom_call``).

Four chips (``--chips 4``): one fit round on a ("data", "rep") mesh against
the same round on one device, and ``make_production_search`` over four
corpus shards, each built on its own chip and laid onto the mesh by
``place_shards``, against the four per-shard single-device searches merged
on the host. It fails unless recall@10 against the exact oracle is at least
0.5 there too.

Both fail if the fitted index leaves out any (rep, item) slot
(``FitStats.dropped_slots``).

Weights and data are random, made from ``--seed``. Latencies printed here
are smoke timings, not benchmark numbers. The last line of standard output
is one JSON object naming the device; without a TPU the script exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Widths kept from repro.configs.irli_deep1b (checked against it in main).
D, HIDDEN, BUCKETS, K_CHOICES, BLOCK = 96, 1024, 20000, 10, 32
M_PROBE, TAU, TOP_K = 5, 2, 10

#: One-chip scale. The paper config (irli_deep1b.fit_config) needs ~168 GB
#: for its [R, chunk, B] affinity block and ~84 GB for its [R, batch, B]
#: targets; the round's compiled peak for a described v5e picked the rest:
#: R=32 needs 22.6 GB at batch 256 / chunk 1024 (18.9 GB at 64 / 256), R=16
#: peaks at 16.7e9 B with no room for the index's own params + Adam copy.
#: Train queries: one per 4 corpus rows. A v5e run with one per row took
#: 1139 s of the 1200 s a run may take (352 s of it labelling them).
ONE_CHIP = dict(n_labels=1 << 20, n_reps=8, batch_size=256,
                affinity_chunk=1024, rounds=2, epochs_per_round=1,
                n_train=1 << 18, k_train=100, n_queries=512, max_batch=64)

#: Four-chip scale: the sharded paths and their single-device references.
#: Train queries: one per 2 corpus rows, so that one round balances the
#: buckets. With one per 16 (2^14) the round left the scorers on a few
#: hundred hub buckets (max bucket load 294 vs member width 26, 0.24 of the
#: slots kept, v5e x4); one per 4 still overflowed 3650 slots on the CPU.
FOUR_CHIP = dict(n_labels=1 << 18, n_reps=8, batch_size=256,
                 affinity_chunk=1024, rounds=1, epochs_per_round=1,
                 n_train=1 << 17, k_train=100, n_queries=256, n_shards=4)


def log(*parts):
    print(*parts, flush=True)


def reduced_cuts(sz: dict, paper) -> dict:
    """{knob: [paper value, smoke value]} for every knob cut to size."""
    full = {"n_labels": paper.N_CORPUS, "n_reps": paper.R,
            "batch_size": paper.FIT_BATCH,
            "affinity_chunk": paper.FIT_AFFINITY_CHUNK,
            "rounds": paper.FIT_ROUNDS,
            "epochs_per_round": paper.FIT_EPOCHS_PER_ROUND,
            "k_train": paper.K_NEIGH}
    return {k: [v, sz[k]] for k, v in full.items() if sz[k] != v}


def recall_at_k(ids, gt) -> float:
    import numpy as np
    ids, gt = np.asarray(ids), np.asarray(gt)
    k = gt.shape[1]
    return float(np.mean([len(set(a[a >= 0]) & set(b)) / k
                          for a, b in zip(ids, gt)]))


def rows_match(ids_a, sc_a, ids_b, sc_b):
    """Per query: the id rows are equal, or differ only in the order of
    equal scores (the same ids and the same multiset of scores)."""
    import numpy as np
    ids_a, ids_b = np.asarray(ids_a), np.asarray(ids_b)
    sc_a, sc_b = np.asarray(sc_a), np.asarray(sc_b)
    same = (ids_a == ids_b).all(1)
    tie = ((np.sort(ids_a, 1) == np.sort(ids_b, 1)).all(1)
           & (np.sort(sc_a, 1) == np.sort(sc_b, 1)).all(1))
    return same | tie


def make_data(sz: dict, seed: int, metric: str = "angular"):
    """Corpus, test queries and separate train queries on the device, with
    exact neighbours from the on-device oracle.

    Deep1B's descriptors carry no planted power-law blobs, so the corpus is
    many small clusters (about one bucket's worth of rows each) of equal
    expected size: ``zipf=0``. The recipe's default Zipf-1.3 sizes put a
    quarter of the rows in one blob, more than power-of-K can spread over
    its K=10 choices; the fit then overflows the 2x member width and drops
    rows (see PERF.md, Open questions)."""
    import jax
    from repro.core import query as Q
    from repro.data.synthetic import clustered_ann_device
    t0 = time.perf_counter()
    base, queries, train_q = clustered_ann_device(
        jax.random.PRNGKey(seed), sz["n_labels"], sz["n_queries"],
        sz["n_train"], D, n_clusters=sz["n_labels"] // 64, metric=metric,
        zipf=0.0)
    train_gt = Q.exact_neighbors(train_q, base, sz["k_train"], metric=metric)
    gt = Q.exact_neighbors(queries, base, TOP_K, metric=metric)
    # the blocked oracle against the plain full-width exact_topk
    import jax.numpy as jnp
    import numpy as np
    ref = Q.exact_topk(queries[:8], base, jnp.zeros(base.shape[0], bool),
                       k=TOP_K, metric=metric)
    if not (np.sort(np.asarray(gt[:8]), 1)
            == np.sort(np.asarray(ref), 1)).all():
        raise AssertionError("blocked exact oracle disagrees with exact_topk")
    jax.block_until_ready((base, queries, train_q, train_gt, gt))
    log(f"data: {sz['n_labels']} x {D} corpus, {sz['n_train']} train "
        f"queries (top-{sz['k_train']} labels), {sz['n_queries']} test "
        f"queries, {time.perf_counter() - t0:.1f} s with exact oracle")
    return base, queries, train_q, train_gt, gt


def make_config(sz: dict, seed: int):
    from repro.core.index import IRLIConfig
    return IRLIConfig(d=D, n_labels=sz["n_labels"], n_buckets=BUCKETS,
                      n_reps=sz["n_reps"], d_hidden=HIDDEN, K=K_CHOICES,
                      rounds=sz["rounds"],
                      epochs_per_round=sz["epochs_per_round"],
                      batch_size=sz["batch_size"], lr=1e-3,
                      affinity_chunk=sz["affinity_chunk"], seed=seed)


def kept_share(members, n_slots: int) -> float:
    """Share of the (rep, item) slots that ``members`` holds."""
    return float((members >= 0).sum()) / n_slots


def fit(cfg, train_q, train_gt, base, mesh=None):
    import jax
    from repro.core.index import IRLIIndex
    idx = IRLIIndex(cfg)
    t0 = time.perf_counter()
    stats = idx.fit(train_q, train_gt, label_vecs=base, mesh=mesh)
    members = idx.index.members
    kept = kept_share(members, cfg.n_reps * cfg.n_labels)
    peak = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()}
    log(f"fit: {len(stats.round_idx)} round(s), "
        f"{time.perf_counter() - t0:.1f} s incl. compile, "
        f"loss {stats.train_loss}, reassigned {stats.n_reassigned}, "
        f"load_std {stats.load_std}, max bucket load "
        f"{int(idx.index.load.max())} vs member width {members.shape[-1]}, "
        f"{stats.dropped_slots} slots dropped ({kept:.4f} of (rep, item) "
        f"slots kept), device peak_bytes_in_use so far {json.dumps(peak)}")
    if stats.dropped_slots or kept < 1.0:
        raise AssertionError(f"the fitted index drops {stats.dropped_slots} "
                             f"(rep, item) slots ({kept:.4f} kept)")
    return idx, stats


# --------------------------------------------------------------- one chip --
def one_chip(sz: dict, seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import query as Q
    from repro.core import search_api as SA
    from repro.core.search_api import SearchParams
    from repro.serve.server import IRLIServer
    from repro.store import encode

    base, queries, train_q, train_gt, gt = make_data(sz, seed)
    idx, _ = fit(make_config(sz, seed), train_q, train_gt, base)
    store = encode(base, "int8", BLOCK)
    L = sz["n_labels"]
    sp = SearchParams(m=M_PROBE, tau=TAU, k=TOP_K, store_dtype="int8")
    members = idx.index.members
    width = SA.candidate_width(M_PROBE, members)
    mb = sz["max_batch"]
    resolved = sp.resolve(L, mb, width=width)
    log(f"resolved SearchParams: mode={resolved.mode} "
        f"kernels={list(resolved.kernels)} (candidate width {width})")

    server = IRLIServer(idx, params=sp, base=store, max_batch=mb,
                        max_wait_ms=20.0)
    try:
        compile_s = {}
        for b in server.buckets:      # one compile per pad bucket, up front
            t0 = time.perf_counter()
            jax.block_until_ready(idx.search(queries[:b], store, sp,
                                             cache=server.cache).ids)
            compile_s[b] = round(time.perf_counter() - t0, 2)
            # the server's per-batch probe counter has its own jit
            Q.probe_buckets(idx.params, queries[:b], M_PROBE)
        log(f"serve compile seconds by batch bucket (first call): "
            f"{json.dumps(compile_s)}")
        fn = server.cache.get(resolved, L, mb)
        hlo = fn.lower(idx.params, members, store, queries[:mb], None,
                       None).compile().as_text()
        n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
        log(f"compiled serve program (batch {mb}): {n_kernels} "
            "tpu_custom_call(s)")

        host_q = np.asarray(queries)
        compiles0 = server.cache.compiles
        done = {}
        t0 = time.perf_counter()
        futs = []
        for i in range(host_q.shape[0]):
            t_sub = time.perf_counter()
            f = server.submit(host_q[i])
            f.add_done_callback(
                lambda _f, i=i: done.__setitem__(i, time.perf_counter()))
            futs.append((t_sub, f))
        res = [f.result(timeout=600) for _, f in futs]
        wall = time.perf_counter() - t0
        lat = np.array([done[i] - s for i, (s, _) in enumerate(futs)])
        served_ids = np.stack([r.ids for r in res])
        served_sc = np.stack([r.scores for r in res])
        log(f"smoke timings (not benchmark numbers): {len(res)} requests "
            f"in {wall:.3f} s, submit-to-result p50 "
            f"{np.percentile(lat, 50) * 1e3:.2f} ms, p99 "
            f"{np.percentile(lat, 99) * 1e3:.2f} ms, "
            f"{server.stats['batches']} batches, "
            f"{server.cache.compiles - compiles0} compiles while serving")
        served = sorted({(r.mode, tuple(r.kernels)) for r in res})
        log(f"served (mode, kernels): {served}")
    finally:
        server.close()

    # the same pipeline through the jnp references, at the served batch size
    ref = [idx.search(queries[s:s + mb], store, sp.replace(kernels=()))
           for s in range(0, queries.shape[0], mb)]
    ref_ids = np.concatenate([np.asarray(r.ids) for r in ref])
    ref_sc = np.concatenate([np.asarray(r.scores) for r in ref])
    match = rows_match(served_ids, served_sc, ref_ids, ref_sc)
    rec = recall_at_k(served_ids, gt)
    log(f"recall@{TOP_K} vs exact oracle: {rec:.4f} over {len(res)} "
        f"queries (jnp-reference pipeline: {recall_at_k(ref_ids, gt):.4f})")
    log(f"served vs jnp-reference ids: {int(match.sum())}/{len(match)} "
        f"rows equal ({match.mean():.4f})")
    failures = []
    if rec < 0.5:
        failures.append(f"recall@{TOP_K} {rec:.4f} < 0.5")
    if match.mean() < 0.99 or len(match) < 256:
        failures.append(f"only {match.mean():.4f} of {len(match)} rows "
                        "match the jnp reference")
    if n_kernels < 1:
        failures.append("no tpu_custom_call in the compiled serve program")
    if served != [(resolved.mode, tuple(resolved.kernels))]:
        failures.append(f"served {served}, resolved ({resolved.mode}, "
                        f"{resolved.kernels})")
    if failures:
        raise AssertionError("; ".join(failures))


# ------------------------------------------------------------- four chips --
def _device_report(label: str, arrays) -> None:
    """Which devices hold ``arrays``, and every device's bytes in use."""
    import jax
    devs = set()
    for a in jax.tree.leaves(arrays):
        devs |= set(a.devices())
    used = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()}
    log(f"{label}: device_set={sorted(d.id for d in devs)} "
        f"bytes_in_use={json.dumps(used)}")


def four_chips(sz: dict, seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import partition as PT
    from repro.core.distributed import (make_production_search,
                                        place_shards, shard_search_local)
    from repro.core.search_api import SearchParams
    from jax.sharding import NamedSharding, PartitionSpec as Pspec
    from repro.launch.mesh import make_fit_mesh, make_host_mesh
    from repro.store import encode

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devs)}")
    base, queries, train_q, train_gt, gt = make_data(sz, seed)
    cfg = make_config(sz, seed)

    # ---- one fit round: (data x rep) mesh vs one device -------------------
    # full-precision matmuls on both sides, so the two programs differ only
    # in their summation order
    with jax.default_matmul_precision("highest"):
        one, s1 = fit(cfg, train_q, train_gt, base)
        a1 = np.asarray(one.assign)
        del one                  # device 0 keeps only the sharded state
        mesh = make_fit_mesh(4, rep_axis=2)
        four, s4 = fit(cfg, train_q, train_gt, base, mesh=mesh)
    _device_report("sharded fit state (params, assign)",
                   (four.params, four.assign))
    a4 = np.asarray(four.assign)
    assign_match = float((a1 == a4).mean())
    loss_rel = float(np.max(np.abs(np.array(s1.train_loss)
                                   - np.array(s4.train_loss))
                            / np.abs(np.array(s1.train_loss))))
    log(f"sharded vs single-device round: assign match {assign_match:.5f}, "
        f"max relative loss difference {loss_rel:.2e}, reassigned "
        f"{s1.n_reassigned} vs {s4.n_reassigned}")

    # ---- make_production_search over 4 corpus shards ----------------------
    # each shard's index holds its slice of the sharded fit's assignment,
    # at the largest load any shard sees, so every (rep, item) slot is
    # kept; it is built on the chip that serves it, from its slice alone
    P, L = sz["n_shards"], sz["n_labels"]
    L_loc = L // P
    smesh = make_host_mesh(P)                        # ("data", "model")
    sdevs = list(smesh.devices.flat)
    assign = np.asarray(four.assign)
    parts = [jax.device_put(assign[:, s * L_loc:(s + 1) * L_loc], d)
             for s, d in enumerate(sdevs)]
    max_load = max(int(PT.loads(a, BUCKETS).max()) for a in parts)
    members = [PT.build_inverted_index(a, BUCKETS, max_load).members
               for a in parts]                        # [R, B, ML] each
    kept = sum(kept_share(m, cfg.n_reps * L) for m in members)
    log(f"per-shard indexes: member width {max_load}, {kept:.4f} of "
        f"(rep, item) slots kept")
    stores = [encode(jax.device_put(base[s * L_loc:(s + 1) * L_loc], d),
                     "int8", BLOCK) for s, d in enumerate(sdevs)]
    sp = SearchParams(m=M_PROBE, tau=TAU, k=TOP_K, store_dtype="int8")

    # per-shard single-device searches (each on its shard's chip), merged
    # on the host
    loc_ids, loc_sc = [], []
    for s, d in enumerate(sdevs):
        r = shard_search_local(jax.device_put(four.params, d), members[s],
                               stores[s], jax.device_put(queries, d), sp)
        ids = np.asarray(r.ids)
        loc_ids.append(np.where(ids >= 0, ids + s * L_loc, -1))
        loc_sc.append(np.asarray(r.scores))
    all_ids, all_sc = np.concatenate(loc_ids, 1), np.concatenate(loc_sc, 1)
    order = np.argsort(-all_sc, axis=1, kind="stable")[:, :TOP_K]
    host_ids = np.take_along_axis(all_ids, order, 1)
    host_sc = np.take_along_axis(all_sc, order, 1)

    params, members_all, store_all = place_shards(smesh, four.params,
                                                  members, stores)
    q_all = jax.device_put(queries, NamedSharding(smesh, Pspec()))
    search = make_production_search(smesh, sp)
    t0 = time.perf_counter()
    res = search(params, members_all, store_all, q_all)
    jax.block_until_ready(res.ids)
    t1 = time.perf_counter()
    jax.block_until_ready(search(params, members_all, store_all, q_all).ids)
    log(f"production search over {P} shards: "
        f"{t1 - t0:.1f} s incl. compile, {time.perf_counter() - t1:.3f} s "
        f"the second call, kernels={list(res.kernels)}")
    _device_report("sharded corpus (members, int8 store)",
                   (members_all, store_all))
    match = rows_match(res.ids, res.scores, host_ids, host_sc)
    rec, rec_host = recall_at_k(res.ids, gt), recall_at_k(host_ids, gt)
    log(f"production vs merged per-shard ids: {int(match.sum())}/"
        f"{len(match)} rows equal; recall@{TOP_K} vs exact oracle "
        f"{rec:.4f} (merged per-shard {rec_host:.4f})")
    failures = []
    if assign_match < 0.98:
        failures.append(f"sharded fit assign match {assign_match:.5f}")
    if loss_rel > 1e-3:
        failures.append(f"sharded fit loss differs by {loss_rel:.2e}")
    if match.mean() < 0.99:
        failures.append(f"production search matches merged per-shard "
                        f"results on {match.mean():.4f} of rows")
    if kept < 1.0:
        failures.append(f"per-shard indexes keep {kept:.4f} of the slots")
    if rec < 0.5:
        failures.append(f"production search recall@{TOP_K} {rec:.4f} < 0.5")
    if failures:
        raise AssertionError("; ".join(failures))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    from repro.configs import irli_deep1b as paper

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    assert (D, HIDDEN, BUCKETS, K_CHOICES, BLOCK) == (
        paper.D, paper.HIDDEN, paper.B_BUCKETS, paper.FIT_K,
        paper.STORE_BLOCK)
    assert (M_PROBE, TAU, TOP_K) == (paper.SERVE_M, paper.SERVE_TAU,
                                     paper.SERVE_K)
    sz = FOUR_CHIP if args.chips == 4 else ONE_CHIP
    log(f"device: {dev.device_kind} x{len(jax.devices())}, compile cache "
        f"{cache_dir}")
    log(f"widths: d={D} hidden={HIDDEN} buckets={BUCKETS} K={K_CHOICES} "
        f"store=int8 block={BLOCK} serve m={M_PROBE} tau={TAU} k={TOP_K}")
    log(f"reduced: {json.dumps(reduced_cuts(sz, paper))}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(sz, args.seed)
    else:
        one_chip(sz, args.seed)
    log(f"total: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
