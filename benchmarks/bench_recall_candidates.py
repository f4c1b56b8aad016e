"""Paper Fig. 3: Recall10@10 vs candidate-set size — IRLI vs k-means,
balanced k-means, LSH (signed random projection), random partition.

Every method produces candidates through the SAME harness: pick top-m
buckets per its own query->bucket rule, union members, measure
(recall, mean candidates). IRLI should dominate: higher recall at equal
candidate budget (paper: ~1/6th the candidates of NLSH for equal recall).
"""
import time

import jax.numpy as jnp
import numpy as np

from repro.core import baselines as BL
from repro.core import query as Q
from repro.core.index import IRLIIndex, IRLIConfig
from repro.core.search_api import SearchParams
from repro.data.synthetic import clustered_ann

B = 128


def run(csv=True):
    data = clustered_ann(n_base=8000, n_queries=200, d=16, n_clusters=400,
                         seed=0)
    gt = jnp.asarray(data.gt)
    rows = []

    # ---- IRLI ------------------------------------------------------------
    cfg = IRLIConfig(d=16, n_labels=8000, n_buckets=B, n_reps=8, d_hidden=128,
                     K=16, rounds=4, epochs_per_round=4, batch_size=512,
                     lr=2e-3, seed=1)
    idx = IRLIIndex(cfg)
    idx.fit(data.train_queries, data.train_gt, label_vecs=data.base)
    for m in (1, 2, 4):
        t0 = time.time()
        mask, _, ncand = idx.query(data.queries, m=m, tau=1)
        us = (time.time() - t0) / len(data.queries) * 1e6
        rec = float(Q.recall_at(mask, gt))
        rows.append((f"recall/irli_m={m}", us,
                     f"recall={rec:.3f};cand={float(ncand.mean()):.0f}"))

    # ---- IRLI, compact pipeline (no [Q, L] table) -------------------------
    # candidate-set recall of the O(C) path at the same probe widths: parity
    # with the dense rows above whenever topC covers the survivors
    for m in (1, 2, 4):
        pipe = SearchParams(mode="compact", m=m, tau=1, k=10,
                            topC=1024).resolve(len(data.base)).pipeline()
        t0 = time.time()
        cands = pipe.candidates(idx.params, idx.index.members,
                                jnp.asarray(data.queries))
        cid, cnt = Q.frequency_topC(cands, pipe.topC)
        us = (time.time() - t0) / len(data.queries) * 1e6
        keep = np.where((np.asarray(cnt) >= pipe.tau) & (np.asarray(cid) >= 0),
                        np.asarray(cid), -1)
        gtn = np.asarray(gt)
        rec = np.mean([len(set(r[r >= 0]) & set(g)) / len(g)
                       for r, g in zip(keep, gtn)])
        rows.append((f"recall/irli_compact_m={m}", us,
                     f"recall={rec:.3f};cand={float((keep >= 0).sum(1).mean()):.0f}"))

    # ---- baselines ---------------------------------------------------------
    L = 8000

    def harness(name, assign, top_buckets_fn):
        for m in (1, 2, 4):
            t0 = time.time()
            bidx = top_buckets_fn(m)
            mask = BL.candidates_from_partition(assign, bidx, L)
            us = (time.time() - t0) / len(data.queries) * 1e6
            rec = BL.recall_of_mask(mask, data.gt)
            cand = mask.sum(1).mean()
            rows.append((f"recall/{name}_m={m}", us,
                         f"recall={rec:.3f};cand={cand:.0f}"))

    ka, kc = BL.kmeans_partition(data.base, B, seed=0)
    harness("kmeans", ka,
            lambda m: BL.centroid_top_buckets(data.queries, kc, m))
    ba, bc = BL.balanced_kmeans_partition(data.base, B, iters=8, seed=0)
    harness("balanced_kmeans", ba,
            lambda m: BL.centroid_top_buckets(data.queries, bc, m))
    la, planes = BL.lsh_partition(data.base, B, seed=0)
    harness("lsh", la,
            lambda m: BL.lsh_top_buckets(data.queries, planes, B, m))
    rng = np.random.default_rng(0)
    rp = BL.random_partition(L, B, 0)
    harness("random", rp,
            lambda m: rng.integers(0, B, (len(data.queries), m)).astype(np.int32))

    if csv:
        for name, us, derived in rows:
            print(f"{name},{us:.0f},{derived}")
    from benchmarks import trajectory
    trajectory.record("recall_candidates", rows)
    return rows


if __name__ == "__main__":
    run()
