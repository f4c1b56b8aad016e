"""Plain reference of the sharded IRLI deployment (paper §5.3), for the
``search_sharded`` configurations. It imports nothing of the program.

Each shard is searched by the one-shard reference beside this file
(``irli_reference.py``, loaded by path): its own rows, codes and partition,
the scorer every shard shares. A shard's ids are offset to global ids
(shard · L_loc + local id), and the shards' top-k are merged by their
float64 scores, descending, a tie going to the lower global id. Its
precisions, and the ``precision="control"`` step, are the one-shard
reference's, applied on every shard.
"""
from __future__ import annotations

import concurrent.futures
import importlib.util
import os

import numpy as np


def _one_shard():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "irli_reference.py")
    spec = importlib.util.spec_from_file_location("irli_reference_one", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ONE = _one_shard()


class ShardedReference:
    """The reference over P shards: the shared scorer weights, and per shard
    its corpus rows [L_loc, D] and partition [R, L_loc], all as the
    benchmark made them. ``codes`` and ``scales`` cover every shard in
    global-id order, which is what ``compare`` reads."""

    def __init__(self, cfg: dict, weights, bases, assigns):
        self.cfg = cfg
        # one thread a shard: building a shard's member lists is numpy
        # sorting, which runs outside the interpreter lock
        with concurrent.futures.ThreadPoolExecutor(len(bases)) as pool:
            self.shards = list(pool.map(
                lambda ba: ONE.ShardReference(cfg, weights, *ba),
                zip(bases, assigns)))
        self.rows = self.shards[0].codes.shape[0]
        self.codes = np.concatenate([s.codes for s in self.shards])
        self.scales = np.concatenate([s.scales for s in self.shards])

    # dequantise and score by global id, as one shard does by local id
    deq = ONE.ShardReference.deq
    score = ONE.ShardReference.score

    def search(self, queries, precision: str = "stated"):
        """(global ids [n, k] with -1 pads, scores [n, k] float64 with
        -inf)."""
        ids, scores = [], []
        for s, shard in enumerate(self.shards):
            i, sc = shard.search(queries, precision)
            ids.append(np.where(i >= 0, i + s * self.rows, -1))
            scores.append(sc)
        ids, scores = np.concatenate(ids, 1), np.concatenate(scores, 1)
        order = np.lexsort((ids, -scores), axis=-1)[:, :self.cfg["k"]]
        return (np.take_along_axis(ids, order, 1),
                np.take_along_axis(scores, order, 1))


compare = ONE.compare
