import os

import numpy as np
import pytest

import gen
import harness as H
import tiny


@pytest.fixture(scope="module")
def shard():
    mod = H.kind(tiny.config())
    s = mod.Shard(tiny.config(), 2**31 + 11)
    ref_mod = mod._reference_module()
    ref = ref_mod.ShardReference(s.cfg, s.weights, s.base, s.assign)
    rows = np.asarray(gen.query_rows(gen.sub_key(7, gen.QUERIES), s.base,
                                     n=96, metric="angular"))
    yield s, ref_mod, ref, rows
    s.free()


def test_reference_agrees_with_the_served_search(shard):
    s, ref_mod, ref, rows = shard
    res = s.index.search(rows, s.store, s.params, cache=s.server.cache)
    want, want_scores = ref.search(rows)
    got = ref_mod.compare(ref, rows, res.ids, res.scores, want)
    assert got["score_err"] < 1e-6
    # rows may differ only where the reference's scores tie
    for i in np.flatnonzero((np.asarray(res.ids) != want).any(1)):
        assert len(np.unique(want_scores[i])) < want.shape[1]


def test_reference_reads_the_members_the_program_built(shard):
    s, _, ref, _ = shard
    members = np.asarray(s.index.index.members)
    for r, (order, starts, width) in enumerate(ref.members):
        for b in range(0, s.cfg["buckets"], 37):
            mine = order[starts[b]:starts[b] + width[b]]
            theirs = members[r, b][members[r, b] >= 0]
            assert np.array_equal(np.sort(mine), np.sort(theirs))


def test_control_fails_the_limits(shard):
    s, ref_mod, ref, rows = shard
    want, _ = ref.search(rows)
    c_ids, c_scores = ref.search(rows, precision="control")
    got = ref_mod.compare(ref, rows, c_ids, c_scores, want)
    assert any(got[k] > s.cfg["limits"][k] for k in got), got


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(H.BENCH, "configs", "irli_reference.py")
    assert "repro" not in open(path).read()
