"""A whole run on the CPU with the served path broken underneath: each fault
a serving cell can have must turn ``correct`` false."""
import contextlib

import jax.numpy as jnp
import pytest

import run
import tiny


@contextlib.contextmanager
def altered_answer():
    """The refine returns a wrong id in its first slot."""
    import repro.store.rerank as RR
    orig = RR.refine_stage

    def broken(queries, store, cids, *, k, metric="angular"):
        ids, scores = orig(queries, store, cids, k=k, metric=metric)
        return ids.at[:, 0].set((ids[:, 0] + 1) % store.codes.shape[0]), scores

    RR.refine_stage = broken
    try:
        yield
    finally:
        RR.refine_stage = orig


@contextlib.contextmanager
def half_batch():
    """Half of each batch is left out: only its even rows are searched, and
    each odd row gets the answer of the row before it."""
    from repro.core.query import QueryPipeline
    orig = QueryPipeline.search

    def broken(self, params, members, base, queries, delta_members=None,
               tombstone=None):
        n = queries.shape[0]
        out = orig(self, params, members, base, queries[0::2], delta_members,
                   tombstone)
        return tuple(jnp.repeat(o, 2, axis=0)[:n] for o in out)

    QueryPipeline.search = broken
    try:
        yield
    finally:
        QueryPipeline.search = orig


def _run(breakage=None):
    # a rate at which the tiny shard's batches hold many requests
    return run.run_cell("shard-saturated", 2**31 + 3, 1.0, False,
                        require_tpu=False, conf_file=tiny.config(),
                        traffic=dict(tiny.TRAFFIC, rate_qps=2000),
                        breakage=breakage)


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [altered_answer, half_batch])
def test_fault_turns_correct_false(fault):
    r = _run(fault)
    assert not r["correct"], r["checks"]
