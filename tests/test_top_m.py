"""``query.top_m`` is ``lax.top_k`` over the bucket axis, bit for bit, and
the served probe programs no longer sort that axis."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import query as Q

#: (R, Q) rows of every case: small, the bucket axis is what matters
ROWS = (2, 3)


def _logits(kind: str, B: int, seed: int = 0) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    shape = ROWS + (B,)
    if kind == "normal":
        x = rng.normal(size=shape)
    elif kind == "ties":                  # small integers: ties everywhere
        x = rng.integers(0, 3, size=shape)
    elif kind == "equal":
        x = np.full(shape, 1.5)
    elif kind == "neg_inf":               # mostly -inf, a few finite
        x = np.where(rng.random(shape) < 0.95, -np.inf, rng.normal(size=shape))
    elif kind == "signed_zero":           # +0.0 ranks above -0.0
        x = np.where(rng.random(shape) < 0.5, 0.0, -0.0)
    elif kind == "nan":                   # NaN ranks above +inf
        x = rng.normal(size=shape)
        x[rng.random(shape) < 0.001] = np.nan
        x[rng.random(shape) < 0.001] = np.inf
    else:
        raise ValueError(kind)
    return jnp.asarray(x, jnp.float32)


def _primitives(fn, *args) -> set:
    out = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            out.add(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


# B: the served width (20000), widths that are and are not multiples of
# 128 (1280, 1000, 641, 100), and as wide as the widest m (10).
@pytest.mark.parametrize("m", [1, 5, 10])
@pytest.mark.parametrize("B", [20000, 1000, 1280, 641, 100, 10])
def test_top_m_is_top_k_bit_for_bit(B, m):
    fn = jax.jit(Q.top_m, static_argnums=1)
    for seed, kind in enumerate(("normal", "ties", "equal", "neg_inf",
                                 "signed_zero", "nan")):
        x = _logits(kind, B, seed)
        vals, idx = fn(x, m)
        want_vals, want_idx = jax.lax.top_k(x, m)
        assert idx.dtype == want_idx.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx),
                                      err_msg=kind)
        np.testing.assert_array_equal(np.asarray(vals).view(np.int32),
                                      np.asarray(want_vals).view(np.int32),
                                      err_msg=kind)
    # the rounds at every width that holds m buckets: no top_k is left
    prims = _primitives(lambda v: Q.top_m(v, m), x)
    assert "top_k" not in prims, prims


def test_top_m_refuses_a_row_narrower_than_m_as_top_k_does():
    x = _logits("normal", 4)
    with pytest.raises(ValueError, match="top_k"):
        Q.top_m(x, 5)


# ----------------------------------------------- the served probe programs --
R, D, H, B, ML, M = 2, 8, 16, 20000, 4, 5


def _params(seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w1": jax.random.normal(k1, (R, D, H)),
            "b1": jnp.zeros((R, H)),
            "w2": jax.random.normal(k2, (R, H, B)),
            "b2": jax.random.normal(k3, (R, B))}


def _members():
    return jnp.arange(R * B * ML, dtype=jnp.int32).reshape(R, B, ML) % 997


def _queries(n=4):
    return jax.random.normal(jax.random.PRNGKey(9), (n, D))


def _candidates(params, members, queries):
    return Q.QueryPipeline(m=M).candidates(params, members, queries)


def _probe(params, members, queries):
    del members
    return Q.probe_buckets(params, queries, M)


def _plain_top_k(params, members, queries):
    """The positive control: the selection before top_m."""
    del members
    return jax.lax.top_k(Q.scorer_logits(params, queries), M)[1]


def _sorts_over_buckets(fn) -> list:
    """(op, operand type) of every sort or top_k in the lowered program
    whose operand has the B-wide bucket axis."""
    mod = jax.jit(fn).lower(_params(), _members(),
                            _queries()).compiler_ir("stablehlo")

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for o in block.operations:
                    yield o.operation
                    yield from walk(o.operation)

    return [(o.name, str(v.type)) for o in walk(mod.operation)
            if o.name in ("chlo.top_k", "stablehlo.sort", "mhlo.topk")
            for v in o.operands if re.search(rf"[<x]{B}x", str(v.type))]


@pytest.mark.parametrize("fn,sorts", [(_candidates, False), (_probe, False),
                                      (_plain_top_k, True)])
def test_served_probe_does_not_sort_the_bucket_axis(fn, sorts):
    found = _sorts_over_buckets(fn)
    assert bool(found) == sorts, found


def test_served_probe_selects_what_top_k_selects():
    params, members, queries = _params(1), _members(), _queries(8)
    logits = Q.scorer_logits(params, queries)
    want = jax.lax.top_k(logits, M)[1]
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_probe)(params, members, queries)),
        np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_candidates)(params, members, queries)),
        np.asarray(Q.gather_members(members, want)))


def test_rounds_path_runs_inside_top_m_scope():
    """The rounds path is named ``max_and_mask`` inside the stage scope
    ``top_m``, so a trace says which path each compiled shape took."""
    text = jax.jit(_candidates).lower(_params(), _members(), _queries()
                                      ).as_text(debug_info=True)
    paths = re.findall(r'loc\("(jit\(_candidates\)/[^"]*)"', text)
    inner = [p for p in paths if "/top_m/max_and_mask/" in p]
    assert inner, paths
    assert not [p for p in paths if "max_and_mask" in p
                and "/top_m/max_and_mask/" not in p]
