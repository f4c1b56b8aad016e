"""Inputs of every cell, made from ``--seed``: keys, the corpus, the scorer
weights, query rows and the open-loop arrival schedule.

The corpus and query recipe is a copy of the program's
``repro.data.synthetic.clustered_ann_device`` (equal expected cluster sizes,
centres N(0, 3^2), members N(0, 0.7^2) around them, queries a corpus row plus
N(0, 0.05^2) noise, unit-normalised for the angular metric), kept here so
that no change to the program can change what the benchmark feeds it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int):
    """A PRNG key for any non-negative seed: the low 32 bits seed the key
    and the high bits are folded in (``PRNGKey`` alone keeps only 32)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


#: fold-in tags, one per input, so each input is its own stream
CORPUS, WEIGHTS, QUERIES, ORDER, SAMPLE, PARTITION = 1, 2, 3, 4, 5, 6


def sub_key(seed: int, tag: int):
    return jax.random.fold_in(root_key(seed), tag)


def _unit(x):
    return x / (jnp.linalg.norm(x, axis=1, keepdims=True) + 1e-9)


@functools.partial(jax.jit,
                   static_argnames=("n", "d", "n_clusters", "metric"))
def corpus(key, *, n: int, d: int, n_clusters: int, metric: str):
    """[n, d] float32 rows: ``n_clusters`` clusters of equal expected size."""
    kc, ka, kn = jax.random.split(key, 3)
    centres = jax.random.normal(kc, (n_clusters, d), jnp.float32) * 3.0
    which = jax.random.randint(ka, (n,), 0, n_clusters)
    rows = centres[which] + jax.random.normal(kn, (n, d), jnp.float32) * 0.7
    return _unit(rows) if metric == "angular" else rows


@functools.partial(jax.jit,
                   static_argnames=("n", "metric"))
def query_rows(key, base, *, n: int, metric: str):
    """[n, d] held-out draws of the corpus mixture: a random corpus row plus
    N(0, 0.05^2) noise."""
    ki, ke = jax.random.split(key)
    rows = jax.random.randint(ki, (n,), 0, base.shape[0])
    q = base[rows] + jax.random.normal(ke, (n, base.shape[1])) * 0.05
    return _unit(q) if metric == "angular" else q


@functools.partial(jax.jit,
                   static_argnames=("R", "d", "H", "B"))
def scorer_weights(key, *, R: int, d: int, H: int, B: int):
    """The R stacked scorer nets as served (float32): w1 [R, d, H] ~
    N(0, 1/d), w2 [R, H, B] ~ N(0, 1/H), zero biases — the program's own
    initialisation, drawn here in one call."""
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (R, d, H), jnp.float32) * d ** -0.5,
            "b1": jnp.zeros((R, H), jnp.float32),
            "w2": jax.random.normal(k2, (R, H, B), jnp.float32) * H ** -0.5,
            "b2": jnp.zeros((R, B), jnp.float32)}


def arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop schedule at
    ``rate`` requests/s over ``seconds``, the one schedule a traffic file's
    ``rate_qps`` asks for.

    Its gaps are not drawn at random: every seed gets the same multiset —
    the exponential distribution's quantiles at (i + 1/2)/n, rescaled to
    span the window, so the gaps of a Poisson stream without its count
    noise — in an order drawn from the seed. Each seed offers exactly
    n = round(rate * seconds) requests with the same burst sizes, and only
    their order differs."""
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {seconds} s offers no request")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng([seed, ORDER])
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def sample(seed: int, n_total: int, n: int) -> np.ndarray:
    """Sorted indices of ``n`` of ``n_total`` items, drawn from the seed."""
    rng = np.random.default_rng([seed, SAMPLE])
    return np.sort(rng.choice(n_total, size=min(n, n_total), replace=False))
