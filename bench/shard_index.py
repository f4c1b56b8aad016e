"""The serving shard's partition, made by the benchmark from ``--seed``.

A serving cell does not fit its index (a fit takes minutes). It partitions
the corpus under the seed's random scorer weights the way a fit round ends:
each label's top-K buckets by scorer logit (the affinity of the paper's
Def. 2), then power-of-K choices — labels inserted one by one in a random
order, each into the least loaded of its K buckets, ties to the bucket with
the higher affinity. Loads then stay balanced as in a fitted index, and a
random scorer acts as a locality-sensitive hash, so a query's neighbours
share its probed buckets.

This is the benchmark's own code: the served program gets the assignment
as its input, as it would load a fitted index, and the reference
(``configs/irli_reference.py``) reads the same assignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _logits(w, x):
    """Scorer logits [R, n, B] at the matmul precision the chip defaults
    to (bf16 operands, fp32 accumulation)."""
    h = jnp.einsum("nd,rdh->rnh", x, w["w1"],
                   preferred_element_type=jnp.float32)
    h = jax.nn.relu(h + w["b1"][:, None, :])
    return jnp.einsum("rnh,rhb->rnb", h, w["w2"],
                      preferred_element_type=jnp.float32) + w["b2"][:, None]


@functools.partial(jax.jit, static_argnames=("K", "chunk"))
def affinity_topk(w, base, K: int, chunk: int):
    """Top-K buckets per label and rep, best first: [R, L·K] int32, label
    l's K buckets at [l·K, (l+1)·K). Flat, because the chip pads a minor
    axis of K=10 to 128 lanes: [R, L, K] at L=2,040,320 would take 33 GB,
    so each block is written into the flat array as it is made. The
    chip's approximate top-k (recall target 0.95): the partition is the
    benchmark's data, and an exact top_k sorts all B logits of every row."""
    L = base.shape[0]
    R = w["w1"].shape[0]

    def block(i, top):
        x = jax.lax.dynamic_slice_in_dim(base, i * chunk, chunk)
        idx = jax.lax.approx_max_k(_logits(w, x), K, recall_target=0.95)[1]
        return jax.lax.dynamic_update_slice_in_dim(
            top, idx.reshape(R, chunk * K), i * chunk * K, axis=1)

    return jax.lax.fori_loop(0, L // chunk, block,
                             jnp.zeros((R, L * K), jnp.int32))


@jax.jit
def centre(w, sample):
    """Set each bucket's bias so that its mean logit over ``sample`` rows is
    0. With random weights a few buckets otherwise lead most rows' top-K,
    and power-of-K cannot spread them (a fitted scorer learns the same
    balance): at L=2^17, B=5000 raw weights overflowed the 2·L/B member
    width by 18514 slots, centred ones by none."""
    return dict(w, b2=w["b2"] - jnp.mean(_logits(w, sample), axis=1))


@functools.partial(jax.jit, static_argnames=("B", "K"))
def power_of_k(top, B: int, K: int, key):
    """Sequential power-of-K insertion, all reps at once: top [R, L·K] (as
    ``affinity_topk`` lays it out) -> assign [R, L] int32. Each rep inserts
    its labels in its own random order; a label goes to the first of its K
    buckets with the least load."""
    R = top.shape[0]
    L = top.shape[1] // K
    order = jax.vmap(lambda k: jax.random.permutation(k, L))(
        jax.random.split(key, R))                              # [R, L]
    reps = jnp.arange(R)
    row = jax.vmap(lambda t, lab: jax.lax.dynamic_slice(t, (lab * K,), (K,)))

    def step(load, t):
        cand = row(top, order[:, t])                           # [R, K]
        cl = jnp.take_along_axis(load, cand, axis=1)
        j = jnp.argmax(cl == cl.min(axis=1, keepdims=True), axis=1)
        b = cand[reps, j]
        return load.at[reps, b].add(1), b

    _, placed = jax.lax.scan(step, jnp.zeros((R, B), jnp.int32),
                             jnp.arange(L))                    # [L, R]
    return jnp.zeros((R, L), jnp.int32).at[reps[:, None], order].set(
        placed.T)


def assignment(w, base, key, *, K: int, B: int, chunk: int):
    """[R, L] int32 bucket of every label in every rep."""
    return power_of_k(affinity_topk(w, base, K=K, chunk=chunk), B=B, K=K,
                      key=key)
