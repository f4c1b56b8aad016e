"""Partition state for IRLI: R independent assignments of L labels into B
buckets, 2-universal hash initialization, load accounting, and the
device-resident inverted index (padded member matrix).

TPU adaptation (DESIGN §3): the inverted index is NOT a host hashmap — it is
a dense [R, B, max_load] member matrix (pad = -1) rebuilt on device after
every re-partition. The paper's load balancing (Thm. 2) is precisely what
keeps ``max_load`` ≈ L/B, so the padded representation is tight: good load
balance == small static shapes == fast TPU gathers. This synergy is the core
of our TPU-native redesign.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


# Large primes for 2-universal hashing  h(x) = ((a*x + b) mod p) mod B
_P = 2_147_483_647  # Mersenne prime 2^31-1


def hash_init(L: int, B: int, R: int, seed: int = 0) -> jnp.ndarray:
    """2-universal random pooling (paper §3.1). Returns assign [R, L] int32."""
    rng = np.random.default_rng(seed)
    a = rng.integers(1, _P, size=(R, 1), dtype=np.int64)
    b = rng.integers(0, _P, size=(R, 1), dtype=np.int64)
    labels = np.arange(L, dtype=np.int64)[None, :]
    assign = ((a * labels + b) % _P) % B
    return jnp.asarray(assign, jnp.int32)


def loads(assign: jnp.ndarray, B: int) -> jnp.ndarray:
    """Bucket loads. assign [R, L] -> [R, B]."""
    one = jnp.ones(assign.shape[1], jnp.int32)
    return jax.vmap(lambda a: jnp.bincount(a, length=B))(assign)


def load_std(assign: jnp.ndarray, B: int) -> jnp.ndarray:
    """Std-dev of bucket load (the paper's Table-3 metric), per rep -> mean."""
    ld = loads(assign, B).astype(jnp.float32)
    return jnp.mean(jnp.std(ld, axis=1))


@dataclasses.dataclass(frozen=True)
class InvertedIndex:
    """Padded CSR-ish inverted index. members[r, b, j] = label id or -1."""
    members: jnp.ndarray   # [R, B, max_load] int32
    load: jnp.ndarray      # [R, B] int32
    max_load: int

    @property
    def dropped(self) -> int:
        """(rep, label) slots past ``max_load`` that the member matrix does
        not hold: those labels cannot be reached through that rep."""
        return int(jnp.sum(jnp.maximum(self.load - self.max_load, 0)))


def build_inverted_index(assign: jnp.ndarray, B: int,
                         max_load: int | None = None) -> InvertedIndex:
    """Rebuild the member matrix from an assignment — pure device ops.

    Sort labels by bucket id; rank-within-bucket via stable cumcount; scatter
    into [B, max_load]. max_load defaults to the observed max (static at
    trace time when assign is concrete; callers pass an explicit bound inside
    jit). Labels past ``max_load`` in a bucket are left out and counted by
    ``InvertedIndex.dropped``. ``assign`` may be sharded (e.g. over the fit
    mesh's "rep" axis): the rebuild is one jitted program, so its ops follow
    the input sharding.
    """
    ld = loads(assign, B)
    if max_load is None:
        max_load = int(jnp.max(ld))
    members = _member_matrix(assign, B, max_load)
    return InvertedIndex(members=members, load=ld, max_load=max_load)


@partial(jax.jit, static_argnames=("B", "max_load"))
def _member_matrix(assign, B: int, max_load: int):
    L = assign.shape[1]

    def one_rep(a):
        order = jnp.argsort(a, stable=True)            # labels grouped by bucket
        sorted_b = a[order]
        # rank of each label within its bucket: position minus the bucket's
        # start (an exclusive cumsum of the bucket sizes)
        counts = jnp.bincount(sorted_b, length=B).astype(jnp.int32)
        start_of_bucket = jnp.cumsum(counts) - counts
        rank = jnp.arange(L) - start_of_bucket[sorted_b]
        mem = jnp.full((B, max_load), -1, jnp.int32)
        # ranks past max_load fall outside the matrix and are dropped (a
        # clipped index would overwrite the bucket's last kept member)
        return mem.at[sorted_b, rank].set(order.astype(jnp.int32),
                                          mode="drop")

    return jax.vmap(one_rep)(assign)


def bucket_targets(assign: jnp.ndarray, label_ids: jnp.ndarray,
                   label_mask: jnp.ndarray, B: int) -> jnp.ndarray:
    """Multi-hot bucket targets for training (paper §3.2).

    assign:    [R, L]
    label_ids: [N, k]  true labels per train point (padded)
    label_mask:[N, k]  1 for real labels
    returns    [R, N, B] float32 — y[r,n,b] = 1 iff some true label in b.
    """
    R = assign.shape[0]
    N, k = label_ids.shape
    buckets = assign[:, label_ids]                       # [R, N, k]
    # scatter-max instead of one_hot+sum: the [R, N, k, B] one-hot
    # intermediate is ~16 GiB/device at production scale (B=20k, k=100).
    r_idx = jnp.arange(R)[:, None, None]
    n_idx = jnp.arange(N)[None, :, None]
    vals = jnp.broadcast_to(label_mask[None, :, :], (R, N, k))
    targets = jnp.zeros((R, N, B), jnp.float32)
    return targets.at[r_idx, n_idx, buckets].max(vals)   # [R, N, B]
