"""Pallas TPU kernel: EmbeddingBag (gather + weighted segment-sum).

The recsys serve hot path: for each bag (sample × field), gather up to
``max_per_bag`` table rows and reduce. JAX has no native EmbeddingBag; the
jnp path (models/embedding.py) does take + segment_sum through HBM. This
kernel uses the canonical TPU embedding pattern: bag ids live in SMEM via
scalar prefetch (PrefetchScalarGridSpec) and drive dynamic row loads from the
HBM-resident table, accumulating each bag in VMEM.

Layout: ids [n_bags, max_per_bag] (pad = -1), weights same shape.
Grid: one program per bag tile; inner loop over the bag slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ANY


def _kernel(ids_ref, w_ref, table_ref, out_ref, *, max_per_bag: int):
    # ids_ref/w_ref: [TB, max_per_bag] (VMEM);  table_ref: [V, D] (ANY/HBM)
    tb = out_ref.shape[0]

    def body(j, acc):
        ids = ids_ref[:, j]                               # [TB]
        w = w_ref[:, j]                                    # [TB]

        def gather_row(i, acc):
            rid = ids[i]
            valid = rid >= 0
            safe = jnp.maximum(rid, 0)
            row = table_ref[pl.ds(safe, 1), :][0]
            contrib = jnp.where(valid, w[i], 0.0).astype(jnp.float32) \
                * row.astype(jnp.float32)
            return acc.at[i].add(contrib)

        return jax.lax.fori_loop(0, tb, gather_row, acc)

    acc = jnp.zeros(out_ref.shape, jnp.float32)
    acc = jax.lax.fori_loop(0, max_per_bag, body, acc)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def embedding_bag(ids, weights, table, *, tb: int = 128,
                  interpret: bool = False):
    """ids [N, P] int32 (pad -1), weights [N, P], table [V, D] -> [N, D]."""
    N, P = ids.shape
    V, D = table.shape
    tb = min(tb, N)
    assert N % tb == 0

    return pl.pallas_call(
        functools.partial(_kernel, max_per_bag=P),
        grid=(N // tb,),
        in_specs=[
            pl.BlockSpec((tb, P), lambda i: (i, 0)),
            pl.BlockSpec((tb, P), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=ANY),
        ],
        out_specs=pl.BlockSpec((tb, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), table.dtype),
        interpret=interpret,
    )(ids, weights, table)
