"""Dispatch site for the quantized coarse rerank (store/rerank calls HERE).

``kernel=True`` -> the fused Pallas kernel (quant_rerank.py). Otherwise a
memory-bounded jnp path that processes candidates in chunks of ``chunk``
rows per query, so the only fp32 dequant intermediate is [Q, chunk, D] —
the pipeline passes chunk = k' (the refine depth), making the coarse
stage's peak fp32 working set equal to the refine gather it feeds. The
full-width oracle (ref.py) exists for kernel parity tests only.
"""
import functools

import jax
import jax.numpy as jnp

from repro.store.quantized import dequant_gathered


@functools.partial(jax.jit,
                   static_argnames=("tau", "k", "metric", "chunk"))
def _coarse_chunked(queries, codes, scales, cand_ids, cand_counts, *,
                    tau: int, k: int, metric: str, chunk: int):
    Q, C = cand_ids.shape
    block = codes.shape[1] // scales.shape[1] if scales is not None else 0
    cc = min(chunk, C)
    Cp = ((C + cc - 1) // cc) * cc
    cid = jnp.pad(cand_ids, ((0, 0), (0, Cp - C)), constant_values=-1)
    chunks = jnp.moveaxis(cid.reshape(Q, Cp // cc, cc), 1, 0)  # [nch, Q, cc]

    def one(ids_c):                                   # [Q, cc] -> [Q, cc] f32
        deq = dequant_gathered(codes, scales, jnp.maximum(ids_c, 0),
                               block)                          # [Q, cc, D]
        if metric == "l2":
            return -jnp.sum((queries[:, None, :] - deq) ** 2, axis=-1)
        return jnp.sum(queries[:, None, :] * deq, axis=-1)

    sim = jnp.moveaxis(jax.lax.map(one, chunks), 0, 1).reshape(Q, Cp)[:, :C]
    valid = (cand_ids >= 0) & (cand_counts >= tau)
    sim = jnp.where(valid, sim, -jnp.inf)
    vals, pos = jax.lax.top_k(sim, k)
    ids = jnp.take_along_axis(cand_ids, pos, axis=1)
    return jnp.where(jnp.isfinite(vals), ids, -1), vals


def quant_coarse_topk(queries, codes, scales, cand_ids, cand_counts, *,
                      tau: int, k: int, metric: str = "angular",
                      chunk: int = 64, kernel: bool = False, tq: int = 8):
    """Coarse top-k' over quantized code rows -> (ids [Q, k] with -1 pads,
    coarse scores [Q, k]). ``kernel`` selects the Pallas kernel — the
    choice made where the request resolved ("quant_rerank" in
    ``SearchParams.kernels``) — else the chunked jnp path; both match
    ref.quant_rerank_ref (parity tests in tests/test_kernels.py).
    ``scales=None`` means scale-less (bf16) codes."""
    k = min(k, cand_ids.shape[1])
    if kernel:
        from repro.kernels.quant_rerank.quant_rerank import quant_rerank
        return quant_rerank(queries, codes, scales, cand_ids, cand_counts,
                            tau=tau, k=k, metric=metric, tq=tq)
    return _coarse_chunked(queries, codes, scales, cand_ids, cand_counts,
                           tau=tau, k=k, metric=metric, chunk=chunk)


# ------------------------------------------------------- static contracts --
from repro.analysis import contracts as _C


def _quant_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.quant_rerank_fixture()


def _quant_fullwidth_control():
    from repro.analysis import fixtures as _FX
    return _FX.quant_rerank_fixture(chunk=80)   # chunk = C: full-width dequant


_C.register(_C.Contract(
    id="kernels.quant_rerank.coarse_dequant_bounded",
    site="repro.kernels.quant_rerank.ops.quant_coarse_topk",
    description="the coarse stage's fp32 dequant working set is [Q, chunk, "
                "D], never the full [Q, C, D] candidate width (the control "
                "runs with chunk=C and must materialize it)",
    fixture=_quant_fixture,
    checks=[
        _C.forbid_dims("Q", "C", "D", dtype="float32"),
        _C.require_dims("Q", "chunk", "D", dtype="float32"),
    ],
    control=_quant_fullwidth_control,
))
