"""Dispatch + budgeting for the fused query megakernel (mode="mega").

``mega_search`` is the ONE dispatch site: QueryPipeline.search routes every
mode="mega" call here, and the traced program is EXACTLY ONE top-level
dispatch (the ``query.mega_single_dispatch`` contract, registered below):
the compact-mode pipeline as ONE jitted call (``_fused``). Because it jits
the verbatim compact op sequence, mode="mega" is bit-identical to
mode="compact" on every surface — the parity suite
(tests/test_mega_query.py) pins this across stores, metrics, adaptive_m,
and mutable delta/tombstone/hot-replica state. The stages inside run the
Pallas kernels that ``pipe.kernels`` names, as compact's do.

The Pallas megakernel (mega_query.py) is not served: it has not been
compiled for a chip, so no request can route to it. It stays, with its
interpret-mode parity tests and the footprint model below, until a PR
compiles it for a chip and shows where it pays (ROADMAP §1 item 4).
``mega_fits`` is that footprint model: the budget is a fraction of the
device kind's per-core VMEM (``repro.kernels.vmem_bytes``, which raises for
a kind it does not know) and the footprint comes from
mega_query.kernel_vmem_bytes over the real serving geometry.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax

from repro.kernels import vmem_bytes
from repro.kernels.freq_topc.freq_topc import MAX_WIDTH, pow2_width
from repro.kernels.mega_query.mega_query import kernel_vmem_bytes

#: Tile constants of the megakernel: tq query rows per program, tb-wide
#: streamed w2 slabs.
TQ, TB = 8, 512

#: fraction of per-core VMEM the kernel may claim (the rest is the
#: compiler's for pipelining slack and output staging)
VMEM_FRACTION = 0.75


def vmem_budget(device_kind: str) -> int:
    """The kernel's VMEM byte budget on ``device_kind``."""
    return int(VMEM_FRACTION * vmem_bytes(device_kind))


def mega_vmem_bytes(m: int, topC: int, refine_k: int, k: int, *,
                    geom: dict) -> int:
    """Megakernel VMEM footprint of a (m, topC, k', k) knob combo over the
    serving geometry ``geom`` (keys d, H, B, R, ML, D, block)."""
    from repro.store.rerank import resolve_refine_k
    W = geom["R"] * m * geom["ML"]
    n = pow2_width(W)
    C = min(topC, W)
    kp = min(resolve_refine_k(refine_k, k, topC), C)
    return kernel_vmem_bytes(
        tq=TQ, d=geom["d"], H=geom["H"], B=geom["B"], R=geom["R"],
        ML=geom["ML"], m=m, n=n, C=C, kp=kp, k=min(k, C),
        tb=min(TB, geom["B"]), D=geom["D"], block=geom["block"])


def mega_fits(m: int, topC: int, refine_k: int, k: int, *, geom: dict,
              device_kind: str) -> bool:
    """True iff the megakernel can lower AND fit for these knobs on
    ``device_kind``: the candidate width's packed sort keys stay within
    int32 (the freq_topc MAX_WIDTH bound) and the resident tile set stays
    within the VMEM budget."""
    if pow2_width(geom["R"] * m * geom["ML"]) > MAX_WIDTH:
        return False
    return (mega_vmem_bytes(m, topC, refine_k, k, geom=geom)
            <= vmem_budget(device_kind))


# ----------------------------------------------------------- dispatch ------
@partial(jax.jit, static_argnames=("pipe",))
def _fused(pipe, params, members, base, queries, delta_members, tombstone):
    """The fused jit: the compact pipeline as ONE jitted dispatch. ``pipe``
    arrives already mode="compact" (the mega pipeline's twin), so the
    jaxpr — and therefore every output bit on a deterministic backend — is
    identical to a plain jit of the compact search."""
    return pipe.search(params, members, base, queries, delta_members,
                       tombstone)


def mega_search(pipe, params, members, base, queries, delta_members=None,
                tombstone=None):
    """mode="mega" entry (called by QueryPipeline.search): one fused
    dispatch -> (ids [Q, k], scores [Q, k], n_candidates [Q])."""
    compact = dataclasses.replace(pipe, mode="compact")
    return _fused(compact, params, members, base, queries, delta_members,
                  tombstone)


# ------------------------------------------------------- static contracts --
# The tentpole's dispatch-count claim as a registered invariant: the traced
# mode="mega" search is EXACTLY ONE top-level dispatch with no [Q, L] count
# table and no fp32 [L, D] decode anywhere inside it. The control is the
# per-stage split pipeline (6 separate jitted stages — the pre-megakernel
# serve hot path), which MUST trip the dispatch counter.
from repro.analysis import contracts as _C  # noqa: E402


def _mega_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.mega_store_search()


def _split_control():
    from repro.analysis import fixtures as _FX
    return _FX.mega_split_control()


_C.register(_C.Contract(
    id="query.mega_single_dispatch",
    site="repro.kernels.mega_query.ops.mega_search",
    description="mode='mega' lowers to exactly one fused dispatch — no "
                "per-stage kernel round-trips — and inside it the compact "
                "guarantees hold: no [Q, L] count table, no fp32 [L, D] "
                "store decode. The control is the 6-dispatch staged split "
                "of the same search, which MUST trip the counter",
    fixture=_mega_fixture,
    checks=[
        _C.max_dispatches(1),
        _C.forbid_dims("Q", "L"),
        _C.require_dtype_free("float32", "L", "D"),
        _C.require_dims("Q", "C"),
    ],
    control=_split_control,
))
