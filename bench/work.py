"""Operations and bytes that each measured piece of work needs, counted from
shapes, and the peaks they are held against.

A kernel's work is the work of its task, not of its implementation, so a
change of algorithm keeps the same yardstick:

  * frequency top-C reads every gathered candidate id (int32) of a query and
    writes the C most frequent (id, count) pairs;
  * the coarse int8 rerank reads each query and, for each of its C
    candidates, the candidate's id and count, its int8 code row and its
    per-block fp32 scales (gathered for it beforehand), does 2·D operations
    per candidate, and writes k' (id, score) pairs.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind`` (``peaks.json``). A kind
    that is not in the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def freq_topc(rows: int, width: int, topc: int) -> tuple[float, float]:
    """(operations, bytes) of frequency top-C over ``rows`` queries of
    ``width`` candidate ids each. Counting moves no arithmetic worth a
    roofline: the operations are 0 and the task is bound by its bytes."""
    return 0.0, float(rows) * (width * 4 + topc * 8)


def quant_rerank(rows: int, topc: int, dim: int, block: int,
                 keep: int) -> tuple[float, float]:
    """(operations, bytes) of the coarse int8 rerank: ``topc`` candidates per
    query of ``dim`` int8 codes with dim/block fp32 scales, top ``keep``
    kept."""
    per_cand = dim + (dim // block) * 4 + 4 + 4
    return (2.0 * rows * topc * dim,
            float(rows) * (dim * 4 + topc * per_cand + keep * 8))


def scorer_flops(reps: int, dim: int, hidden: int, buckets: int) -> float:
    """Operations of the R stacked scorer nets for one query."""
    return 2.0 * reps * (dim * hidden + hidden * buckets)


def serve_flops(reps: int, dim: int, hidden: int, buckets: int, topc: int,
                keep: int) -> float:
    """Useful operations of one served query: the scorer, the coarse dots
    over its ``topc`` candidates and the refine dots over ``keep``."""
    return scorer_flops(reps, dim, hidden, buckets) + 2.0 * dim * (topc + keep)


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> float | None:
    """Least time the chip could take for the work over the time it took,
    as a percentage; None where nothing was timed."""
    if seconds <= 0:
        return None
    least = max(ops / peak["bf16_flop_per_s"], nbytes / peak["hbm_byte_per_s"])
    if least <= 0:
        return None
    return 100.0 * least / seconds
