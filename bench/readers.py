"""What the per-layer metric readers (``metrics/<name>.py``) share. A reader
gets the run's record and returns a number, or None where the run holds
nothing for it to read; a share of a roofline or a peak is never 0 for
want of a reading."""
from __future__ import annotations

import numpy as np

import work


def client_quantile(rec: dict, key: str, q: float):
    """q-th percentile of the client's per-request times (ms)."""
    vals = np.asarray(rec[key], np.float64)
    if vals.size == 0:
        return None
    return float(np.percentile(vals, q))


def server_mean(rec: dict, name: str, scale: float = 1.0):
    """Mean of one of the server's histograms over the window."""
    total, count = rec["server"][name]
    return scale * total / count if count else None


def rows(rec: dict) -> int:
    """Query rows the device searched: requests and the server's pad rows."""
    s = rec["server"]
    return s["serve_requests_total"] + s["serve_pad_waste_total"]


def keep(cfg: dict) -> int:
    """Candidates the coarse rerank keeps for the refine (``refine_k``, or
    the program's default max(4k, 32))."""
    return cfg["refine_k"] or max(4 * cfg["k"], 32)


def kernel_roofline(rec: dict, kernel: str, ops: float, nbytes: float):
    """Share (%) of its roofline that the device operations named
    ``kernel`` reached over the traced window: ``ops`` and ``nbytes`` (the
    work of the kernel's task, counted by ``work.py``) over their device
    time. None where the trace holds no such operation."""
    tr = rec.get("trace")
    if not tr or not tr["op_s"].get(kernel):
        return None
    return work.roofline_share(ops, nbytes, tr["op_s"][kernel], rec["peak"])


def serve_mfu(rec: dict):
    """Useful scorer and rerank operations of the answered requests over the
    traced window, as a share (%) of the chip's bf16 peak."""
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    cfg = rec["cfg"]
    per_q = work.serve_flops(cfg["reps"], cfg["d"], cfg["hidden"],
                             cfg["buckets"], cfg["topC"], keep(cfg))
    done = rec["server"]["serve_requests_total"]
    if not done:
        return None
    return 100.0 * done * per_q / (tr["window_s"]
                                   * rec["peak"]["bf16_flop_per_s"])


def device_idle(rec: dict):
    """Share (%) of the traced window in which no operation ran on the
    device."""
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
