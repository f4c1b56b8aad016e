"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``): three searches of an int8 shard, with 20 ms host
pauses between them, inside a ``bench.window`` span."""
import json
import os

import numpy as np
import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "trace_small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_matches_the_recording(reduced):
    with open(os.path.join(HERE, "trace_small.json")) as f:
        want = json.load(f)["reduced"]
    assert reduced == want


def test_busy_is_the_union_of_device_ops(reduced):
    """Busy time recomputed independently, on a 1 us timeline."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TRACE)
    host = [ev for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name == trace_reduce.WINDOW]
    lo = host[0].start_ns
    hi = lo + host[0].duration_ns
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    t = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for line in dev.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            s = max(ev.start_ns, lo)
            e = min(ev.start_ns + ev.duration_ns, hi)
            if e > s:
                t[int((s - lo) // 1000):int(np.ceil((e - lo) / 1000))] = True
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert reduced["busy_s"] == pytest.approx(t.sum() * 1e-6, rel=0.05,
                                              abs=5e-5)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_kernels_and_gaps(reduced):
    ks = reduced["op_s"]
    assert ks["freq_topc"] > 0 and ks["quant_rerank"] > 0
    # every instruction that ran is timed, and together they fill the busy
    # time (ops of one device do not overlap)
    assert {"sort", "fusion"} <= set(ks)
    assert sum(ks.values()) == pytest.approx(reduced["busy_s"], rel=1e-6)
    # the two pauses between searches are the longest idle gaps, and the
    # host was in them
    gaps = reduced["idle_gaps"]
    assert [g[0] for g in gaps[:2]] == ["host.pause", "host.pause"]
    assert all(0.015 < g[1] < 0.1 for g in gaps[:2])
    assert reduced["collective_s"] == 0
    names = [op for op, _ in reduced["device_ops"]]
    assert any("freq_topc" in n for n in names)
