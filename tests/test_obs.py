"""Observability substrate (repro.obs + benchmarks.trajectory): histogram
bucket math at the edges, snapshot merge associativity (hypothesis property
tests where available), Prometheus text exposition, Span/fence tracing, the
JSONL MetricsLogger, and the longitudinal perf-trajectory regression gate.
"""
import json
import math
import time

import numpy as np
import pytest

from repro import obs
from repro.obs.registry import (COUNT_BUCKETS, LATENCY_BUCKETS, Histogram,
                                bucket_index, load_balance_stats,
                                log_buckets, merge_snapshots)


# ------------------------------------------------------------ bucket math --
def test_log_buckets_shape():
    for lo, hi, pd in ((1e-6, 1e2, 3), (1.0, 1e6, 4), (0.5, 7.0, 1)):
        b = log_buckets(lo, hi, pd)
        assert b[0] == lo and b[-1] >= hi
        assert list(b) == sorted(set(b)), "bounds must be strictly ascending"
    with pytest.raises(ValueError, match="lo"):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError, match="per_decade"):
        log_buckets(1.0, 10.0, 0)


def test_bucket_index_edge_values():
    bounds = LATENCY_BUCKETS
    # a value exactly equal to a bound lands IN that bound's bucket (le
    # semantics) — the edge the regression in Prometheus parlance is 'le'
    for i, b in enumerate(bounds):
        assert bucket_index(bounds, b) == i
    assert bucket_index(bounds, 0.0) == 0                  # below first
    assert bucket_index(bounds, bounds[-1] * 2) == len(bounds)   # overflow
    assert bucket_index(bounds, math.inf) == len(bounds)


def test_histogram_counts_min_max():
    h = Histogram(bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 1.0000001, 99.0, 1e6):
        h.observe(v)
    s = h.snapshot()
    assert s["counts"] == [2, 1, 1, 1]          # [<=1, <=10, <=100, +Inf]
    assert s["count"] == 5 and sum(s["counts"]) == 5
    assert s["min"] == 0.5 and s["max"] == 1e6
    assert s["sum"] == pytest.approx(0.5 + 1.0 + 1.0000001 + 99.0 + 1e6)
    with pytest.raises(ValueError, match="ascending"):
        Histogram(bounds=(1.0, 1.0, 2.0))


def test_counter_and_gauge_semantics():
    reg = obs.MetricRegistry()
    c = reg.counter("x_total")
    c.inc(); c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match=">= 0"):
        c.inc(-1)
    assert reg.counter("x_total") is c          # get-or-create: same object
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x_total")
    g = reg.gauge("y")
    g.set(7); g.add(-2)
    assert g.value == 5.0
    # labels are part of identity
    a = reg.counter("z", {"stage": "a"})
    b = reg.counter("z", {"stage": "b"})
    assert a is not b
    a.inc()
    assert reg.counter("z", {"stage": "a"}).value == 1.0
    assert reg.counter("z", {"stage": "b"}).value == 0.0


def test_vector_counter_load_balance():
    reg = obs.MetricRegistry()
    v = reg.vector("probes", 4)
    v.inc_at([0, 0, 1, 2, 3])                   # repeats accumulate
    v.add([1, 0, 0, 0])
    np.testing.assert_array_equal(v.value, [3, 1, 1, 1])
    s = v.snapshot()
    assert s["sum"] == 6 and s["min"] == 1 and s["max"] == 3
    # KL: uniform -> 0; one-hot -> log(B)
    assert load_balance_stats([5, 5, 5, 5])["kl_vs_uniform"] == \
        pytest.approx(0.0)
    assert load_balance_stats([10, 0, 0, 0])["kl_vs_uniform"] == \
        pytest.approx(math.log(4))
    assert load_balance_stats([0, 0])["kl_vs_uniform"] == 0.0
    with pytest.raises(ValueError, match="shape"):
        v.add([1, 2])


# ----------------------------------------------------------------- merges --
def _sample_registry(seed):
    rng = np.random.default_rng(seed)
    reg = obs.MetricRegistry()
    reg.counter("req_total").inc(float(rng.integers(0, 100)))
    reg.gauge("epoch").set(float(rng.integers(0, 10)))
    h = reg.histogram("lat", bounds=(1.0, 10.0, 100.0))
    for v in rng.uniform(0, 200, size=rng.integers(1, 20)):
        h.observe(float(v))
    reg.vector("load", 8).add(rng.integers(0, 50, 8))
    return reg.snapshot()


def _assert_snap_equal(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        for field, va in a[k].items():
            vb = b[k][field]
            if isinstance(va, float):
                assert va == pytest.approx(vb), (k, field)
            else:
                assert va == vb, (k, field)


def test_merge_snapshots_associative_and_identity():
    s1, s2, s3 = (_sample_registry(i) for i in range(3))
    left = merge_snapshots(merge_snapshots(s1, s2), s3)
    right = merge_snapshots(s1, merge_snapshots(s2, s3))
    _assert_snap_equal(left, right)
    _assert_snap_equal(merge_snapshots({}, s1), s1)
    # gauges are last-write-wins: the right argument
    assert left["epoch"]["value"] == s3["epoch"]["value"]
    # counters and histogram counts add
    assert left["req_total"]["value"] == pytest.approx(
        s1["req_total"]["value"] + s2["req_total"]["value"]
        + s3["req_total"]["value"])
    assert left["lat"]["count"] == (s1["lat"]["count"] + s2["lat"]["count"]
                                    + s3["lat"]["count"])


def test_merge_rejects_incompatible():
    a = Histogram(bounds=(1.0, 2.0)).snapshot()
    b = Histogram(bounds=(1.0, 3.0)).snapshot()
    with pytest.raises(ValueError, match="bounds"):
        merge_snapshots({"h": a}, {"h": b})
    with pytest.raises(ValueError, match="cannot merge"):
        merge_snapshots({"m": {"type": "counter", "value": 1.0}},
                        {"m": {"type": "gauge", "value": 1.0}})


# --------------------------------------------------- hypothesis properties --
def test_bucket_index_property():
    pytest.importorskip("hypothesis")  # optional dev dep — skip, don't error
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(st.floats(min_value=0.0, max_value=1e8, allow_nan=False),
           st.sampled_from([LATENCY_BUCKETS, COUNT_BUCKETS,
                            (1.0, 2.0, 4.0)]))
    def prop(v, bounds):
        i = bucket_index(bounds, v)
        assert 0 <= i <= len(bounds)
        if i > 0:
            assert v > bounds[i - 1]      # strictly above every lower bound
        if i < len(bounds):
            assert v <= bounds[i]         # within its own upper bound

    prop()


def test_merge_associativity_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(st.integers(0, 1000), st.integers(0, 1000), st.integers(0, 1000))
    def prop(a, b, c):
        s1, s2, s3 = (_sample_registry(s) for s in (a, b, c))
        _assert_snap_equal(
            merge_snapshots(merge_snapshots(s1, s2), s3),
            merge_snapshots(s1, merge_snapshots(s2, s3)))

    prop()


# ------------------------------------------------------------- exposition --
def test_prometheus_text_exposition():
    reg = obs.MetricRegistry()
    reg.counter("req_total", {"stage": "gather"}).inc(3)
    reg.gauge("epoch").set(2)
    h = reg.histogram("lat", bounds=(1.0, 10.0))
    h.observe(0.5); h.observe(5.0); h.observe(50.0)
    reg.vector("load", 4).add([1, 2, 3, 4])
    text = reg.to_text()
    assert '# TYPE req_total counter' in text
    assert 'req_total{stage="gather"} 3' in text
    assert "epoch 2" in text
    # histogram buckets are CUMULATIVE and end at +Inf == _count
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="10"} 2' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_count 3" in text
    # vectors expose the load summary, not B raw series
    assert 'load{stat="kl_vs_uniform"}' in text
    assert text.endswith("\n")


def test_snapshot_is_jsonable():
    snap = _sample_registry(0)
    assert json.loads(json.dumps(snap)) == snap


# ----------------------------------------------------------- span / fence --
def test_trace_records_on_success_and_exception():
    reg = obs.MetricRegistry()
    with obs.trace(reg, "op_seconds", stage="x") as sp:
        assert sp.fence(41) == 41           # fence returns its argument
    with pytest.raises(RuntimeError):
        with obs.trace(reg, "op_seconds", stage="x"):
            raise RuntimeError("boom")
    h = reg.histogram("op_seconds", {"stage": "x"})
    assert h.count == 2                     # the failed span still recorded
    assert h.snapshot()["sum"] >= 0.0


def test_fence_blocks_jax_arrays():
    jnp = pytest.importorskip("jax.numpy")
    reg = obs.MetricRegistry()
    with obs.trace(reg, "op_seconds") as sp:
        out = sp.fence(jnp.arange(4) * 2)
    np.testing.assert_array_equal(np.asarray(out), [0, 2, 4, 6])


def _profiled_events(tmp_path, body) -> list:
    """Run ``body`` under the JAX profiler -> its host events as (name,
    {arg: value}) pairs, in the order they started."""
    import glob

    import jax
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        body()
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    events = [ev for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for line in p.lines
              for ev in line.events]
    return [(ev.name, {k: v for k, v in ev.stats})
            for ev in sorted(events, key=lambda ev: ev.start_ns)]


def test_span_without_registry_annotates_only(tmp_path):
    """A span given no registry opens a profiler annotation under its name
    and labels, and takes no histogram anywhere."""
    before = obs.DEFAULT_REGISTRY.names()

    def body():
        with obs.trace(None, "unit.span", batch_id=3, fill=5) as sp:
            time.sleep(0.001)
        assert sp.seconds >= 0.001

    events = _profiled_events(tmp_path, body)
    assert ("unit.span", {"batch_id": 3, "fill": 5}) in events
    assert obs.DEFAULT_REGISTRY.names() == before
    assert "unit.span" not in before


def test_span_annotates_and_records_on_exception(tmp_path):
    """A span that raises still closes its annotation and observes its
    histogram."""
    reg = obs.MetricRegistry()

    def body():
        with pytest.raises(RuntimeError):
            with obs.trace(reg, "op_seconds", stage="x"):
                raise RuntimeError("boom")

    events = _profiled_events(tmp_path, body)
    assert ("op_seconds", {"stage": "x"}) in events
    assert reg.histogram("op_seconds", {"stage": "x"}).count == 1


# ---------------------------------------------------------- MetricsLogger --
def test_metrics_logger_roundtrip(tmp_path):
    path = tmp_path / "m.jsonl"
    reg = obs.MetricRegistry()
    reg.counter("n").inc(2)
    with obs.MetricsLogger(str(path)) as log:
        log.log({"loss": np.float32(0.5), "round": 0}, step=0)
        log.log({"loss": 0.25, "round": 1}, step=1)
        log.log_snapshot(reg)
    rows = [json.loads(x) for x in path.read_text().splitlines()]
    assert len(rows) == 3
    assert rows[0]["loss"] == pytest.approx(0.5)    # np scalars serialized
    assert rows[1]["step"] == 1
    assert rows[2]["snapshot"]["n"]["value"] == 2.0


# -------------------------------------------------------------- trajectory --
def test_trajectory_record_load_check(tmp_path):
    from benchmarks import trajectory as tj
    path = str(tmp_path / "TRAJECTORY.jsonl")
    rows = [("a/lat", 100.0, "recall=0.9"), ("a/qps", 0.0, 123.4)]
    written = tj.record("a", rows, path=path)
    assert [w["name"] for w in written] == ["a/lat", "a/qps"]
    assert all(w["git_rev"] and w["unit"] == "us_per_call" for w in written)
    # same value again: within 20% -> no failures
    tj.record("a", [("a/lat", 105.0, "")], path=path)
    assert tj.check(path) == []
    # >20% regression vs the median of priors -> flagged + enforce exits 1
    tj.record("a", [("a/lat", 200.0, "")], path=path)
    fails = tj.check(path)
    assert len(fails) == 1 and "a/lat" in fails[0]
    with pytest.raises(SystemExit):
        tj.enforce(path)
    # an IMPROVEMENT is never a failure
    tj.record("a", [("a/lat", 50.0, "")], path=path)
    assert tj.check(path) == []
    # zero-valued (qps-style) and single-recording metrics never gate
    assert all("a/qps" not in f for f in tj.check(path))


def test_trajectory_registry_mirror_and_bad_lines(tmp_path):
    from benchmarks import trajectory as tj
    path = str(tmp_path / "t.jsonl")
    reg = obs.MetricRegistry()
    tj.record("b", [("b/x", 10.0, None)], path=path, registry=reg)
    assert reg.gauge("bench_value", {"bench": "b", "name": "b/x"}).value \
        == 10.0
    with open(path, "a") as f:
        f.write("not json at all\n{\"half\": 1\n")
    assert [r["name"] for r in tj.load(path)] == ["b/x"]


# -------------------------------------------- decay / windowing / quantile --
def test_vector_counter_decay_and_reset():
    reg = obs.MetricRegistry()
    v = reg.vector("probes", 4)
    v.inc_at(np.array([0, 0, 1, 3]))
    v.decay(0.5)
    np.testing.assert_allclose(v.value, [1.0, 0.5, 0.0, 0.5])
    with pytest.raises(ValueError, match="factor"):
        v.decay(1.5)
    with pytest.raises(ValueError, match="factor"):
        v.decay(-0.1)
    window = v.reset()
    np.testing.assert_allclose(window, [1.0, 0.5, 0.0, 0.5])
    np.testing.assert_allclose(v.value, np.zeros(4))


def test_vector_counter_merge_decay_commute():
    """Property (satellite spec): merge-then-decay == decay-then-merge.
    Holds exactly because decay is a linear map and merge is addition —
    float64 counts make factor=0.5 on integer counts exact."""
    rng = np.random.default_rng(0)
    for factor in (0.0, 0.25, 0.5, 1.0):
        ra, rb = obs.MetricRegistry(), obs.MetricRegistry()
        a, b = ra.vector("v", 16), rb.vector("v", 16)
        a.inc_at(rng.integers(0, 16, 100))
        b.inc_at(rng.integers(0, 16, 100))
        # merge-then-decay (merge_snapshots adds vector counts)
        merged = merge_snapshots(ra.snapshot(), rb.snapshot())
        md = np.asarray(merged["v"]["counts"]) * factor
        # decay-then-merge
        a.decay(factor); b.decay(factor)
        dm = merge_snapshots(ra.snapshot(), rb.snapshot())
        np.testing.assert_allclose(md, np.asarray(dm["v"]["counts"]))


def test_histogram_quantile():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    assert math.isnan(h.quantile(0.5))          # empty
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):
        h.observe(v)
    # median falls in the (1, 2] bucket; overflow reports the true max
    assert 1.0 <= h.quantile(0.5) <= 2.0
    assert h.quantile(1.0) == 100.0
    assert h.quantile(0.0) <= 1.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    # p99 of a tight latency-like stream stays inside the right bucket
    h2 = Histogram(bounds=LATENCY_BUCKETS)
    h2.observe_many(np.full(1000, 3e-3))
    q = h2.quantile(0.99)
    lo = max(b for b in LATENCY_BUCKETS if b < 3e-3)
    hi = min(b for b in LATENCY_BUCKETS if b >= 3e-3)
    assert lo < q <= hi


def test_query_log_sampling_and_drain():
    reg = obs.MetricRegistry()
    qlog = obs.QueryLog(capacity=8, sample=1.0, registry=reg)
    assert len(qlog) == 0
    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    ids = np.arange(18, dtype=np.int32).reshape(6, 3)
    assert qlog.record(x, ids) == 6
    assert len(qlog) == 6
    # ring wraps: 6 more rows overwrite the 4 oldest
    qlog.record(x + 100, ids + 100)
    assert len(qlog) == 8
    gx, gids = qlog.drain()
    assert gx.shape == (8, 2) and gids.shape == (8, 3)
    assert len(qlog) == 0 and qlog.drain()[0].shape == (0, 2)
    # shape drift is an error, not silent corruption
    qlog.record(x, ids)
    with pytest.raises(ValueError):
        qlog.record(np.zeros((2, 5), np.float32), ids[:2])
    # sample=0 keeps nothing but still counts traffic
    q2 = obs.QueryLog(capacity=8, sample=0.0, registry=obs.MetricRegistry())
    assert q2.record(x, ids) == 0 and len(q2) == 0


def test_trajectory_quality_units_gate_inverted(tmp_path):
    """Satellite spec: recall/frac rows are larger-is-better — the gate
    flags a DROP below median/factor, never a rise; latency rows in the
    same history keep the original larger-is-worse direction."""
    from benchmarks import trajectory as tj
    path = str(tmp_path / "T.jsonl")
    tj.record("q", [("q/recall", 0.80, "")], unit="recall", path=path)
    tj.record("q", [("q/recall", 0.78, "")], unit="recall", path=path)
    assert tj.check(path) == []                   # within the 1/1.2 band
    tj.record("q", [("q/recall", 0.95, "")], unit="recall", path=path)
    assert tj.check(path) == []                   # improvement never fails
    tj.record("q", [("q/recall", 0.50, "")], unit="recall", path=path)
    fails = tj.check(path)
    assert len(fails) == 1 and "q/recall" in fails[0]
    assert "larger-is-better" in fails[0]
    with pytest.raises(SystemExit):
        tj.enforce(path)
    # recovering clears the gate (newest vs median of priors)
    tj.record("q", [("q/recall", 0.81, "")], unit="recall", path=path)
    assert tj.check(path) == []
    # zero is a legal (terrible) recall and still gates — unlike the
    # zero-qps exemption on latency units
    tj.record("q", [("q/recall", 0.0, "")], unit="recall", path=path)
    assert any("q/recall" in f for f in tj.check(path))
    # mixed-direction history: a latency regression in the same file is
    # still caught with the original direction
    tj.record("q", [("q/lat", 100.0, "")], path=path)
    tj.record("q", [("q/lat", 100.0, "")], path=path)
    tj.record("q", [("q/lat", 200.0, "")], path=path)
    assert any("q/lat" in f for f in tj.check(path))
    # frac shares the quality direction
    p2 = str(tmp_path / "T2.jsonl")
    tj.record("q", [("q/hit", 0.9, "")], unit="frac", path=p2)
    tj.record("q", [("q/hit", 0.5, "")], unit="frac", path=p2)
    assert len(tj.check(p2)) == 1


def test_exposition_derived_quantiles_match_le_buckets():
    """Satellite spec: to_text() carries derived p50/p95/p99 summary lines
    that agree with Histogram.quantile's le-bucket interpolation."""
    import re
    reg = obs.MetricRegistry()
    h = reg.histogram("lat", bounds=(1.0, 2.0, 4.0))
    assert "quantile=" not in reg.to_text()       # empty -> no quantiles
    h.observe_many([0.5, 1.5, 1.5, 3.0])
    text = reg.to_text()
    vals = {}
    for q in ("0.5", "0.95", "0.99"):
        m = re.search(r'lat\{quantile="%s"\} ([0-9.eE+-]+)' % q, text)
        assert m, f"quantile {q} line missing:\n{text}"
        vals[q] = float(m.group(1))
    # exported values are exactly the histogram's own quantile estimates,
    # each inside the le-bucket that contains that rank
    assert vals["0.5"] == pytest.approx(h.quantile(0.5))
    assert 1.0 <= vals["0.5"] <= 2.0              # median rank in (1, 2]
    assert 2.0 <= vals["0.95"] <= 4.0             # p95 rank in (2, 4]
    assert vals["0.5"] <= vals["0.95"] <= vals["0.99"]   # monotone in q
    # the le-bucket lines themselves stay cumulative and end at +Inf
    buckets = re.findall(r'lat_bucket\{le="([^"]+)"\} (\d+)', text)
    counts = [int(c) for _, c in buckets]
    assert counts == sorted(counts) and counts[-1] == 4
    assert buckets[-1][0] == "+Inf"


def test_query_log_sampling_uniform_and_fields_survive():
    """Satellite spec: the keep decision is per-row Bernoulli(sample) —
    independent of stream position — and (epoch, latency) survive drain
    and DrainedLog.merge alongside (x, ids)."""
    # position-uniformity: stream 4000 rows (position encoded in x[:, 0])
    # at sample=0.25 into a ring big enough to never overwrite, then check
    # retention per 500-row segment is flat
    qlog = obs.QueryLog(capacity=4000, sample=0.25, seed=7)
    for s in range(0, 4000, 100):
        x = np.zeros((100, 2), np.float32)
        x[:, 0] = np.arange(s, s + 100)
        qlog.record(x, np.zeros((100, 3), np.int32))
    w = qlog.drain()
    pos = w.x[:, 0].astype(int)
    per_seg = np.bincount(pos // 500, minlength=8)
    # E[seg] = 125, sigma ~ 9.7; +-5 sigma keeps this deterministic-seed
    # test far from flaky while catching any early/late bias
    assert np.all(per_seg > 75) and np.all(per_seg < 175), per_seg
    assert abs(len(w) - 1000) < 150
    # with sample=1 the ring is a recency window: the LAST capacity rows
    # survive an overflowing stream
    q2 = obs.QueryLog(capacity=16, sample=1.0)
    x = np.arange(40, dtype=np.float32).reshape(40, 1)
    q2.record(x, np.zeros((40, 1), np.int32))
    assert sorted(q2.drain().x[:, 0].astype(int)) == list(range(24, 40))

    # epoch + latency ride along through drain ...
    q3 = obs.QueryLog(capacity=32)
    x3 = np.ones((3, 2), np.float32)
    ids3 = np.zeros((3, 4), np.int32)
    q3.record(x3, ids3, epoch=5, latencies=0.25)
    q3.record(2 * x3, ids3 + 1, epoch=6, latencies=[0.1, 0.2, 0.3])
    a = q3.drain()
    assert a.epoch.tolist() == [5, 5, 5, 6, 6, 6]
    np.testing.assert_allclose(a.latency,
                               [0.25, 0.25, 0.25, 0.1, 0.2, 0.3], rtol=1e-6)
    gx, gids = a                                  # legacy 2-tuple unpack
    assert gx.shape == (6, 2) and a[1].shape == (6, 4)
    # ... and through merge (self rows first, all four fields aligned)
    q3.record(3 * x3, ids3 + 2, epoch=7)          # latency unmeasured -> nan
    b = q3.drain()
    m = a.merge(b)
    assert len(m) == 9
    assert m.epoch.tolist() == [5, 5, 5, 6, 6, 6, 7, 7, 7]
    assert np.isnan(m.latency[-3:]).all()
    np.testing.assert_allclose(m.latency[:6], a.latency, rtol=1e-6)
    # empty windows are identity elements
    empty = q3.drain()
    assert m.merge(empty) is m and empty.merge(m) is m
    # d/k mismatch refuses instead of silently mangling
    q4 = obs.QueryLog(capacity=4)
    q4.record(np.zeros((1, 3), np.float32), np.zeros((1, 4), np.int32))
    with pytest.raises(ValueError, match="merge"):
        m.merge(q4.drain())


def test_vector_counter_concurrent_decay_reset_snapshot():
    """Satellite spec: decay/reset racing snapshot/merge_snapshots never
    tears — every observed count vector is finite, non-negative, and
    mergeable, and reset windows + the final state account for every
    increment exactly (reset is an atomic read+clear)."""
    import threading
    reg = obs.MetricRegistry()
    v = reg.vector("probes", 32)
    stop = threading.Event()
    errs, windows = [], []
    N_PER_CALL, writes = 64, [0, 0]

    def writer(slot):
        rng = np.random.default_rng(1 + slot)
        while not stop.is_set():
            v.inc_at(rng.integers(0, 32, N_PER_CALL))
            writes[slot] += 1

    def cycler():
        while not stop.is_set():
            v.decay(1.0)                          # identity decay: racy
            windows.append(v.reset())             # path, conserved totals

    def reader():
        prev = None
        while not stop.is_set():
            try:
                snap = reg.snapshot()
                c = np.asarray(snap["probes"]["counts"])
                assert c.shape == (32,)
                assert np.all(np.isfinite(c)) and np.all(c >= 0)
                if prev is not None:
                    m = merge_snapshots(prev, snap)
                    assert m["probes"]["sum"] >= 0
                prev = snap
            except Exception as e:                # pragma: no cover
                errs.append(e)
                return
    threads = ([threading.Thread(target=writer, args=(i,)) for i in range(2)]
               + [threading.Thread(target=cycler),
                  threading.Thread(target=reader)])
    for t in threads:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not errs, errs
    # conservation: with factor=1.0 decay, every increment lands in
    # exactly one reset window or the final counts
    total = sum(float(w.sum()) for w in windows) + float(v.value.sum())
    assert total == sum(writes) * N_PER_CALL
