"""The program-span reduction on a small trace recorded on a TPU v5e
(``record_program_trace.py``): an int8 shard served through an
``IRLIServer``, three rounds of 40 queries, each followed by a 20 ms pause
on an empty queue, inside a ``bench.window`` span; and on
a small trace built by hand, whose answer is known exactly."""
import json
import os
import subprocess
import sys

import pytest

import program_trace
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "program_trace_small.xplane.pb")
PHASES = ("serve.pad", "serve.probe_stats", "serve.search", "serve.sync",
          "serve.results")


@pytest.fixture(scope="module")
def recording():
    with open(os.path.join(HERE, "program_trace_small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced():
    return program_trace.reduce(TRACE)


def test_matches_the_recording(reduced, recording):
    assert reduced == recording["reduced"]


def test_scopes_cover_the_busy_time(reduced):
    """Every stage of the served int8 compact path and the probe pass has
    device time; the stages and ``unscoped`` add up to the busy time that
    ``trace_reduce`` finds, and each kernel's custom-call lies in the
    stage of its name."""
    scope_s = reduced["scope_s"]
    assert {"scorer_logits", "top_m", "gather", "freq_topc", "quant_rerank",
            "refine", "probe_stats"} <= {k for k, v in scope_s.items()
                                          if v > 0}
    busy = trace_reduce.reduce(TRACE)["busy_s"]
    assert sum(scope_s.values()) == pytest.approx(busy, rel=1e-3)
    assert reduced["busy_s"] == pytest.approx(busy, rel=1e-3)
    ops = reduced["scope_op_s"]
    assert ops["freq_topc"]["freq_topc"] > 0
    assert ops["quant_rerank"]["quant_rerank"] > 0
    assert not any(k in ops.get("unscoped", {})
                   for k in ("freq_topc", "quant_rerank", "sort"))
    # both top-m sorts, the search's and the probe pass's, are scoped
    assert ops["top_m"]["sort"] > 0 and ops["probe_stats"]["sort"] > 0
    assert scope_s["unscoped"] < 0.05 * reduced["busy_s"]


def test_batches_agree_with_the_server(reduced, recording):
    """One ``serve.batch`` span per served batch, whose fills and pad rows
    agree with the server's counters over the window."""
    batches = reduced["batches"]
    server = recording["server"]
    assert len(batches) == server["batches"]
    assert sum(b["fill"] for b in batches) == server["requests"] \
        == recording["fill_sum"]
    assert sum(b["bucket"] - b["fill"] for b in batches) \
        == server["pad_waste"]
    assert sorted(b["batch_id"] for b in batches) \
        == sorted({b["batch_id"] for b in batches})
    assert all(0 <= b["start_s"] < reduced["window_s"] for b in batches)


def test_idle_by_span(reduced):
    """The idle time, split by span, adds up to the window less the busy
    time; the pauses, in which the server's queue is empty, fall in
    ``serve.wait``, and the rest in the server's phases."""
    idle = reduced["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(reduced["idle_s"], rel=1e-6)
    assert reduced["idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    # the batcher is still in its last wait when the profiler stops, and a
    # span still open then is not written: the last pause reads "no span"
    assert 2 * 0.02 <= idle["serve.wait"] < 2 * 0.05
    assert 0.02 <= idle[program_trace.NO_SPAN] < 0.05
    assert set(idle) <= {program_trace.NO_SPAN, "serve.wait",
                         "serve.collect", "serve.batch", *PHASES}
    assert sum(v for k, v in idle.items() if k in PHASES) > 0


def test_stage_reads_the_outermost_scope():
    assert program_trace.stage("jit(run)/top_m/top_k:") == "top_m"
    assert program_trace.stage(
        "jit(run)/quant_rerank/jit(quant_rerank)/gather") == "quant_rerank"
    # the op's own name is not a scope, though ``gather`` is also a stage
    assert program_trace.stage("jit(run)/jit(quant_rerank)/gather") \
        == program_trace.UNSCOPED
    assert program_trace.stage("m_") == program_trace.UNSCOPED
    # the probe pass compiled without its scope (a cached executable)
    assert program_trace.stage("jit(probe_buckets)/top_k:") == "probe_stats"
    assert program_trace.stage(
        "jit(probe_buckets)/probe_stats/top_k:") == "probe_stats"


def _write_space(path):
    """A two-batch trace built by hand: times in µs from the window's start
    at 1000 µs, so every expected number below is exact."""
    import xplane_pb2
    space = xplane_pb2.XSpace()
    host = space.planes.add(name="/host:CPU")
    names = ["bench.window", "serve.collect", "serve.batch", "serve.sync",
             "other"]
    for i, n in enumerate(names, 1):
        host.event_metadata[i].name = n
    for i, n in enumerate(["batch_id", "fill", "bucket"], 1):
        host.stat_metadata[i].name = n
    main, batcher = host.lines.add(), host.lines.add()

    def event(line, md, start_us, end_us, **args):
        ev = line.events.add(metadata_id=md, offset_ps=start_us * 10**6,
                             duration_ps=(end_us - start_us) * 10**6)
        for i, n in enumerate(["batch_id", "fill", "bucket"], 1):
            if n in args:
                ev.stats.add(metadata_id=i, int64_value=args[n])

    event(main, 1, 1000, 2000)                    # the window
    event(main, 5, 1000, 2000)                    # not a server span
    # batch 0: collect 1000-1100, batch 1100-1500 (sync 1300-1500)
    event(batcher, 2, 1000, 1100, batch_id=0)
    event(batcher, 3, 1100, 1500, batch_id=0, fill=3, bucket=4)
    event(batcher, 4, 1300, 1500, batch_id=0)
    # batch 1 starts inside the window, runs past its end
    event(batcher, 2, 1500, 1600, batch_id=1)
    event(batcher, 3, 1650, 2100, batch_id=1, fill=6, bucket=8)
    event(batcher, 3, 900, 950, batch_id=9, fill=1, bucket=1)  # before
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    ops = [("%sort.3 = f32[4,8]{1,0} sort(...)", "jit(run)/top_m/sort:"),
           ("%sort.7 = f32[4,8]{1,0} sort(...)",
            "jit(probe_buckets)/probe_stats/sort:"),
           ("%fusion.1 = f32[4]{0} fusion(...)", "jit(run)/reduce_sum:"),
           ("%copy.2 = s32[4]{0} copy(...)", "")]
    for i, (name, tf_op) in enumerate(ops, 1):
        md = dev.event_metadata[i]
        md.name = name
        if tf_op:
            md.stats.add(metadata_id=1, str_value=tf_op)
    xla = dev.lines.add(name="XLA Ops")
    for md, s, e in [(4, 950, 1050),               # half before the window
                     (2, 1150, 1250), (1, 1250, 1300), (3, 1400, 1450),
                     (1, 1700, 1900)]:
        xla.events.add(metadata_id=md, offset_ps=s * 10**6,
                       duration_ps=(e - s) * 10**6)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_reduce_on_a_built_trace(tmp_path):
    """Scopes, batches and idle attribution on a trace whose answer is
    known to the microsecond: clipping to the window, the op's own name at
    the end of its path, the innermost span, and ``no span``."""
    path = str(tmp_path / "t.xplane.pb")
    _write_space(path)
    red = program_trace.reduce(path)
    us = pytest.approx
    assert red["window_s"] == us(1000e-6)
    assert red["busy_s"] == us(450e-6)
    assert red["idle_s"] == us(550e-6)
    assert red["scope_s"] == {k: us(v * 1e-6) for k, v in {
        "unscoped": 100, "top_m": 250, "probe_stats": 100}.items()}
    assert red["scope_op_s"] == {
        "top_m": {"sort": us(250e-6)}, "probe_stats": {"sort": us(100e-6)},
        "unscoped": {"copy": us(50e-6), "fusion": us(50e-6)}}
    assert [(b["batch_id"], b["fill"], b["bucket"]) for b in red["batches"]] \
        == [(0, 3, 4), (1, 6, 8)]
    assert red["batches"][0]["start_s"] == us(100e-6)
    assert red["batches"][1]["dur_s"] == us(450e-6)
    # idle: 1050-1150 collect/batch, 1300-1400 and 1450-1500 sync,
    # 1500-1600 collect, 1600-1650 none, 1650-1700 and 1900-2000 batch
    assert red["idle_by_span"] == {k: us(v * 1e-6) for k, v in {
        "serve.collect": 150, "serve.batch": 200, "serve.sync": 150,
        program_trace.NO_SPAN: 50}.items()}


def test_no_tensorflow():
    """Both reductions read the trace without importing tensorflow."""
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "import program_trace, trace_reduce; "
            "program_trace.reduce(sys.argv[2]); trace_reduce.reduce(sys.argv[2]); "
            "print(sorted({m.split('.')[0] for m in sys.modules}))")
    out = subprocess.run([sys.executable, "-c", code,
                          os.path.dirname(HERE), TRACE], capture_output=True,
                         text=True, timeout=120, check=True).stdout
    assert "'jax'" in out and "'tensorflow'" not in out


def test_metric_readers(reduced, recording, monkeypatch):
    """The four per-layer readers, fed this trace as the run's newest."""
    import harness as H
    monkeypatch.setattr(program_trace, "newest", lambda: TRACE)
    rec = {"trace": {"busy_s": reduced["busy_s"]}}
    n = len(reduced["batches"])
    got = {name: H.metric_reader(name).read(rec) for name in (
        "server.probe_stats_ms.saturated", "search.top_m_ms.saturated",
        "search.rerank_gather_ms.saturated", "server.pad_share.saturated")}
    assert got["server.probe_stats_ms.saturated"] == pytest.approx(
        1e3 * reduced["scope_s"]["probe_stats"] / n)
    assert got["search.top_m_ms.saturated"] == pytest.approx(
        1e3 * reduced["scope_s"]["top_m"] / n)
    qr = reduced["scope_op_s"]["quant_rerank"]
    assert got["search.rerank_gather_ms.saturated"] == pytest.approx(
        1e3 * (sum(qr.values()) - qr["quant_rerank"]) / n)
    server = recording["server"]
    assert got["server.pad_share.saturated"] == pytest.approx(
        100 * server["pad_waste"] / (server["requests"]
                                     + server["pad_waste"]))
    assert all(v > 0 for v in got.values())
    # an untraced run reads nothing
    assert H.metric_reader("search.top_m_ms.saturated").read(
        {"trace": None}) is None
