"""The sharded cell (``kinds/search_sharded.py``) on the CPU with four
virtual devices, in a subprocess (JAX fixes its device count when it
starts): a tiny ``deep1b-4shard`` run is correct against the sharded
reference, and each fault of the merge across shards turns ``correct``
false. Also the per-chip trace reduction (``mesh_trace.py``) and the
cell's readers, on a trace built by hand."""
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

import harness as H
import mesh_trace
import program_trace

#: every width cut, every rule kept (member width 2 x floor(L / B))
TINY = dict(n_corpus=8192, d=32, hidden=64, buckets=256, reps=4,
            member_width=64, topC=128, affinity_chunk=512, sample=64)
TRAFFIC = {"batch": 64, "in_flight": 2, "pool_batches": 4}

_SCRIPT = textwrap.dedent("""
    import os
    import sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import contextlib
    import json
    bench, tiny, traffic = sys.argv[1], json.loads(sys.argv[2]), \\
        json.loads(sys.argv[3])
    sys.path[:0] = [bench, os.path.join(os.path.dirname(bench), "src")]
    import jax
    import jax.numpy as jnp
    import harness as H
    import run

    @contextlib.contextmanager
    def patched(obj, name, new):
        orig = getattr(obj, name)
        setattr(obj, name, new(orig))
        try:
            yield
        finally:
            setattr(obj, name, orig)

    def no_offset():
        # every shard's ids left local: the offset to global ids is lost
        return patched(jax.lax, "axis_index", lambda orig: lambda axes: 0)

    def shard_dropped():
        # the last shard's answers never win the merge
        import repro.core.distributed as D

        def new(orig):
            def merge(ids, scores, n_cand, k, axes):
                last = jax.lax.axis_index(axes) == 3
                return orig(ids, jnp.where(last, -jnp.inf, scores), n_cand,
                            k, axes)
            return merge
        return patched(D, "_merge_across_shards", new)

    cf = dict(H.cell(H.spec(), "shard4-offline")[1], **tiny)
    out = {}
    for name, breakage in [("sound", None), ("no_offset", no_offset),
                           ("shard_dropped", shard_dropped)]:
        r = run.run_cell("shard4-offline", 2**31 + 7, 1.0, False,
                         require_tpu=False, conf_file=cf, traffic=traffic,
                         breakage=breakage)
        out[name] = {"correct": r["correct"], "checks": r["checks"],
                     "metrics": sorted(r["metrics"]),
                     "attempted": r["attempted"], "failed": r["failed"],
                     "count": r["device"]["count"]}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", _SCRIPT, H.BENCH,
                        json.dumps(TINY), json.dumps(TRAFFIC)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    # each run logs what its window compiled and traced, in run order
    windows = re.findall(r"(\d+) compiles and (\d+) traces in the window",
                         p.stderr)
    assert len(windows) == len(out), p.stderr[-3000:]
    for r, (compiles, traces) in zip(out.values(), windows):
        r["window"] = [int(compiles), int(traces)]
    return out


def test_sound_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert r["count"] == 4
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == ["serve_qps", "setup_s"]
    assert r["window"] == [0, 0]


@pytest.mark.parametrize("fault", ["no_offset", "shard_dropped"])
def test_fault_turns_correct_false(runs, fault):
    r = runs[fault]
    assert not r["correct"], r["checks"]
    assert r["checks"]["id_miss"]["value"] > r["checks"]["id_miss"]["limit"]


def test_cell_metrics_follow_the_spec():
    spec = H.spec()
    e2e = {m["name"] for m in H.cell_metrics(spec, "shard4-offline", False)}
    assert e2e == {"setup_s", "serve_qps"}
    per = {m["name"] for m in H.cell_metrics(spec, "shard4-offline", True)}
    assert per == {"merge.collective_share.x4", "shard.busy_spread.x4",
                   "device.idle.x4", "serve_mfu.x4"}
    assert all(os.path.exists(os.path.join(H.BENCH, "metrics", f"{m}.py"))
               for m in per)


def test_reference_merges_by_score_then_lower_id():
    """The sharded reference's merge on two stub shards: a tie across
    shards goes to the lower global id, and -1 pads sort last."""
    import numpy as np
    mod = H.load_module(os.path.join(H.BENCH, "configs",
                                     "irli_reference_sharded.py"))

    class Stub:
        def __init__(self, ids, scores):
            self.out = (np.array(ids), np.array(scores, np.float64))
            self.codes = np.zeros((4, 1))
            self.scales = np.zeros((4, 1))

        def search(self, queries, precision):
            return self.out

    ref = mod.ShardedReference.__new__(mod.ShardedReference)
    ref.cfg, ref.rows = {"k": 3}, 4
    ref.shards = [Stub([[2, 1, -1]], [[0.5, 0.25, -np.inf]]),
                  Stub([[3, 0, 1]], [[0.5, 0.75, 0.1]])]
    ids, scores = ref.search(np.zeros((1, 1)))
    assert ids.tolist() == [[4, 2, 7]]
    assert scores.tolist() == [[0.75, 0.5, 0.5]]


def _write_space(path):
    """Two chips, times in µs from the window's start at 1000 µs: chip 0
    busy 400 µs (merge all-gather 100, all-reduce 50), chip 1 busy 200 µs
    (merge all-gather 20)."""
    import xplane_pb2
    space = xplane_pb2.XSpace()
    host = space.planes.add(name="/host:CPU")
    for i, n in enumerate(["bench.window", "sharded.batch", "sharded.read"],
                          1):
        host.event_metadata[i].name = n
    line = host.lines.add()
    for md, s, e in [(1, 1000, 2000), (2, 1000, 1050), (3, 1050, 1500),
                     (2, 1500, 1550)]:
        line.events.add(metadata_id=md, offset_ps=s * 10**6,
                        duration_ps=(e - s) * 10**6)
    ops = [("%fusion.1 = f32[4]{0} fusion(...)",
            "jit(search)/shard_map/scorer_logits/dot:"),
           ("%all-gather-start.2 = f32[4,2,3]{2,1,0} all-gather-start(...)",
            "jit(search)/shard_map/merge/all_gather:"),
           # a psum keeps its own name; its opcode is the all-reduce
           ("%psum.3 = s32[4]{0:T(1024)} all-reduce(s32[4]{0} %copy-done.5)",
            "jit(search)/shard_map/merge/psum:"),
           ("%sort.4 = f32[4,6]{1,0} sort(...)",
            "jit(search)/shard_map/merge/top_k:")]
    for dev, events in [
            (0, [(1, 1100, 1300), (2, 1300, 1400), (3, 1400, 1450),
                 (4, 1450, 1500)]),
            (1, [(1, 1100, 1250), (2, 1250, 1270), (4, 1270, 1300)])]:
        plane = space.planes.add(name=f"/device:TPU:{dev}")
        plane.stat_metadata[1].name = "tf_op"
        for i, (name, tf_op) in enumerate(ops, 1):
            md = plane.event_metadata[i]
            md.name = name
            md.stats.add(metadata_id=1, str_value=tf_op)
        xla = plane.lines.add(name="XLA Ops")
        for md, s, e in events:
            xla.events.add(metadata_id=md, offset_ps=s * 10**6,
                           duration_ps=(e - s) * 10**6)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_mesh_trace_on_a_built_trace(tmp_path, monkeypatch):
    path = str(tmp_path / "t.xplane.pb")
    _write_space(path)
    red = mesh_trace.reduce(path)
    us = pytest.approx
    assert red["window_s"] == us(1000e-6)
    assert [d["busy_s"] for d in red["devices"]] == [us(400e-6),
                                                     us(200e-6)]
    assert red["devices"][0]["scope_s"] == {
        "merge": us(200e-6), "scorer_logits": us(200e-6)}
    assert [d["merge_collective_s"] for d in red["devices"]] == [
        us(150e-6), us(20e-6)]
    # chip 0 idle: 1000-1100 in sharded.batch (50) and sharded.read (50),
    # 1500-2000 in sharded.batch (50) and no span (450)
    assert red["idle_by_span"] == {k: us(v * 1e-6) for k, v in {
        "sharded.batch": 100, "sharded.read": 50,
        program_trace.NO_SPAN: 450}.items()}
    assert mesh_trace.stage("jit(search)/shard_map/merge/all_gather:") \
        == "merge"

    monkeypatch.setattr(program_trace, "newest", lambda: path)
    monkeypatch.setattr(mesh_trace, "_parsed", {})
    rec = {"trace": {"window_s": 1e-3, "busy_s": 3e-4}, "answered": 1000,
           "cfg": H.cell(H.spec(), "shard4-offline")[1],
           "peak": {"bf16_flop_per_s": 1e18}}
    read = {m: H.metric_reader(m).read(rec)
            for m in ("merge.collective_share.x4", "shard.busy_spread.x4",
                      "device.idle.x4", "serve_mfu.x4")}
    assert read["merge.collective_share.x4"] == us(100 * (150 / 400
                                                          + 20 / 200) / 2)
    assert read["shard.busy_spread.x4"] == us(50.0)
    assert read["device.idle.x4"] == us(70.0)
    assert 0 < read["serve_mfu.x4"] < 100
    # an untraced run reads nothing
    assert all(H.metric_reader(m).read(dict(rec, trace=None)) is None
               for m in read)


def test_mesh_trace_agrees_with_program_trace_on_a_chip_trace():
    """On a trace the chip wrote (one TPU, the served program, recorded for
    ``test_program_trace.py``), the per-chip reduction reads the same
    window, busy time and stage scopes as ``program_trace``; the device's
    idle time is all placed, and with no ``sharded.*`` span in that run it
    is all ``no span``."""
    trace = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "program_trace_small.xplane.pb")
    red = mesh_trace.reduce(trace)
    want = program_trace.reduce(trace)
    us = pytest.approx
    assert red["window_s"] == us(want["window_s"])
    assert len(red["devices"]) == 1
    dev = red["devices"][0]
    assert dev["busy_s"] == us(want["busy_s"])
    assert dev["scope_s"] == {k: us(v) for k, v in want["scope_s"].items()}
    assert dev["merge_collective_s"] == 0.0
    assert red["idle_by_span"] == {
        program_trace.NO_SPAN: us(want["window_s"] - want["busy_s"])}
