"""Record the small TPU trace that ``test_program_trace.py`` reads.

    python3 bench/tests/record_program_trace.py      # on a machine with a TPU

Serves a small int8 shard through an ``IRLIServer`` (probe statistics on,
batches of at most 64): inside a ``bench.window`` span, three rounds of 40
queries submitted at once and waited for, each followed by a 20 ms pause
in which the server waits on an empty queue (``serve.wait``; the last
one is still open when the profiler stops, so it is not in the trace).
The served program's stage scopes and the server's phase spans land in
the trace.
Writes the trace, its reduction by ``program_trace.reduce`` and the
server's own counters for the window (``program_trace_small.json``) beside
this file.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

ROUNDS, PER_ROUND, PAUSE_S = 3, 40, 0.02


def main():
    import jax
    import numpy as np

    import gen
    import harness as H
    import program_trace
    import shard_index
    from repro.core import query as Q
    from repro.core.index import IRLIConfig, IRLIIndex
    from repro.core.search_api import SearchParams
    from repro.serve.server import IRLIServer
    from repro.store import encode

    assert jax.devices()[0].platform == "tpu"
    L, R, B, d = 1 << 14, 8, 512, 96
    base = gen.corpus(gen.sub_key(1, gen.CORPUS), n=L, d=d, n_clusters=256,
                      metric="angular")
    w = gen.scorer_weights(gen.sub_key(1, gen.WEIGHTS), R=R, d=d, H=256, B=B)
    idx = IRLIIndex(IRLIConfig(d=d, n_labels=L, n_buckets=B, n_reps=R,
                               d_hidden=256))
    idx.params = w
    idx.assign = shard_index.assignment(w, base, gen.sub_key(1, 6), K=4,
                                        B=B, chunk=1024)
    idx.build_index()
    store = encode(base, "int8", 32)
    sp = SearchParams(m=5, tau=2, k=10, store_dtype="int8")
    server = IRLIServer(idx, params=sp, base=store, max_batch=64)
    q = np.asarray(gen.query_rows(gen.sub_key(1, gen.QUERIES), base,
                                  n=ROUNDS * PER_ROUND, metric="angular"))
    for b in server.buckets:                  # warm every pad bucket
        res = idx.search(q[:b], store, sp, cache=server.cache)
        np.asarray(res.ids)
        np.asarray(Q.probe_buckets(idx.params, q[:b], sp.m))
    reg = server.registry
    before = server.stats
    tmp = tempfile.mkdtemp()
    H.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for r in range(ROUNDS):
            futs = [server.submit(row)
                    for row in q[r * PER_ROUND:(r + 1) * PER_ROUND]]
            for f in futs:
                f.result(timeout=60)
            time.sleep(PAUSE_S)
    H.stop_trace()
    after = server.stats
    fill = reg.histogram("serve_batch_fill").snapshot()
    server.close()
    src = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                        "*.xplane.pb")))[-1]
    dst = os.path.join(HERE, "program_trace_small.xplane.pb")
    shutil.copy(src, dst)
    red = program_trace.reduce(dst)
    counters = {k: after[k] - before[k]
                for k in ("batches", "requests", "pad_waste")}
    with open(os.path.join(HERE, "program_trace_small.json"), "w") as f:
        json.dump({"kernels": list(res.kernels), "server": counters,
                   "fill_sum": fill["sum"], "reduced": red}, f, indent=1)
    print(json.dumps(counters), json.dumps(red["scope_s"]),
          json.dumps(red["idle_by_span"]), os.path.getsize(dst))


if __name__ == "__main__":
    main()
