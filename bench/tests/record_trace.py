"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py      # on a machine with a TPU

Traces three searches of a small int8 shard through the program's compact
pipeline (its freq_topc and quant_rerank kernels run), inside a
``bench.window`` span with a host pause between them, and writes the trace
with what it must reduce to (``trace_small.json``) beside this file.
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


def main():
    import jax
    import numpy as np

    import gen
    import harness as H
    from repro.core.search_api import SearchParams
    from repro.core.index import IRLIConfig, IRLIIndex
    from repro.store import encode
    import shard_index

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
    q = np.asarray(gen.query_rows(gen.sub_key(1, gen.QUERIES), base, n=64,
                                  metric="angular"))
    res = idx.search(q, store, sp)
    np.asarray(res.ids)
    tmp = tempfile.mkdtemp()
    H.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            np.asarray(idx.search(q, store, sp).ids)
            with jax.profiler.TraceAnnotation("host.pause"):
                time.sleep(0.02)
    H.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                        "*.xplane.pb")))[-1]
    dst = os.path.join(HERE, "trace_small.xplane.pb")
    shutil.copy(src, dst)
    import trace_reduce
    red = trace_reduce.reduce(dst)
    with open(os.path.join(HERE, "trace_small.json"), "w") as f:
        json.dump({"kernels": list(res.kernels), "reduced": red}, f,
                  indent=1)
    print(json.dumps(red), os.path.getsize(dst))


if __name__ == "__main__":
    main()
