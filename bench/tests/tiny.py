"""A tiny deep1b-shard for CPU tests: every width cut, every rule kept."""
import harness as H


def config(**kw) -> dict:
    cf = H.cell(H.spec(), "shard-saturated")[1]
    cf = dict(cf, n_corpus=8192, d=32, hidden=64, buckets=256, reps=4,
              member_width=64, topC=128, affinity_chunk=512, sample=64)
    cf.update(kw)
    return cf


TRAFFIC = {"rate_qps": 200}
