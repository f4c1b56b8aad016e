"""Share (%) of each chip's busy time spent in the cross-shard merge's
collectives: the all-gather and all-reduce operations inside the ``merge``
scope of ``make_production_search``, over that chip's device busy time, as
a mean over the chips. A collective on a fast chip includes its wait for
the slowest, so this reads the straggler cost that exists only across
chips. None where the trace holds no such operation."""
import mesh_trace


def read(rec):
    red = mesh_trace.read(rec)
    if not red:
        return None
    devs = [d for d in red["devices"] if d["busy_s"] > 0]
    if not devs or not any(d["merge_collective_s"] for d in devs):
        return None
    return 100.0 * sum(d["merge_collective_s"] / d["busy_s"]
                       for d in devs) / len(devs)
