"""Spread (%) of the chips' device busy time over the traced window:
(max - min) / max of the per-chip busy seconds. Every chip searches every
query against its own shard, so a spread is a shard that works longer than
the others, and the others wait for it in the merge."""
import mesh_trace


def read(rec):
    red = mesh_trace.read(rec)
    if not red or len(red["devices"]) < 2:
        return None
    busy = [d["busy_s"] for d in red["devices"]]
    if max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
