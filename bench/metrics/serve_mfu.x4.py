"""Useful operations per chip of the queries answered in the traced window,
as a share (%) of one chip's bf16 peak: each chip runs the scorer and the
rerank of every query against its own shard, so the queries answered times
``work.serve_flops`` is each chip's work."""
import readers
import work


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0 or not rec["answered"]:
        return None
    cfg = rec["cfg"]
    per_q = work.serve_flops(cfg["reps"], cfg["d"], cfg["hidden"],
                             cfg["buckets"], cfg["topC"], readers.keep(cfg))
    return 100.0 * rec["answered"] * per_q / (
        tr["window_s"] * rec["peak"]["bf16_flop_per_s"])
