"""Device time (ms) a served batch spends in the search's own top-m probe:
the ``top_m`` scope of ``QueryPipeline.candidates`` (the sort over every
bucket, its copies and iota), over the window's ``serve.batch`` spans."""
import program_trace


def read(rec):
    return program_trace.per_batch_ms(
        rec, lambda red: red["scope_s"].get("top_m"))
