"""Device time (ms) a served batch spends in the server's probe-statistics
pass: the ``probe_stats`` scope of ``probe_buckets`` (its scorer and its
top-m sort), over the window's ``serve.batch`` spans."""
import program_trace


def read(rec):
    return program_trace.per_batch_ms(
        rec, lambda red: red["scope_s"].get("probe_stats"))
