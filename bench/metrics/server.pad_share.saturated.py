"""Share (%) of the rows the device searched that were padding: the sum of
(bucket - fill) over the sum of bucket, over the window's ``serve.batch``
spans."""
import program_trace


def read(rec):
    red = program_trace.read(rec)
    if not red or not red["batches"]:
        return None
    rows = sum(b["bucket"] for b in red["batches"])
    pad = sum(b["bucket"] - b["fill"] for b in red["batches"])
    return 100.0 * pad / rows
