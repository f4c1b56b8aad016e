"""Device time (ms) a served batch spends gathering what the coarse int8
rerank reads: the ``quant_rerank`` scope less its ``quant_rerank``
custom-call (the code-row and scale gathers and their index glue), over
the window's ``serve.batch`` spans. None where the kernel did not run."""
import program_trace


def gathers(red):
    ops = red["scope_op_s"].get("quant_rerank", {})
    if "quant_rerank" not in ops:
        return None
    return sum(ops.values()) - ops["quant_rerank"]


def read(rec):
    return program_trace.per_batch_ms(rec, gathers)
