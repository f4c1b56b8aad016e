"""The coarse int8 rerank's share (%) of its roofline, from the device
trace: the custom-call ``quant_rerank`` alone, against what it reads and
does (``work.quant_rerank``). The gather of the candidates' code rows and
scales runs as separate operations before it, and its time is not counted
here; the whole step's share is ``serve_mfu.saturated``."""
import readers
import work


def read(rec):
    cfg = rec["cfg"]
    return readers.kernel_roofline(rec, "quant_rerank", *work.quant_rerank(
        readers.rows(rec), cfg["topC"], cfg["d"], cfg["store_block"],
        readers.keep(cfg)))
