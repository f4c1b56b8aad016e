"""p99 request latency from due time to answer (ms); recorded here, not judged: the queue grows all through a saturated run."""
import readers


def read(rec):
    return readers.client_quantile(rec, "latency_ms", 99)
