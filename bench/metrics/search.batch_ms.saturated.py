"""Mean time of one served batch (ms), synchronised on its results, from the server's serve_batch_seconds."""
import readers


def read(rec):
    return readers.server_mean(rec, "serve_batch_seconds", 1e3)
