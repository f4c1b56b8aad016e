"""Mean requests per served batch, from the server's serve_batch_fill."""
import readers


def read(rec):
    return readers.server_mean(rec, "serve_batch_fill")
