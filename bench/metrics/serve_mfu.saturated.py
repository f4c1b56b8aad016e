"""Useful operations of the answered requests over the traced window, as a share (%) of the bf16 peak."""
import readers


def read(rec):
    return readers.serve_mfu(rec)
