"""Share (%) of the traced window with no operation on a chip, as a mean
over the chips (1 - mean busy / window)."""
import readers


def read(rec):
    return readers.device_idle(rec)
