"""Share (%) of the traced window with no operation on the device."""
import readers


def read(rec):
    return readers.device_idle(rec)
