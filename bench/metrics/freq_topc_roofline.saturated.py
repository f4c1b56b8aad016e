"""Frequency top-C's share (%) of its roofline, from the device trace: the
custom-call ``freq_topc`` against the bytes of its task (``work.freq_topc``)."""
import readers
import work


def read(rec):
    cfg = rec["cfg"]
    return readers.kernel_roofline(rec, "freq_topc", *work.freq_topc(
        readers.rows(rec), rec["width"], cfg["topC"]))
