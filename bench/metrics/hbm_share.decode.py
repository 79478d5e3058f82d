"""Bytes the decode ticks of the traced window must move (weights once, live
keys and values read, new ones written) over their device time at the chip's
HBM bandwidth, in percent."""

from harness import readers


def read(run):
    return readers.decode_hbm(run)
