"""Model FLOPs of the decode ticks of the traced window over their device time
at the chip's bf16 peak, in percent."""

from harness import readers


def read(run):
    return readers.decode_mfu(run)
