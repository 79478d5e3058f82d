"""Model FLOPs of the extend (chunked prefill) calls of the traced window over
their device time at the chip's bf16 peak, in percent."""

from harness import readers


def read(run):
    return readers.extend_mfu(run)
