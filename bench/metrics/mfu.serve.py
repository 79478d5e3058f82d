"""The whole serving step: model FLOPs of the decode ticks and extend calls of
the traced window over the device time of both programs at the chip's bf16
peak, in percent."""

from harness import readers


def read(run):
    return readers.serve_mfu(run)
