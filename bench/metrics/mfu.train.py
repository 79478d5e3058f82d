"""Forward and backward model FLOPs of the steps of the traced window (6 per
weight per token plus causal attention, no recomputed work) over the device
time of the train tick program at the chip's bf16 peak, in percent."""

from harness import readers


def read(run):
    return readers.train_mfu(run)
