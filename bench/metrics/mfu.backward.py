"""Backward model FLOPs of the steps of the traced window (two thirds of
mfu.train's; the rematerialised forward counts in time, not in FLOPs) over the
self time of the train tick's operations under a transpose, at the chip's bf16
peak, in percent."""

from harness import scopes


def read(run):
    return scopes.train_phase_mfu(run, "backward")
