"""Forward model FLOPs of the steps of the traced window (a third of
mfu.train's: 2 per weight per token plus causal attention) over the self time
of the train tick's operations under a jvp and outside the transpose, at the
chip's bf16 peak, in percent."""

from harness import scopes


def read(run):
    return scopes.train_phase_mfu(run, "forward")
