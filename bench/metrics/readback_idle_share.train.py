"""Device idle time in the traced window whose innermost program span is
train.readback (the train engine waiting on its one readback of a tick), over
the window, in percent."""

from harness import scopes


def read(run):
    return scopes.span_idle_share(run, "train.readback")
