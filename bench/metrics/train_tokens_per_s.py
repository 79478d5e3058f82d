"""Label tokens of the optimizer steps completed in the window, over the
window."""


def read(run):
    tokens = run.train.get("tokens")
    return tokens / run.window_s if tokens else None
