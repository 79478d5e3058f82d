"""Prompt tokens prefilled and output tokens emitted by the steps of the
window, over the window."""


def read(run):
    if run.mix["kind"] != "serve":
        return None
    return (sum(s.prefill_tokens + s.tokens for s in run.steps)
            / run.window_s)
