"""Share of the prompt tokens served in the window that came from the prefix
cache: the engine's prefix-hit tokens over those and the prompt tokens it
computed, in percent."""


def read(run):
    hit = sum(s.prefix_hit_tokens for s in run.steps)
    done = hit + sum(s.prefill_tokens for s in run.steps)
    return 100.0 * hit / done if done else None
