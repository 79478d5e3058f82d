"""95th percentile of the time to first token of every request due in the
window, from its due time; a request with no token by the window's end
counts with its wait so far."""

from harness import readers


def read(run):
    return readers.percentile_ms(run, "ttft_s")
