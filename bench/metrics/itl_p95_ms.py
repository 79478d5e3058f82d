"""95th percentile of the gaps between consecutive steps that emitted tokens
for one request, over every gap that ends in the window."""

from harness import readers


def read(run):
    return readers.percentile_ms(run, "itl_s")
