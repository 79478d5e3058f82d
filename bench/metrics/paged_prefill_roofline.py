"""Roofline share of the paged prefill attention kernel (_paged_prefill_kernel)
in the traced window, in percent."""

from harness import readers


def read(run):
    return readers.paged_prefill_roofline(run)
