"""Roofline share of the paged decode attention kernel (_paged_kernel) in the
traced window, in percent."""

from harness import readers


def read(run):
    return readers.paged_decode_roofline(run)
