"""Roofline share of the flash attention backward kernels (_flash_bwd_dq_kernel
and _flash_bwd_dkv_kernel together) in the traced window, in percent."""

from harness import readers


def read(run):
    return readers.flash_bwd_roofline(run)
