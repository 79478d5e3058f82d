"""The least bytes of the AdamW updates of the traced window (28 per
parameter per step: fp32 master, first and second moment read and written,
the bf16 gradient read, the bf16 weight written; parameters counted from the
configuration's widths) over the self time of the train tick's operations
under the adamw scope, at the chip's HBM peak, in percent."""

from harness import scopes


def read(run):
    return scopes.adamw_hbm_share(run)
