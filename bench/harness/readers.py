"""Reductions shared by the metric readers in ``bench/metrics``. Each
returns ``None`` where its run has nothing to read (no trace, no call of
the program or kernel in the window, no peak for the device), never 0
for a share of a peak or a roofline. Operations and bytes come from the
counts of the run's model family (``run.family``)."""

from __future__ import annotations

from typing import Iterable, Optional

from . import trace as trace_lib
from .common import nearest_rank

# the programs as the trace names them: the serving tick and extend, and
# the train tick (also "jit_tick")
TICK, EXTEND = "jit_tick", "jit_admit_b"


def percentile_ms(run, key: str, q: float = 0.95) -> Optional[float]:
    v = nearest_rank(run.samples.get(key, []), q)
    return None if v is None else v * 1e3


def idle_share(run) -> Optional[float]:
    if run.trace is None or not run.trace.window_ns:
        return None
    return 100.0 * (1.0 - trace_lib.busy_ns(run.trace) / run.trace.window_ns)


def _ready(run) -> bool:
    return run.trace is not None and bool(run.peaks)


def decode_mfu(run) -> Optional[float]:
    if not _ready(run):
        return None
    t = trace_lib.module_ns(run.trace, TICK)
    flops = _decode_flops(run)
    if not t or not flops:
        return None
    return 100.0 * flops / (t / 1e9 * run.peaks["bf16_flops"])


def decode_hbm(run) -> Optional[float]:
    if not _ready(run):
        return None
    t = trace_lib.module_ns(run.trace, TICK)
    byt = sum(run.family.decode_bytes(run.model, s.decode_ctxs)
              for s in run.steps if s.decode_ctxs)
    if not t or not byt:
        return None
    return 100.0 * byt / (t / 1e9 * run.peaks["hbm_bytes_per_s"])


def _extend_flops(run) -> float:
    return sum(run.family.extend_flops(run.model, rows)
               for _, rows in run.extend_rows if rows)


def _decode_flops(run) -> float:
    return sum(run.family.decode_flops(run.model, s.decode_ctxs)
               for s in run.steps if s.decode_ctxs)


def extend_mfu(run) -> Optional[float]:
    if not _ready(run):
        return None
    t = trace_lib.module_ns(run.trace, EXTEND)
    flops = _extend_flops(run)
    if not t or not flops:
        return None
    return 100.0 * flops / (t / 1e9 * run.peaks["bf16_flops"])


def serve_mfu(run) -> Optional[float]:
    """The whole serving step: model FLOPs of the decode ticks and the
    extend calls over the device time of both programs."""
    if not _ready(run):
        return None
    t = (trace_lib.module_ns(run.trace, TICK)
         + trace_lib.module_ns(run.trace, EXTEND))
    flops = _decode_flops(run) + _extend_flops(run)
    if not t or not flops:
        return None
    return 100.0 * flops / (t / 1e9 * run.peaks["bf16_flops"])


def roofline(run, kernels: Iterable[str], work) -> Optional[float]:
    """Share of the kernels' device time that the chip would need at its
    peaks for ``work``: a list of (FLOPs, bytes), one per call group.
    Notes which bound, compute or bandwidth, sets it."""
    if not _ready(run):
        return None
    t, calls = trace_lib.kernel_ns(run.trace, run.kernels, kernels)
    if not calls or not work:
        return None
    p, b = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    need = sum(max(f / p, by / b) for f, by in work)
    mem = sum(by / b for f, by in work if by / b >= f / p)
    run.notes.append(f"{sorted(kernels)}: {calls} calls, {t / 1e9:.6f} s; "
                     f"{'bandwidth' if mem >= need / 2 else 'compute'} "
                     f"bound ({mem / need:.3f} of the least time is "
                     f"bandwidth-bound work)")
    return 100.0 * need / (t / 1e9)


def paged_decode_roofline(run) -> Optional[float]:
    return roofline(run, ["_paged_kernel"],
                    [run.family.paged_decode_kernel(run.model, s.decode_ctxs)
                     for s in run.steps if s.decode_ctxs])


def paged_prefill_roofline(run) -> Optional[float]:
    return roofline(run, ["_paged_prefill_kernel"],
                    [run.family.paged_prefill_kernel(run.model, rows)
                     for _, rows in run.extend_rows if rows])


def train_mfu(run) -> Optional[float]:
    """Forward and backward model FLOPs of the traced steps over the
    device time of the train tick program in the trace."""
    if not _ready(run) or not run.train.get("traced_tokens"):
        return None
    t = trace_lib.module_ns(run.trace, TICK)
    if not t:
        return None
    f = run.family.train_flops_per_token(run.model, run.mix["seq_len"])
    return (100.0 * f * run.train["traced_tokens"]
            / (t / 1e9 * run.peaks["bf16_flops"]))


def flash_bwd_roofline(run) -> Optional[float]:
    if not run.train.get("traced_steps"):
        return None
    return roofline(run, ["_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"],
                    [run.family.attention_kernels(run.model, run.mix["batch"],
                                             run.mix["seq_len"],
                                             run.train["traced_steps"])])
