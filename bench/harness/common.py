"""Records of one run, shared by the cell modules, the metric readers and
the result line."""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple


def nearest_rank(xs: Sequence[float], q: float) -> Optional[float]:
    """The ``q`` quantile by nearest rank: the smallest value with at least
    a share ``q`` of the sample at or below it."""
    if not xs:
        return None
    s = sorted(xs)
    return s[max(0, math.ceil(q * len(s)) - 1)]


class CompileLog:
    """Counts backend compiles (a program read back from JAX's persistent
    cache counts too) from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


@dataclasses.dataclass
class StepRec:
    """One serving step: host clock at its start and end (seconds from the
    start of serving) and the engine's counters for it. ``decode_ctxs``:
    keys attended by each token the decode tick emitted."""
    t0: float
    t1: float
    prefill_tokens: int
    tokens: int
    prefix_hit_tokens: int
    active: int
    admitted: int
    queue: int
    decode_ctxs: List[int]


@dataclasses.dataclass
class Run:
    cell: dict
    model: dict
    mix: dict
    seed: int
    window_s: float
    setup_s: float
    compiles_in_window: int
    memory_peak: int
    kernels: Dict[str, Dict[str, str]] = dataclasses.field(
        default_factory=dict)
    attempted: int = 0
    failed: int = 0
    steps: List[StepRec] = dataclasses.field(default_factory=list)
    extend_rows: List[Tuple[float, List[Tuple[int, int]]]] = \
        dataclasses.field(default_factory=list)
    samples: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    sample: list = dataclasses.field(default_factory=list)
    train: Dict[str, float] = dataclasses.field(default_factory=dict)
    program_train: dict = dataclasses.field(default_factory=dict)
    trace: object = None
    peaks: dict = dataclasses.field(default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)
    # the model family module (``bench/families/<family>.py``) whose
    # counts the readers use
    family: object = None
