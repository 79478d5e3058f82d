"""Reduction of one profiler trace to device busy time, program and kernel
device time, the costliest device operations and the longest idle gaps.

A trace is what ``jax.profiler`` writes (``*.xplane.pb``), read with
``jax.profiler.ProfileData``. On a TPU the device plane is
``/device:TPU:<n>``; its line ``XLA Modules`` holds one event per run of a
compiled program (``jit_tick(<fingerprint>)``), and its line ``XLA Ops``
one event per operation, named by the operation's HLO text
(``%paged_decode_attention.9 = bf16[...] custom-call(...)``). Operations
nest: a ``while`` loop's event spans the operations of its body. A Pallas
kernel is a ``custom-call`` whose instruction name says nothing reliable
of the kernel, so kernels are found by instruction name in the compiled
program's HLO text (:func:`kernel_instructions`). Host threads are on
``/host:CPU``, one line each; the main thread's line, named after the
process (``python3``), carries the benchmark's ``TraceAnnotation`` spans
and, with the Python tracer on, the host's function calls. It is found
as the line that holds the window's span.

Busy time is the union of the ``XLA Ops`` intervals inside the window;
idle is the rest of the window.
"""

from __future__ import annotations

import base64
import bisect
import collections
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"
_INSTR = re.compile(r"^%?([A-Za-z0-9_.\-]+)")
_MOSAIC_CALL = re.compile(
    r'^\s*(?:ROOT )?%([A-Za-z0-9_.\-]+) = .*custom_call_target='
    r'"tpu_custom_call".*?"body":"([^"]+)"', re.M)
_KERNEL_SYMBOL = re.compile(rb"[A-Za-z0-9_]+_kernel")


def kernel_instructions(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> Pallas kernel function name, for every Mosaic
    custom call in a compiled program's HLO text. The first ``*_kernel``
    symbol in a serialized Mosaic body is the kernel function."""
    out = {}
    for instr, body in _MOSAIC_CALL.findall(hlo_text):
        m = _KERNEL_SYMBOL.search(base64.b64decode(body))
        out[instr] = m.group(0).decode() if m else "?"
    return out


def module_base(name: str) -> str:
    """``jit_tick(123)`` -> ``jit_tick``."""
    return name.split("(", 1)[0]


def op_instr(name: str) -> str:
    """``%copy.96 = bf16[...] copy(...)`` -> ``copy.96``."""
    m = _INSTR.match(name)
    return m.group(1) if m else name


def op_kind(instr: str) -> str:
    """``copy_bitcast_fusion.15`` -> ``copy_bitcast_fusion``."""
    return re.sub(r"\.\d+$", "", instr)


@dataclasses.dataclass
class Op:
    start: int
    end: int
    instr: str
    module: str
    self_ns: int = 0


@dataclasses.dataclass
class Trace:
    """One device's operations and modules and the host's spans, cut to
    the window, in nanoseconds on the profiler's clock."""
    t0: int
    t1: int
    ops: List[Op]
    modules: List[Tuple[int, int, str]]
    host: List[Tuple[int, int, str]]
    devices: int = 1

    @property
    def window_ns(self) -> int:
        return self.t1 - self.t0


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def keep(path: str, kernels: Dict[str, Dict[str, str]], dest) -> None:
    """Keep a trace, gzipped, with the kernel names of its programs:
    ``<dest>/trace.xplane.pb.gz`` and ``<dest>/kernels.json``."""
    os.makedirs(dest, exist_ok=True)
    with open(path, "rb") as f, gzip.open(
            os.path.join(dest, "trace.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    with open(os.path.join(dest, "kernels.json"), "w") as f:
        json.dump(kernels, f, indent=1, sort_keys=True)


def _events(line) -> Iterable[Tuple[int, int, str]]:
    for e in line.events:
        s = int(e.start_ns)
        yield s, s + int(e.duration_ns), e.name


def load(profile, window: Optional[Tuple[int, int]] = None,
         device: int = 0) -> Trace:
    """Read a ``ProfileData``. The window is the host span named
    ``bench.window`` unless given; the host's spans are those of the line
    that holds it."""
    host: List[Tuple[int, int, str]] = []
    dev_planes = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev_planes[int(m.group(1))] = plane
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = list(_events(line))
                if any(n == WINDOW_SPAN for _, _, n in evs):
                    host.extend(evs)
    if device not in dev_planes:
        raise ValueError(f"no /device:TPU:{device} plane in the trace "
                         f"(planes: {[p.name for p in profile.planes]})")
    if window is None:
        spans = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no host span {WINDOW_SPAN!r} in the trace")
        window = spans[0]
    t0, t1 = window
    modules: List[Tuple[int, int, str]] = []
    raw: List[Tuple[int, int, str]] = []
    for line in dev_planes[device].lines:
        if line.name == "XLA Modules":
            modules = sorted(_events(line))
        elif line.name == "XLA Ops":
            raw = sorted(_events(line), key=lambda x: (x[0], -x[1]))
    ops = []
    starts = [m[0] for m in modules]
    for s, e, name in raw:
        if e <= t0 or s >= t1:
            continue
        i = bisect.bisect_right(starts, s) - 1
        mod = (module_base(modules[i][2])
               if i >= 0 and modules[i][1] >= s else "?")
        ops.append(Op(max(s, t0), min(e, t1), op_instr(name), mod))
    _fill_self_times(ops)
    host = sorted((max(s, t0), min(e, t1), n) for s, e, n in host
                  if e > t0 and s < t1 and n != WINDOW_SPAN)
    return Trace(t0, t1, ops, [m for m in modules if m[1] > t0 and m[0] < t1],
                 host, devices=len(dev_planes))


def _fill_self_times(ops: List[Op]) -> None:
    """An op's self time is its duration less that of the ops it
    encloses (a loop's body)."""
    stack: List[Op] = []
    for op in ops:
        op.self_ns = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
        stack.append(op)


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(tr: Trace) -> int:
    return union_ns((o.start, o.end) for o in tr.ops)


def module_ns(tr: Trace, base: str) -> int:
    """Device time of the runs of the program ``base`` in the window."""
    return sum(min(e, tr.t1) - max(s, tr.t0) for s, e, n in tr.modules
               if module_base(n) == base)


def kernel_ns(tr: Trace, kernels: Dict[str, Dict[str, str]],
              names: Iterable[str]) -> Tuple[int, int]:
    """(device time, calls) of the Pallas kernels ``names``. ``kernels``
    maps a module base name to :func:`kernel_instructions` of its HLO."""
    want = set(names)
    total = calls = 0
    for op in tr.ops:
        if kernels.get(op.module, {}).get(op.instr) in want:
            total += op.end - op.start
            calls += 1
    return total, calls


def top_ops(tr: Trace, kernels: Dict[str, Dict[str, str]],
            n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` device operations with the most self time, grouped by
    program and operation kind, in seconds."""
    acc: Dict[str, int] = collections.Counter()
    for op in tr.ops:
        k = kernels.get(op.module, {}).get(op.instr)
        label = f"{op.module}/{k or op_kind(op.instr)}"
        acc[label] += op.self_ns
    return [(k, v / 1e9) for k, v in acc.most_common(n)]


def idle_gaps(tr: Trace, n: int = 10, min_ns: int = 0
              ) -> List[Tuple[str, float]]:
    """Device idle time inside the window, attributed to the innermost
    host span that covers each gap's midpoint, summed per span name; the
    ``n`` largest, in seconds."""
    busy = []
    for s, e in sorted((o.start, o.end) for o in tr.ops):
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    gaps, prev = [], tr.t0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if tr.t1 > prev:
        gaps.append((prev, tr.t1))
    starts = [h[0] for h in tr.host]
    acc: Dict[str, int] = collections.Counter()
    for s, e in gaps:
        if e - s < min_ns:
            continue
        mid = (s + e) // 2
        label = "no host span"
        best = None
        i = bisect.bisect_right(starts, mid)
        for hs, he, name in reversed(tr.host[max(0, i - 400):i]):
            if he >= mid and (best is None or he - hs < best):
                best, label = he - hs, name
        acc[label] += e - s
    return [(k, v / 1e9) for k, v in acc.most_common(n)]
