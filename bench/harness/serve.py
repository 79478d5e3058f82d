"""A serving cell: set-up, the measured window, and the reading of its
records.

Open loop: requests are due on the mix's Poisson schedule, counted from
the start of serving, and are sent at the first step boundary after they
are due; each is timed from its due time. The window opens after the
mix's ``lead_in_s`` of the same traffic, so that it sees the engine in
its steady state, and closes at the first step boundary after
``seconds``. Closed loop: ``outstanding`` requests are kept in the
system; a finished one is replaced at the next step boundary.

Every step of the engine ends in a readback of its results, so the host
clock at a step's end is when its tokens exist.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import time
from typing import Dict, List, Optional

import numpy as np

from . import program
from .common import CompileLog, Run, StepRec, nearest_rank
from .traffic import ServeTraffic, quantiles


@dataclasses.dataclass
class ReqRec:
    idx: int
    due: Optional[float]
    plen: int
    max_tokens: int
    prompt: np.ndarray
    sent: float = 0.0
    emits: List[tuple] = dataclasses.field(default_factory=list)
    served: Optional[List[int]] = None
    done_t: Optional[float] = None


def serve_run(cell: dict, family, model: dict, mix: dict, seed: int,
              seconds: float, trace_dir: Optional[str], t_proc: float,
              device, fault=None) -> Run:
    import jax
    serve = model["serve"]
    clog = CompileLog()
    cfg = family.program_config(model)
    params = family.make_weights(model, seed, serve["dtype"])
    eng = program.serve_engine(params, cfg, serve, seed)
    if fault is not None:
        fault(eng)
    traffic = ServeTraffic(mix, seed, model["vocab_size"])
    lead = float(mix.get("lead_in_s", 0.0))
    open_loop = mix["loop"] == "open"
    if open_loop:
        pending = traffic.open_loop(lead + seconds)
        stream, lengths = None, [r.max_tokens for r in pending]
    else:
        pending, stream = [], traffic.closed_loop()
        lengths = quantiles(mix["output"], int(mix["block"]))
    widths = program.extend_widths(serve)
    program.warm_serve(eng, widths, lengths)
    kernels = program.serve_kernels(eng, widths) if trace_dir else {}
    recs: Dict[int, ReqRec] = {}
    nxt = 0
    last: Dict[int, int] = {}
    steps: List[StepRec] = []
    live = 0
    win0 = win1 = None
    compiles0 = 0
    window_span = None

    def submit(r, now):
        nonlocal live
        uid = eng.submit(r.prompt, max_tokens=r.max_tokens)
        recs[uid] = ReqRec(r.idx, r.due, len(r.prompt), r.max_tokens,
                           r.prompt, sent=now)
        live += 1

    t0 = time.perf_counter()
    ext = (program.ExtendLog(eng, lambda: time.perf_counter() - t0)
           if trace_dir else None)
    while True:
        now = time.perf_counter() - t0
        if win0 is None and now >= lead:
            if trace_dir:
                jax.profiler.start_trace(trace_dir)
                window_span = jax.profiler.TraceAnnotation("bench.window")
                window_span.__enter__()
                now = time.perf_counter() - t0
            win0 = now
            compiles0 = clog.compiles
            setup_s = time.perf_counter() - t_proc
        if win0 is not None and now >= win0 + seconds:
            win1 = now
            break
        if open_loop:
            while nxt < len(pending) and pending[nxt].due <= now:
                submit(pending[nxt], now)
                nxt += 1
            if live == 0:
                wake = (pending[nxt].due if nxt < len(pending)
                        else math.inf)
                wake = min(wake, lead if win0 is None else win0 + seconds)
                with _span(trace_dir and win0 is not None, "bench.idle"):
                    time.sleep(max(0.0, wake - now))
                continue
        else:
            while live < int(mix["outstanding"]):
                submit(next(stream), now)
        ta = time.perf_counter() - t0
        with _span(trace_dir and win0 is not None, "bench.step"):
            finished = eng.step()
        tb = time.perf_counter() - t0
        m = eng.last_metrics
        counts = program.emitted_counts(eng, finished)
        ctxs = []
        for uid, c in counts.items():
            before = last.get(uid, 0)
            if c > before:
                rec = recs[uid]
                rec.emits.append((tb, c))
                # tokens 2.. come from the decode tick: token k's query
                # attends the prompt and the k-1 tokens before it
                ctxs.extend(rec.plen + k - 1 for k in range(max(before + 1, 2),
                                                            c + 1))
                last[uid] = c
        for r in finished:
            rec = recs[r.uid]
            rec.served = list(r.generated)
            rec.done_t = tb
            live -= 1
        steps.append(StepRec(ta, tb, m.prefill_tokens, m.tokens,
                             m.prefix_hit_tokens, m.active_slots,
                             m.admitted, m.queue_depth, ctxs))
    compiles = clog.compiles - compiles0
    if trace_dir:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    peak = program_peak(device)
    guards = program.guard_counters(eng)
    ext_rows = ext.rows() if ext else []
    del eng, params, ext
    gc.collect()

    run = Run(cell=cell, model=model, mix=mix, seed=seed,
              window_s=win1 - win0, setup_s=setup_s,
              compiles_in_window=compiles, memory_peak=peak,
              kernels=kernels, family=family)
    run.steps = [s for s in steps if s.t0 >= win0]
    run.extend_rows = [(t, rows) for t, rows in ext_rows if t >= win0]
    run.notes.append(f"guard counters {guards}")
    _read_requests(run, recs, win0, win1, open_loop)
    run.sample = _sample(recs, seed, win0, win1)
    return run


def _span(on, name):
    """A host span in the trace, only in a traced window."""
    import jax
    return (jax.profiler.TraceAnnotation(name) if on
            else contextlib.nullcontext())


def program_peak(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _read_requests(run: Run, recs: Dict[int, ReqRec], win0: float,
                   win1: float, open_loop: bool) -> None:
    ttft, itl, late = [], [], []
    attempted = failed = 0
    for rec in recs.values():
        ref_t = rec.due if open_loop else rec.sent
        in_win = win0 <= ref_t < win1
        if in_win:
            attempted += 1
            late.append(rec.sent - ref_t)
            first = rec.emits[0][0] if rec.emits else None
            ttft.append((first if first is not None else win1) - ref_t)
            if rec.served is not None and len(rec.served) != rec.max_tokens:
                failed += 1
        times = [t for t, _ in rec.emits]
        itl.extend(b - a for a, b in zip(times, times[1:]) if b >= win0)
    run.attempted, run.failed = attempted, failed
    run.samples["ttft_s"] = ttft
    run.samples["itl_s"] = itl
    run.samples["late_s"] = late
    run.notes.append(
        f"requests due in window {attempted}, finished in window "
        f"{sum(1 for r in recs.values() if r.done_t and r.done_t >= win0)}, "
        f"ttft n={len(ttft)} median {_ms(nearest_rank(ttft, 0.5))} ms, "
        f"itl n={len(itl)} median {_ms(nearest_rank(itl, 0.5))} ms; "
        f"generator late median {_ms(nearest_rank(late, 0.5))} ms, "
        f"max {_ms(max(late) if late else None)} ms")


def _ms(x):
    return "none" if x is None else f"{x * 1e3:.3f}"


def _sample(recs: Dict[int, ReqRec], seed: int, win0: float, win1: float,
            want_tokens: int = 512, most: int = 12) -> List[ReqRec]:
    """Requests finished in the window, drawn from the seed, the longest
    (prompt and served tokens) first, until ``want_tokens`` served tokens
    or ``most`` requests."""
    done = sorted((r for r in recs.values()
                   if r.done_t is not None and win0 <= r.done_t <= win1),
                  key=lambda r: r.idx)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.plen + len(r.served), -r.idx))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 1]).permutation(len(rest))
    out, tokens = [longest], len(longest.served)
    for i in order:
        if tokens >= want_tokens or len(out) >= most:
            break
        out.append(rest[int(i)])
        tokens += len(rest[int(i)].served)
    return out


def serve_check(run: Run, model: dict, seed: int, control: bool = False
                ) -> Dict[str, float]:
    """Widest gap, over the sampled requests' served tokens, by which a
    served token's logit lies below the reference's best. ``control``:
    at the same positions of the same prompts and served tokens, the gap
    of the token that the reference with int8 weights puts first, in the
    program's place."""
    family = run.family
    w = family.make_weights(model, seed, model["serve"]["dtype"])
    worst = 0.0
    n = 0
    for rec in run.sample:
        gaps = family.served_gaps(w, model, rec.prompt, rec.served,
                                  control=control)
        worst = max(worst, float(np.max(gaps)))
        n += len(gaps)
    run.notes.append(f"{'int8 control' if control else 'reference'} over "
                     f"{len(run.sample)} requests, {n} served tokens")
    return {"max_logit_gap": worst if n else math.inf}
