"""The adapter between the benchmark and the program under test
(``src/repro``). Everything else in the benchmark reads only what this
module hands it: engines built through the program's own launchers, the
program's counters, and the kernel names in its compiled programs; and
the program's model config, which each family's ``program_config``
(``bench/families/``) builds.

The serving engine reports emitted tokens only when a request finishes;
per-tick emissions are read here from its host mirrors
(``slot_req``, ``_host_gen``, ``_prefilling``), and extend calls from the
arguments of its per-width executables. Both are private to the engine;
a public per-tick emission record would replace them.
"""

from __future__ import annotations

import importlib
import pathlib
import sys
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import trace as trace_lib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def import_program():
    """Put ``<checkout>/src`` on the path and import the program. Raises
    ImportError in a checkout that holds only the benchmark."""
    src = str(CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return importlib.import_module("repro")


# -- serving --------------------------------------------------------------------

def serve_engine(params, cfg, serve: dict, seed: int):
    """A serving engine built by the program's launcher from the
    configuration's ``serve`` block: ``quant`` (``none`` or ``int8``:
    int8 weights and KV) and ``spec_k``/``spec_drafter`` (speculative
    decoding) pass through to the program as they stand."""
    import_program()
    from repro.launch import serve as launch_serve
    from repro.serve import ServeConfig
    scfg = ServeConfig(
        max_slots=serve["max_slots"], max_len=serve["max_len"],
        eos_id=serve["eos_id"], temperature=0.0,
        cache_dtype=jnp.dtype(serve["kv_dtype"]),
        seed=seed % (2 ** 31), paged=True, page_size=serve["page_size"],
        num_pages=serve["num_pages"], prefix_cache=serve["prefix_cache"],
        prefill_chunk=serve["prefill_chunk"],
        quant=serve.get("quant", "none"), spec_k=serve.get("spec_k", 0),
        spec_drafter=serve.get("spec_drafter", "ngram"))
    return launch_serve.build_engine(params, cfg, scfg)


def extend_widths(serve: dict) -> List[int]:
    """The extend programs chunked prefill can call: power-of-two widths
    from 4 up to the chunk."""
    w, out = 4, []
    while w < serve["prefill_chunk"]:
        out.append(w)
        w *= 2
    return out + [serve["prefill_chunk"]]


def _dead_extend_args(eng, width: int):
    """Extend arguments whose every row is dead: out-of-range slots, no
    tokens, sink page tables. The call leaves the state as it was."""
    n, nb = eng.scfg.max_slots, eng._blocks_per_slot
    i32 = np.int32
    return (jnp.zeros((n, width), i32), jnp.zeros(n, i32),
            jnp.zeros(n, i32), jnp.full(n, n + 1, i32),
            jnp.full((n, nb), eng.pool.sink, i32), jnp.ones(n, i32),
            jnp.zeros(n, np.float32), jnp.zeros(n, i32),
            jnp.zeros(n, bool))


def warm_serve(eng, widths: List[int], out_lengths) -> None:
    """Run every program the window will run once, on all-dead rows and
    inactive slots: the decode tick and each extend width; and the read of
    a finished request's tokens, which the engine compiles once for each
    output length."""
    for w in widths:
        eng.state, done = eng._admit_exe(w)(eng.params, eng.state,
                                            *_dead_extend_args(eng, w))
        done.block_until_ready()
    eng.state, packed = eng._tick(eng.params, eng.state, eng._zero_poison)
    packed.block_until_ready()
    for n in sorted(set(out_lengths)):
        eng._readback(eng.state.out_buf[0, :n])


def serve_kernels(eng, widths: List[int]) -> Dict[str, Dict[str, str]]:
    """Module name -> {instruction -> Pallas kernel} of the tick and the
    extend programs, from their compiled HLO (found again in JAX's
    caches)."""
    out: Dict[str, Dict[str, str]] = {}
    c = eng._tick.lower(eng.params, eng.state, eng._zero_poison).compile()
    out.setdefault("jit_tick", {}).update(
        trace_lib.kernel_instructions(c.as_text()))
    for w in widths:
        c = eng._admit_exe(w).lower(eng.params, eng.state,
                                    *_dead_extend_args(eng, w)).compile()
        out.setdefault("jit_admit_b", {}).update(
            trace_lib.kernel_instructions(c.as_text()))
    return out


class ExtendLog:
    """Keeps the (starts, lens) arguments of every extend call made after
    it is installed, as device arrays; read them after the window."""

    def __init__(self, eng, clock):
        self.calls: List[Tuple[float, object, object]] = []
        self._orig = eng._admit_exe
        self._clock = clock
        eng._admit_exe = self._exe

    def _exe(self, bucket):
        fn = self._orig(bucket)

        def call(params, st, toks, starts, lens, *rest):
            self.calls.append((self._clock(), starts, lens))
            return fn(params, st, toks, starts, lens, *rest)
        return call

    def rows(self) -> List[Tuple[float, List[Tuple[int, int]]]]:
        out = []
        for t, s, n in self.calls:
            s, n = np.asarray(s), np.asarray(n)
            out.append((t, [(int(a), int(b)) for a, b in zip(s, n) if b > 0]))
        return out


def emitted_counts(eng, finished) -> Dict[int, int]:
    """Tokens each live or just-finished request has been served so far."""
    out = {}
    for slot, req in enumerate(eng.slot_req):
        if req is not None and req.fork_group is None:
            out[req.uid] = (0 if slot in eng._prefilling
                            else int(eng._host_gen[slot]))
    for r in finished:
        out[r.uid] = len(r.generated)
    return out


def guard_counters(eng) -> Dict[str, float]:
    """Counters a healthy run leaves at 0: quarantined or shed requests,
    re-read readbacks, fallbacks, degraded ticks, failed audits."""
    s = eng.summary()
    return {k: float(s.get(k, 0.0)) for k in
            ("quarantined", "shed", "readback_retries", "fp_fallbacks",
             "degraded_ticks", "audit_failures")}


# -- training ---------------------------------------------------------------------

def train_engine(params, cfg, feed, train: dict):
    """The fused train engine built by the program's launcher."""
    import_program()
    from repro.launch import train as launch_train
    from repro.optim import AdamWConfig
    defaults = AdamWConfig()
    for key in ("b1", "b2", "eps", "weight_decay", "grad_clip"):
        if getattr(defaults, key) != train[key]:
            raise ValueError(f"AdamW {key}: the file says {train[key]}, the "
                             f"launcher uses {getattr(defaults, key)}")
    if train["warmup_steps"] != max(train["schedule_steps"] // 10, 1):
        raise ValueError("the launcher warms up over a tenth of the "
                         "schedule")
    eng = launch_train.build_engine(
        params, cfg, feed, steps=train["schedule_steps"],
        steps_per_tick=train["steps_per_tick"], lr=train["lr"])
    if jax.default_backend() == "tpu" and not eng.model_cfg.flash_train:
        raise RuntimeError("the flash attention route is off")
    return eng


class TickLog:
    """Keeps the per-step metrics (loss, global gradient norm) that each
    train tick returns, as device arrays."""

    def __init__(self, eng):
        self.outs: List[dict] = []
        orig = eng._tick

        def tick(params, opt_state, batches):
            p, s, ms = orig(params, opt_state, batches)
            self.outs.append(ms)
            return p, s, ms
        tick.lower = orig.lower
        eng._tick = tick


def train_kernels(eng, steps: int, batch: int, seq: int
                  ) -> Dict[str, Dict[str, str]]:
    block = jax.ShapeDtypeStruct((steps, batch, seq), jnp.int32)
    c = eng._tick.lower(eng.params, eng.opt_state,
                        {"tokens": block, "labels": block}).compile()
    return {"jit_tick": trace_lib.kernel_instructions(c.as_text())}


def opt_state_view(eng):
    """(first moments, float32 masters) of the program's AdamW state."""
    st = eng.opt_state
    return st["m"], st.get("master", eng.params)
