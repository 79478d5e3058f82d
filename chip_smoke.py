#!/usr/bin/env python3
"""Bring-up check: the paged serve engine and the fused train tick on one
TPU at StarCoder2-7B's published widths.

    python chip_smoke.py

Run it from the root of a checkout, on a machine with a TPU. It is one
process: it imports JAX once, starts no child process, and uses the first
device. Off a TPU it exits non-zero before any work.

Phases:

* serve: StarCoder2-7B widths (d_model 4608, 36 heads, 4 KV heads, head_dim
  128, d_ff 18432, vocab 49152), depth cut to 8 of 32 layers (one stage of
  a four-stage pipeline), bf16 weights from a seed, bf16 paged KV (4096
  pages of 16 tokens). Eight requests with 200-1000 token prompts, two of
  them sharing a 512-token prefix, 32 output tokens each, chunked prefill
  of 256, through ``ServeEngine.submit``/``run_until_drained`` in three
  arms: bf16, int8 weights + KV, and n-gram speculation with k=4. Each arm
  must finish every request in full with every guard counter at 0, and the
  programs it ran must hold its Pallas kernels compiled. The bf16 arm must
  also reuse the shared prefix, and its logits must agree with the plain
  XLA attention path at highest matmul precision.
* train: ``TrainEngine.for_lm`` at the same widths, depth 2, one 2048-token
  sequence per step, 4 steps per tick, with the flash kernels forward and
  backward. Memory is reckoned from the compiled tick before it runs; the
  loss must stay finite.

The persistent compilation cache is on (``repro.launch.runtime``), so a
second run reads its programs back; each phase's line says which. Wall
times are smoke wall times, not benchmarks. Any failure raises. The last
line of stdout, printed only when every check passed, is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

SEED = 0
PUBLISHED_LAYERS = 32
SERVE_LAYERS = 8
PAGE_SIZE = 16
NUM_PAGES = 4096
N_REQUESTS = 8
PROMPT_LEN = (200, 1000)
SHARED_PREFIX = 512
MAX_TOKENS = 32
PREFILL_CHUNK = 256
# longest prompt + outputs, rounded up to whole pages
MAX_LEN = -(-(PROMPT_LEN[1] + MAX_TOKENS) // PAGE_SIZE) * PAGE_SIZE
TRAIN_LAYERS = 2
TRAIN_SEQ = 2048
STEPS_PER_TICK = 4
TRAIN_STEPS = 2 * STEPS_PER_TICK
LOGIT_STEPS = 8
# Logits of the kernel path against the plain path, as the largest
# absolute difference over the largest reference logit. Both paths hold
# bf16 weights, a bf16 residual stream and a bf16 KV cache; they round at
# different points (online softmax over 16-token pages in f32 against a
# materialized score matrix), and each of the 8 layers re-rounds its
# output to bf16 (relative step 2**-8). A few such steps per layer
# compound to about 1e-2. A wrong page, mask, position or scale moves
# logits by their own size, far above 5e-2.
LOGIT_RTOL = 5e-2
# ServeEngine.summary() counters that a healthy run leaves at 0: a NaN
# lane quarantined and re-prefilled, a re-read readback, an int8->fp
# fallback, a tick served degraded, a failed page audit, a shed request
GUARDS = ("quarantined", "readback_retries", "fp_fallbacks",
          "degraded_ticks", "audit_failures", "shed")
# Pallas kernels each serve arm's programs must hold compiled
ARM_KERNELS = {
    "bf16": {"tick": {"_paged_kernel"},
             "extend": {"_paged_prefill_kernel"}},
    "int8": {"tick": {"_paged_kernel", "_int8_matmul_kernel"},
             "extend": {"_paged_prefill_kernel", "_int8_matmul_kernel"}},
    "spec_k4": {"tick": {"_paged_verify_kernel"},
                "extend": {"_paged_prefill_kernel"}},
}
TRAIN_KERNELS = {"_flash_kernel", "_flash_bwd_dq_kernel",
                 "_flash_bwd_dkv_kernel"}


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's monitoring events. A program read back from the cache still
    counts its (short) load as compile seconds. Given the ``device``, each
    phase also reports the device's peak bytes in use so far."""

    def __init__(self, monitoring, device=None):
        self.secs, self.hits, self.misses = 0.0, 0, 0
        self._monitoring = monitoring
        self._device = device
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._duration)
        self._monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration_secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        s0, h0, m0 = self.secs, self.hits, self.misses
        t0 = time.perf_counter()
        yield
        hits, misses = self.hits - h0, self.misses - m0
        state = "warm" if hits else "cold" if misses else "not consulted"
        peak = (f"; peak bytes in use "
                f"{self._device.memory_stats()['peak_bytes_in_use']}"
                if self._device is not None else "")
        log(f"[{name}] compile {self.secs - s0:.2f} s, compile cache "
            f"{state} ({hits} hits, {misses} misses); "
            f"wall {time.perf_counter() - t0:.2f} s (smoke wall time, not a "
            f"benchmark){peak}")


def check_kernels(label: str, text: str, want: set) -> None:
    from repro.kernels import ops as kops
    got = kops.compiled_kernels(text)
    log(f"  {label}: compiled kernels {dict(sorted(got.items()))}")
    if not want <= set(got):
        raise RuntimeError(f"{label}: kernels {sorted(want - set(got))} are "
                           f"not compiled into the program that ran")


def engine_programs(eng):
    """(label, compiled HLO text) of every tick and admission program the
    engine built and ran. Lowering again with the same argument types finds
    the executables in JAX's caches."""
    import jax
    import jax.numpy as jnp
    for k, fn in sorted(eng._tick_fns.items()):
        yield (f"tick(spec_k={k})", "tick", fn.lower(
            eng.params, eng.state, eng._zero_poison).compile().as_text())
    n, nb = eng.scfg.max_slots, eng._blocks_per_slot

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)
    for width, fn in sorted(eng._admit_fns.items()):
        i32 = jnp.int32
        args = (spec(i32, n, width), spec(i32, n), spec(i32, n),
                spec(i32, n), spec(i32, n, nb), spec(i32, n),
                spec(jnp.float32, n), spec(i32, n), spec(jnp.bool_, n))
        yield (f"extend(width={width})", "extend", fn.lower(
            eng.params, eng.state, *args).compile().as_text())


def make_prompts(vocab: int) -> list:
    """Seeded prompts; request 0 and the first request of the second wave
    share their first SHARED_PREFIX tokens."""
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=N_REQUESTS)
    lens[0] = max(lens[0], SHARED_PREFIX + PAGE_SIZE)
    lens[1] = max(lens[1], SHARED_PREFIX + PAGE_SIZE)
    prompts = [rng.integers(0, vocab, size=int(n), dtype=np.int32)
               for n in lens]
    prompts[1][:SHARED_PREFIX] = prompts[0][:SHARED_PREFIX]
    return prompts


def serve_arm(name: str, params, cfg, prompts, clog, **overrides):
    """Serve ``prompts`` in two waves (request 0, then the rest: the
    sharer's prefix blocks are published by then) and check the arm.
    Returns the engine and request 0."""
    import jax.numpy as jnp
    from repro.launch import serve as launch_serve
    from repro.serve import ServeConfig
    scfg = ServeConfig(max_slots=N_REQUESTS, max_len=MAX_LEN,
                       cache_dtype=jnp.bfloat16, paged=True,
                       page_size=PAGE_SIZE, num_pages=NUM_PAGES,
                       prefill_chunk=PREFILL_CHUNK, seed=SEED, **overrides)
    with clog.phase(f"serve {name}"):
        eng = launch_serve.build_engine(params, cfg, scfg)
        done = []
        for wave in (prompts[:1], prompts[1:]):
            for p in wave:
                eng.submit(p, max_tokens=MAX_TOKENS)
            done.extend(eng.run_until_drained())
    s = eng.summary()
    log(f"  {len(done)} requests, {s['decode_tokens']:.0f} decode + "
        f"{s['prefill_tokens']:.0f} prefill tokens, "
        f"{s.get('prefix_hit_tokens', 0):.0f} prefix-hit tokens; engine "
        f"wall {s['wall_s']:.2f} s incl. compiles (smoke wall time, not a "
        f"benchmark)")
    if name == "spec_k4":
        log(f"  accept rate {s['accept_rate']:.3f}, "
            f"{s['accepted_tokens_per_tick']:.3f} tokens per slot-tick")
    short = [(r.uid, len(r.generated)) for r in done
             if not r.done or len(r.generated) != MAX_TOKENS]
    if len(done) != len(prompts) or short:
        raise RuntimeError(f"serve {name}: {len(done)}/{len(prompts)} "
                           f"finished; short (uid, tokens): {short}")
    bad = {g: s[g] for g in GUARDS if s[g] != 0}
    if bad:
        raise RuntimeError(f"serve {name}: guard counters not 0: {bad}")
    if name == "bf16" and not s["prefix_hit_tokens"] > 0:
        raise RuntimeError("serve bf16: the shared prefix was not reused")
    with clog.phase(f"serve {name} kernel check"):
        for label, kind, text in engine_programs(eng):
            check_kernels(label, text, ARM_KERNELS[name][kind])
    return eng, min(done, key=lambda r: r.uid)


def decode_logits(params, cfg, prompt, tokens, kernel: bool):
    """Logits of chunked prefill over ``prompt`` and then of decode steps
    fed ``tokens[:-1]``, one request on its own page chain, through the
    Pallas kernels or the plain XLA attention path."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tf_lib
    cfg = dataclasses.replace(cfg, decode_kernel=kernel)
    n_pages = -(-(len(prompt) + len(tokens)) // PAGE_SIZE)
    caches = tf_lib.init_paged_caches(cfg, n_pages, PAGE_SIZE, jnp.bfloat16)
    table = jnp.arange(n_pages, dtype=jnp.int32)[None]
    extend = jax.jit(lambda p, t, s, n, pt, c: tf_lib.paged_extend(
        p, cfg, t, s, n, pt, c), donate_argnums=(5,))
    decode = jax.jit(lambda p, t, pos, pt, c: tf_lib.paged_decode_step(
        p, cfg, t, pos, pt, c), donate_argnums=(4,))
    out = []
    for start in range(0, len(prompt), PREFILL_CHUNK):
        piece = prompt[start:start + PREFILL_CHUNK]
        toks = np.zeros((1, PREFILL_CHUNK), np.int32)
        toks[0, :len(piece)] = piece
        logits, caches = extend(params, toks, np.array([start], np.int32),
                                np.array([len(piece)], np.int32), table,
                                caches)
    out.append(logits[0, 0])
    for t, tok in enumerate(tokens[:-1]):
        logits, caches = decode(params, np.array([[tok]], np.int32),
                                np.array([len(prompt) + t], np.int32),
                                table, caches)
        out.append(logits[0, 0])
    return np.asarray(jnp.stack(out).astype(jnp.float32))


def check_logits(params, cfg, req) -> None:
    import jax
    tokens = req.generated[:LOGIT_STEPS]
    got = decode_logits(params, cfg, req.prompt, tokens, kernel=True)
    with jax.default_matmul_precision("highest"):
        ref = decode_logits(params, cfg, req.prompt, tokens, kernel=False)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise RuntimeError("logit check: non-finite logits")
    err = float(np.abs(got - ref).max()) / float(np.abs(ref).max())
    same = int((got.argmax(-1) == ref.argmax(-1)).sum())
    engine = int((got.argmax(-1) == np.asarray(tokens)).sum())
    log(f"  logits of request {req.uid}, {len(tokens)} steps: max |kernel - "
        f"reference| / max |reference| = {err:.3e} (limit {LOGIT_RTOL}); "
        f"argmax agrees on {same}/{len(tokens)} steps with the reference "
        f"and {engine}/{len(tokens)} with the engine's tokens")
    if not err <= LOGIT_RTOL:
        raise RuntimeError(f"logit check: relative error {err:.3e} > "
                           f"{LOGIT_RTOL}")


def serve_phase(clog) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import starcoder2_7b
    from repro.models import transformer as tf_lib
    cfg = dataclasses.replace(starcoder2_7b.make_config(),
                              repeats=SERVE_LAYERS)
    log(f"serve model: StarCoder2-7B widths, depth cut to {SERVE_LAYERS} of "
        f"{PUBLISHED_LAYERS} layers (one stage of a four-stage pipeline); "
        f"bf16 weights from seed {SEED}; paged bf16 KV, {NUM_PAGES} pages "
        f"of {PAGE_SIZE} tokens")
    with clog.phase("serve init"):
        params = tf_lib.init_lm(jax.random.PRNGKey(SEED), cfg,
                                dtype=jnp.bfloat16).params
        jax.block_until_ready(params)
    n_params = sum(math.prod(a.shape) for a in jax.tree.leaves(params))
    log(f"  {n_params / 1e9:.3f} B parameters")
    prompts = make_prompts(cfg.vocab)
    log(f"  traffic: {len(prompts)} prompts of "
        f"{sorted(len(p) for p in prompts)} tokens, requests 0 and 1 share "
        f"{SHARED_PREFIX} tokens, {MAX_TOKENS} output tokens each, prefill "
        f"chunk {PREFILL_CHUNK}")
    for name, overrides in (("bf16", {}), ("int8", {"quant": "int8"}),
                            ("spec_k4", {"spec_k": 4,
                                         "spec_drafter": "ngram"})):
        eng, first = serve_arm(name, params, cfg, prompts, clog, **overrides)
        del eng
        gc.collect()
        if name == "bf16":
            with clog.phase("serve logit check"):
                check_logits(params, cfg, first)


def train_phase(clog, device) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import starcoder2_7b
    from repro.data import DataConfig, make_pipeline
    from repro.kernels import ops as kops
    from repro.launch import train as launch_train
    from repro.models import transformer as tf_lib
    cfg = dataclasses.replace(starcoder2_7b.make_config(),
                              repeats=TRAIN_LAYERS)
    log(f"train model: StarCoder2-7B widths, depth {TRAIN_LAYERS} of "
        f"{PUBLISHED_LAYERS}; bf16 weights + fp32 AdamW master and moments; "
        f"batch 1 x {TRAIN_SEQ}, {STEPS_PER_TICK} steps per tick")
    with clog.phase("train init + compile"):
        params = tf_lib.init_lm(jax.random.PRNGKey(SEED), cfg,
                                dtype=jnp.bfloat16).params
        pipeline = make_pipeline(DataConfig(
            vocab=cfg.vocab, seq_len=TRAIN_SEQ, global_batch=1, seed=SEED))
        eng = launch_train.build_engine(params, cfg, pipeline,
                                        steps=TRAIN_STEPS,
                                        steps_per_tick=STEPS_PER_TICK)
        del params
        if not eng.model_cfg.flash_train:
            raise RuntimeError("train: the flash VJP route is off")
        block = jax.ShapeDtypeStruct((STEPS_PER_TICK, 1, TRAIN_SEQ),
                                     jnp.int32)
        compiled = eng._tick.lower(eng.params, eng.opt_state,
                                   {"tokens": block, "labels": block}
                                   ).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    limit = device.memory_stats()["bytes_limit"]
    log(f"  tick memory: arguments {ma.argument_size_in_bytes / 1e9:.2f} GB, "
        f"temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB, total "
        f"{need / 1e9:.2f} GB (compiler's peak "
        f"{ma.peak_memory_in_bytes / 1e9:.2f} GB) of {limit / 1e9:.2f} GB")
    if need > limit:
        raise RuntimeError("train: the tick does not fit the device")
    check_kernels("train tick", compiled.as_text(), TRAIN_KERNELS)
    del compiled
    with clog.phase("train run"):
        eng.run(TRAIN_STEPS)
    s = eng.summary()
    losses = [m.loss_mean for m in eng.metrics_log]
    log(f"  {s['steps']} steps in {s['ticks']} ticks, {s['tokens']} tokens, "
        f"mean loss per tick {losses}, tick wall "
        f"{[round(m.wall_s, 3) for m in eng.metrics_log]} s (smoke wall "
        f"time, not a benchmark)")
    if not all(math.isfinite(x) for m in eng.metrics_log
               for x in (m.loss, m.loss_mean, m.grad_norm)):
        raise RuntimeError(f"train: non-finite loss or grad norm: {losses}")


def main() -> None:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX found {len(devices)} "
                         f"{dev.platform!r} device(s); this check runs only "
                         f"on a TPU")
    sys.path.insert(0, str(SRC))
    from repro.core import hw
    from repro.launch import runtime
    cache = pathlib.Path(runtime.enable_compile_cache())
    entries = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    spec = hw.spec_for_kind(dev.device_kind)
    log(f"device: {dev.device_kind} x {len(devices)} ({dev.platform}); "
        f"accountant bills {spec.name}")
    log(f"compile cache: {cache} ({entries} entries at start)")
    clog = CompileLog(jax.monitoring, dev)
    serve_phase(clog)
    gc.collect()
    train_phase(clog, dev)
    log(f"peak bytes in use: {dev.memory_stats()['peak_bytes_in_use']}")
    log(f"compile total {clog.secs:.2f} s ({clog.hits} cache hits, "
        f"{clog.misses} misses)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
