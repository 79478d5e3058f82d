"""Serving launcher: batched request serving with carbon accounting.

``--smoke`` serves the arch's reduced config, on the CPU (kernels in
interpret mode) or on a TPU. The published widths run on one TPU v5e
through ``python chip_smoke.py``, which builds its engines with
:func:`build_engine` and cuts the depth to what one chip holds.
"""

from __future__ import annotations

import argparse
import json
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cfgbase
from repro.core import accounting
from repro.launch import runtime
from repro.models import transformer as tf_lib
from repro.serve import (FAULT_KINDS, FaultPlan, ProcessKilled, Scheduler,
                         SchedulerConfig, ServeConfig, ServeEngine)


def build_engine(params, cfg: tf_lib.LMConfig, scfg: ServeConfig, *,
                 policy: str = "fifo", grid_mix: str = "NY",
                 accountant: Optional[accounting.CarbonAccountant] = None
                 ) -> ServeEngine:
    """The serving engine as this launcher and ``chip_smoke.py`` build it.
    Without an ``accountant`` a new one bills the device that runs the
    engine (``runtime.accountant_device``); pass the old one to keep a
    single ledger across a warm restart."""
    if accountant is None:
        accountant = accounting.CarbonAccountant(accounting.AccountantConfig(
            device=runtime.accountant_device(),
            n_devices=jax.device_count(), grid_mix=grid_mix))
    return ServeEngine(params, cfg, scfg, accountant=accountant,
                       scheduler=Scheduler(SchedulerConfig(policy=policy)))


def validate_args(ap: argparse.ArgumentParser,
                  args: argparse.Namespace) -> None:
    """Reject nonsensical flag combinations with actionable messages BEFORE
    any device work — the engine would also raise, but deep in __init__
    with a traceback instead of a usage line (DESIGN.md §17 satellite)."""
    if args.spec_k < 0:
        ap.error(f"--spec-k must be >= 0, got {args.spec_k}")
    if args.page_size <= 0:
        ap.error(f"--page-size must be > 0, got {args.page_size}")
    if args.prefill_chunk < 0:
        ap.error(f"--prefill-chunk must be >= 0, got {args.prefill_chunk}")
    if (args.paged and args.prefill_chunk > 0
            and args.prefill_chunk % args.page_size != 0):
        ap.error(f"--prefill-chunk ({args.prefill_chunk}) must be a "
                 f"multiple of --page-size ({args.page_size}) in paged "
                 f"mode: chunk boundaries must land on page boundaries")
    if not (0.0 <= args.compact_threshold <= 1.0):
        ap.error(f"--compact-threshold must be in [0, 1], got "
                 f"{args.compact_threshold}")
    if args.num_pages is not None and args.num_pages <= 0:
        ap.error(f"--num-pages must be > 0, got {args.num_pages}")
    if args.spec_k > 0 and not args.paged:
        ap.error("--spec-k requires --paged (speculative decode runs on "
                 "the paged path only)")
    if args.fault_kind is not None and args.fault_tick < 0:
        ap.error(f"--fault-tick must be >= 0, got {args.fault_tick}")
    if args.deadline_ticks is not None and args.deadline_ticks <= 0:
        ap.error(f"--deadline-ticks must be > 0, got {args.deadline_ticks}")
    if args.nbest < 1:
        ap.error(f"--nbest must be >= 1, got {args.nbest}")
    if args.nbest > 1 and not args.paged:
        ap.error("--nbest requires --paged (n-best sampling forks the "
                 "paged KV cache, DESIGN.md §18)")
    if args.nbest > args.slots:
        ap.error(f"--nbest ({args.nbest}) cannot exceed --slots "
                 f"({args.slots}): every fork decodes concurrently")
    if args.spec_tree_m < 1:
        ap.error(f"--spec-tree-m must be >= 1, got {args.spec_tree_m}")
    if args.spec_tree_m > 1 and args.spec_k <= 0:
        ap.error("--spec-tree-m > 1 requires --spec-k > 0 (tree "
                 "speculation rides the speculative verify pass)")
    if args.spec_tree_m > 1 and args.spec_drafter != "ngram":
        ap.error("--spec-tree-m > 1 drafts with the ngram drafter only")
    if args.checkpoint_interval < 0:
        ap.error(f"--checkpoint-interval must be >= 0, got "
                 f"{args.checkpoint_interval}")
    if args.checkpoint_interval > 0 and args.checkpoint_dir is None:
        ap.error("--checkpoint-interval requires --checkpoint-dir "
                 "(snapshots need somewhere durable to land, "
                 "DESIGN.md §19)")
    if args.resume and args.checkpoint_dir is None:
        ap.error("--resume requires --checkpoint-dir (restore loads the "
                 "snapshot + journal written there)")
    if args.fault_kind == "process_kill" and args.checkpoint_dir is None:
        ap.error("--fault-kind process_kill requires --checkpoint-dir: "
                 "the kill is only survivable with a snapshot + journal "
                 "to restart from (DESIGN.md §19)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--grid-mix", default="NY")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--policy", default="fifo",
                    choices=("fifo", "longest_prompt"))
    ap.add_argument("--quant", default="none", choices=("none", "int8"),
                    help="int8: serve through the quantized fast path "
                         "(int8 weights + int8 KV cache, DESIGN.md §12)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache with prefix reuse (DESIGN.md §14)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool capacity in pages (default: dense-equivalent)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable content-hash prefix block reuse")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="admit prompts in chunks of this many tokens, "
                         "interleaved with decode ticks (0 = whole prompt)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode: draft this many tokens per "
                         "tick and verify them in one multi-query pass "
                         "(paged mode only, DESIGN.md §15; 0 = off)")
    ap.add_argument("--spec-drafter", default="ngram",
                    choices=("ngram", "oracle"),
                    help="ngram: prompt-lookup self-drafting (near-free); "
                         "oracle: the target model drafts itself (parity "
                         "harness)")
    ap.add_argument("--spec-tree-m", type=int, default=1,
                    help="tree speculation: verify this many independent "
                         "draft branches per slot in the one multi-query "
                         "pass and commit the longest-accepted branch "
                         "(requires --spec-k, ngram drafter; DESIGN.md "
                         "§18; 1 = linear)")
    ap.add_argument("--nbest", type=int, default=1,
                    help="fork each request into this many decode streams "
                         "sharing prompt KV pages copy-on-write; stream 0 "
                         "is the canonical greedy stream (paged mode, "
                         "DESIGN.md §18; 1 = off)")
    ap.add_argument("--compact-threshold", type=float, default=0.0,
                    help="compact a slot's private page suffix into a "
                         "contiguous run when its page-table fragmentation "
                         "reaches this score in [0, 1] (paged mode, "
                         "DESIGN.md §16; 0 = compaction off)")
    ap.add_argument("--evict-policy", default="lru",
                    choices=("lru", "cost"),
                    help="parked-prefix reclamation: lru evicts the least-"
                         "recently-parked block; cost evicts the cheapest-"
                         "to-recompute block first (recompute FLOPs per "
                         "byte, DESIGN.md §16)")
    ap.add_argument("--fault-kind", default=None, choices=FAULT_KINDS,
                    help="chaos tier (DESIGN.md §17): inject one seeded "
                         "fault of this kind and exercise the degradation "
                         "ladder (default: no injection)")
    ap.add_argument("--fault-tick", type=int, default=2,
                    help="engine tick at which the fault fires")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the fault payload (reproducible chaos)")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="per-request deadline in ticks; overdue queued "
                         "requests are shed, not served late")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="durability tier (DESIGN.md §19): journal every "
                         "admission (fsync'd) and snapshot engine state "
                         "here; a killed engine warm-restarts "
                         "token-identically via --resume")
    ap.add_argument("--checkpoint-interval", type=int, default=0,
                    help="snapshot every N ticks (0 = journal only; "
                         "requires --checkpoint-dir). Smaller = less "
                         "replay after a crash, more write energy")
    ap.add_argument("--resume", action="store_true",
                    help="restore from --checkpoint-dir before serving: "
                         "load the latest snapshot, replay the journal "
                         "tail, resume mid-stream requests exactly")
    args = ap.parse_args()
    validate_args(ap, args)

    if not args.smoke:
        raise SystemExit("this launcher serves the reduced config only; pass "
                         "--smoke. The published widths run on one TPU v5e "
                         "through `python chip_smoke.py` (depth cut to fit "
                         "the chip).")
    runtime.enable_compile_cache()
    arch = cfgbase.get(args.arch)
    if arch.kind != "lm":
        raise SystemExit(f"serve launcher supports LM archs; {args.arch} is "
                         f"{arch.kind}")
    cfg = arch.make_smoke()
    params = tf_lib.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32).params
    scfg = ServeConfig(max_slots=args.slots, max_len=256,
                       temperature=args.temperature,
                       quant=args.quant, paged=args.paged,
                       page_size=args.page_size,
                       num_pages=args.num_pages,
                       prefix_cache=not args.no_prefix_cache,
                       prefill_chunk=args.prefill_chunk,
                       spec_k=args.spec_k,
                       spec_drafter=args.spec_drafter,
                       spec_tree_m=args.spec_tree_m,
                       compact_threshold=args.compact_threshold,
                       evict_policy=args.evict_policy,
                       faults=(FaultPlan.single(
                           args.fault_kind, tick=args.fault_tick,
                           seed=args.fault_seed)
                           if args.fault_kind else None),
                       checkpoint_dir=args.checkpoint_dir,
                       checkpoint_interval=args.checkpoint_interval)

    eng = build_engine(params, cfg, scfg, policy=args.policy,
                       grid_mix=args.grid_mix)
    acct = eng.accountant
    done = []
    if args.resume:
        done.extend(eng.restore())
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=rng.integers(4, 12))
        eng.submit(prompt, max_tokens=args.max_tokens,
                   deadline_ticks=args.deadline_ticks,
                   n_best=args.nbest)
    while True:
        try:
            done.extend(eng.run_until_drained())
            break
        except ProcessKilled as e:
            # simulated crash (DESIGN.md §19): the old engine object is
            # dead — restart purely from disk and keep serving
            print(f"engine killed ({e}); warm-restarting from "
                  f"{args.checkpoint_dir}")
            eng = build_engine(params, cfg, scfg, policy=args.policy,
                               accountant=acct)
            done.extend(eng.restore())
    # restore delivery is at-least-once: dedupe by uid, keep stream order
    done = sorted({r.uid: r for r in done}.values(), key=lambda r: r.uid)
    for r in done:
        print(f"req {r.uid}: prompt_len={len(r.prompt)} -> {r.generated}")
        if r.nbest is not None:
            for i, alt in enumerate(r.nbest[1:], start=1):
                print(f"  nbest[{i}]: {alt}")
    s = eng.summary()
    rep = acct.report()
    print(f"serve: {s['ticks']} ticks, {s['decode_tokens']:.0f} decode toks "
          f"({s['decode_tokens_per_s']:.1f} tok/s), "
          f"{s['prefill_tokens']:.0f} prefill toks")
    jpt = rep.get("j_per_token")
    if jpt is not None:
        print(f"live J/token: {jpt:.3f}")
    mjpt = rep.get("modeled_j_per_token")
    if mjpt is not None:
        print(f"modeled (FLOPs+DRAM) J/token: {mjpt:.3e} "
              f"({rep['bytes_moved']:.3g} bytes moved)")
    if args.paged:
        print(f"prefix cache: {rep['prefix_hit_rate']:.1%} hit rate "
              f"({rep['prefix_hit_tokens']:.0f} prompt tokens reused), "
              f"saved {rep['saved_bytes']:.3g} KV bytes "
              f"= {rep['saved_dram_j']:.3e} J DRAM")
        print(f"long-context: {rep['prefill_gather_bytes']:.3g} prefill "
              f"gather bytes = {rep['prefill_gather_dram_j']:.3e} J DRAM, "
              f"{rep['compaction_moves']:.0f} pages compacted")
    if args.paged and (args.nbest > 1 or s["cow_copies"] > 0):
        print(f"copy-on-write: {s['forks']:.0f} forks, "
              f"{s['cow_copies']:.0f} page copies "
              f"({rep.get('cow_bytes', 0.0):.3g} bytes = "
              f"{rep.get('cow_dram_j', 0.0):.3e} J DRAM), saved "
              f"{rep.get('fork_saved_bytes', 0.0):.3g} duplicate KV bytes "
              f"= {rep.get('fork_saved_dram_j', 0.0):.3e} J DRAM")
    if args.fault_kind is not None:
        print(f"chaos ({args.fault_kind}@{args.fault_tick}): "
              f"{s['faults_injected']} injected, {s['quarantined']} "
              f"quarantined, {s['shed']} shed, recovery "
              f"{s['recovery_j']:.3e} J ({s['recovery_tokens']} toks), "
              f"{s['degraded_ticks']} degraded ticks")
    if args.checkpoint_dir is not None:
        print(f"durability: {s['snapshots_taken']:.0f} snapshots "
              f"({s['snapshot_bytes']:.3g} B) + journal "
              f"{s['journal_bytes']:.3g} B = "
              f"{s['durability_write_j']:.3e} J writes; replayed "
              f"{s['replayed_ticks']:.0f} ticks on restore "
              f"({s['restore_j']:.3e} J)")
    if args.spec_k > 0:
        print(f"speculative decode (k={args.spec_k}, "
              f"{args.spec_drafter}): {s['accept_rate']:.1%} accept rate, "
              f"{s['accepted_tokens_per_tick']:.2f} emitted "
              f"tokens/slot-tick, J/accepted-token "
              f"{rep['spec']['j_per_accepted_token']:.3e}")
    print("carbon report:", json.dumps(rep, default=float))


if __name__ == "__main__":
    main()
