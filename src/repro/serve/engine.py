"""Device-resident continuous-batching serve core.

One jitted **engine tick** does everything on device: the batched decode step
over the shared slot-major KV cache (per-slot positions — no expand/squeeze
vmap tricks), sampling (greedy + per-slot temperature with per-slot PRNG
keys), token/position advance, EOS/max-token done flags, and a device-side
output ring buffer. The host reads back ONE compact (max_slots,) finished
mask per tick; generated tokens leave the device only when a request
finishes. Throughput and J/token are therefore properties of the hardware,
not of Python overhead (the paper's operational-energy argument, measured on
the live path).

Admission is batched too: the scheduler (serve/scheduler.py) picks queued
requests, the engine pads-and-stacks them into ONE prefill call and scatters
every admitted slot's cache rows at once.

Every tick produces a :class:`StepMetrics` billed to the CarbonAccountant,
so J/token is a first-class live serving metric.

The host-loop baseline this replaces lives on as serve/reference.py (the
correctness oracle and the benchmark's "before").
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
from repro.core import accounting, energy
from repro.models import transformer as tf_lib
from repro.serve import spec as spec_lib
from repro.serve.faults import (FaultInjector, FaultPlan, GuardrailConfig,
                                ProcessKilled, corrupt_kv_page)
from repro.serve.pages import ROOT, PagePool, block_tokens, fragmentation
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.serve.snapshot import (Journal, check_fingerprint,
                                  host_state_dict, install_host_state,
                                  reconcile_ownership)
from repro.train.ft import Ewma

PyTree = Any

# sentinel stream for a fork cancelled at activation (parent finished on
# its first token): resolved to a copy of stream 0 when the group closes
_FORK_MIRROR = object()


@dataclasses.dataclass
class ServeConfig:
    max_slots: int = 4
    max_len: int = 512
    eos_id: int = -1          # -1: never; sampling stops at max_tokens
    temperature: float = 0.0  # default per-request temperature; 0 = greedy
    cache_dtype: Any = jnp.float32
    seed: int = 0
    # route batched decode attention through the Pallas decode kernel
    # (kernels/decode_attention.py). None = auto: on for TPU backends, off
    # elsewhere (interpret mode is correctness-only).
    decode_kernel: Optional[bool] = None
    # quantized serving fast path (DESIGN.md §12): "none" | "int8".
    # int8 quantizes the weight tree (per-channel scales) AND the KV cache
    # (per-token/head scales); cache_dtype is ignored for K/V in that mode.
    quant: str = "none"
    # paged KV cache + prefix reuse + chunked prefill (DESIGN.md §14):
    paged: bool = False
    page_size: int = 16       # tokens per KV page (block granularity)
    # pool capacity in pages. None = dense-equivalent:
    # max_slots * ceil(max_len / page_size) — prefix sharing then *raises*
    # effective capacity; smaller pools admit by deferral.
    num_pages: Optional[int] = None
    # content-matched block reuse at admission: a hit copies page-table
    # entries instead of recomputing the shared prefix's prefill
    prefix_cache: bool = True
    # admit long prompts in chunks of this many tokens, interleaved with
    # decode ticks (bounds tick-time tail latency). 0 = whole suffix in
    # one extend call.
    prefill_chunk: int = 0
    # speculative multi-token decode on the paged path (DESIGN.md §15):
    # draft spec_k tokens per slot per tick, verify all of them in ONE
    # multi-query pass through the page table, commit the accepted prefix
    # plus a correction/bonus token. 0 = off. Requires paged=True.
    spec_k: int = 0
    # "ngram": device-resident prompt-lookup drafter over each slot's own
    # token history (near-zero draft cost); "oracle": the target model
    # drafts greedily — k extra decode passes, the accept-all parity
    # harness, not an energy win (serve/spec.py).
    spec_drafter: str = "ngram"
    # tree speculation (DESIGN.md §18): draft spec_tree_m independent
    # k-token branches per slot per tick over COW-forked page tables and
    # verify ALL of them in the one multi-query pass (branches fold into
    # batch rows); the longest-accepted branch commits, the rest release.
    # 1 = linear speculation (the §15 behavior, bit-identical). Requires
    # spec_k > 0. Branches beyond the first apply to greedy slots only —
    # temperature slots keep the distribution-exact linear path on branch
    # 0, because multi-branch rejection sampling would need a joint
    # residual scheme to stay unbiased.
    spec_tree_m: int = 1
    # long-context tier (DESIGN.md §16):
    # compact a live slot's private page suffix into a contiguous run when
    # its table's fragmentation score (serve/pages.py:fragmentation)
    # reaches this threshold; 0.0 = compaction off. One slot per tick.
    compact_threshold: float = 0.0
    # park reclamation: "lru" | "cost" (evict the cheapest-to-recompute
    # cached block first, scored by costing.block_recompute_flops per byte)
    evict_policy: str = "lru"
    # chaos tier (DESIGN.md §17): a seeded fault schedule to replay
    # against this engine (None = no injection), and the guardrail knobs
    # that arm detection/degradation rungs. All-default guard keeps the
    # pre-chaos behavior exactly; the numerics sentinel is always on (it
    # rides the existing packed readback for free).
    faults: Optional[FaultPlan] = None
    guard: GuardrailConfig = dataclasses.field(
        default_factory=GuardrailConfig)
    # durability tier (DESIGN.md §19): directory for crash-consistent
    # snapshots + the write-ahead request journal (None = durability off,
    # the pre-§19 behavior exactly). checkpoint_interval > 0 snapshots the
    # full engine state every N completed ticks — the knob trades snapshot
    # write J/token against recovery replay J (restore_j): shorter
    # intervals write more, replay less.
    checkpoint_dir: Optional[str] = None
    checkpoint_interval: int = 0


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_tokens: int = 16
    temperature: Optional[float] = None   # None -> ServeConfig.temperature
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # per-request deadline (DESIGN.md §17): shed from the queue once
    # ``deadline_ticks`` engine ticks have passed since ``submit_tick``
    # without admission (None = wait forever). The engine stamps
    # ``submit_tick``; it also feeds the scheduler's queue-aging term.
    deadline_ticks: Optional[int] = None
    submit_tick: int = -1
    # n-best sampling over COW forks (DESIGN.md §18): a submission with
    # n_best > 1 admits ONE prefill and fans out to n_best slots sharing
    # the prompt's committed pages; the parent request completes only when
    # every fork's stream is in, with ``nbest`` holding all of them
    # (``generated`` aliases stream 0). Fork-internal requests (children,
    # continuations of forks) carry ``fork_group`` (the parent's uid) and
    # their ``fork_idx``; they are never returned to the caller directly.
    n_best: int = 1
    nbest: Optional[List[List[int]]] = None
    fork_group: Optional[int] = None
    fork_idx: int = 0


@dataclasses.dataclass
class StepMetrics:
    """What one engine tick did — the unit core/accounting.py bills."""
    tokens: int                 # decode tokens produced this tick
    active_slots: int           # slots decoding this tick
    wall_s: float               # host wall time of the tick (incl. admission)
    prefill_tokens: int = 0     # prompt tokens prefilled this tick
    admitted: int = 0           # requests admitted this tick
    queue_depth: int = 0        # requests still waiting after the tick
    # dtype-aware modeled traffic/compute of the tick (engine-computed from
    # the actual resident array sizes; the paper's bytes-dominate-energy
    # argument made measurable — CarbonAccountant bills a per-byte DRAM
    # term from these alongside the FLOPs term)
    weight_bytes: float = 0.0   # parameter bytes streamed from HBM
    kv_bytes: float = 0.0       # KV-cache bytes read/written
    flops: float = 0.0          # modeled FLOPs
    # prefix-cache effect of this tick's admission (DESIGN.md §14): prompt
    # tokens served from cached pages, and the traffic/compute the dense
    # path would have billed for them — the sustainability win, first-class
    prefix_hit_tokens: int = 0  # prompt tokens reused via prefix-cache hits
    saved_bytes: float = 0.0    # KV write bytes NOT moved thanks to reuse
    saved_flops: float = 0.0    # prefill FLOPs NOT executed thanks to reuse
    # speculative decode split (DESIGN.md §15): in spec mode ``tokens`` is
    # the EMITTED count (accepted drafts + correction/bonus) and the tick's
    # decode traffic/compute is additionally billed per phase — the drafter
    # and the verification pass are different energy stories (an n-gram
    # drafter is nearly free; the oracle drafter streams weights k times)
    spec_draft_tokens: int = 0      # tokens drafted this tick (k * active)
    spec_accepted_tokens: int = 0   # emitted beyond the 1/tick baseline
    draft_flops: float = 0.0
    draft_bytes: float = 0.0        # drafter DRAM traffic (incl. weights)
    verify_flops: float = 0.0
    verify_bytes: float = 0.0       # verify DRAM traffic (incl. weights)
    # long-context tier (DESIGN.md §16): the cached-window gather term of
    # this tick's prefill — the bytes the extend path actually moved to
    # read KV behind the in-flight chunk (kernel path: page-granular
    # ceil(start/page_size) pages per row; XLA fallback: the whole-table
    # materialization _paged_gather really performs). Included in
    # ``kv_bytes``; broken out because it is the fragmentation-sensitive
    # channel the paged prefill kernel exists to bound.
    prefill_gather_bytes: float = 0.0
    compaction_moves: int = 0       # pages relocated by compaction this tick
    # resilience tier (DESIGN.md §17): what the chaos layer did to this
    # tick and what recovery cost. ``recovery_*`` bill the re-prefill of
    # quarantined slots' context — energy the fault-free run never spends,
    # reported first-class ("On the Sustainability of AI Inferences in
    # the Edge", PAPERS.md). ``degraded`` marks a tick served under any
    # active ladder rung (reduced spec-k, fp fallback, compaction pause).
    faults_injected: int = 0
    quarantined: int = 0            # slots torn down by the sentinel
    shed: int = 0                   # requests deadline-/retry-shed
    recovery_tokens: int = 0        # prompt tokens re-prefilled for recovery
    recovery_flops: float = 0.0
    recovery_bytes: float = 0.0
    degraded: int = 0               # 1 if any degradation rung was active
    readback_retries: int = 0       # re-reads of a garbled/dropped readback
    # copy-on-write tier (DESIGN.md §18): first-class channels for the
    # fork economy. ``cow_bytes`` is real traffic (a shared page copied
    # before a divergent write — read + write of one page, also included
    # in ``kv_bytes``); ``fork_saved_*`` is the duplicate-KV bill a fork
    # did NOT pay (the prompt KV bytes + prefill FLOPs an independent
    # duplicate admission of the same stream would have spent).
    cow_bytes: float = 0.0
    cow_copies: int = 0
    forks: int = 0                  # fork children activated this tick
    fork_saved_bytes: float = 0.0
    fork_saved_flops: float = 0.0

    @property
    def bytes_moved(self) -> float:
        return self.weight_bytes + self.kv_bytes


# StepMetrics fields that are deliberately NOT energy channels — pure
# occupancy/queue observability with no joule interpretation. Everything
# else MUST have a bill site in CarbonAccountant.observe_serve; the
# accounting-completeness lint pass (repro-lint L401, DESIGN.md §20)
# fails CI on any field that is neither billed nor listed here, so a new
# channel can never ship half-wired.
ACCOUNTING_EXEMPT = frozenset({"active_slots", "admitted", "queue_depth"})


@dataclasses.dataclass
class _AdmitInfo:
    """What one admission pass did + its modeled traffic/compute bill."""
    admitted: int = 0           # requests newly selected this tick
    prefill_tokens: int = 0     # prompt tokens actually computed this tick
    weight_passes: int = 0      # extra weight-tree streams (0 or 1)
    kv_bytes: float = 0.0
    flops: float = 0.0
    prefix_hit_tokens: int = 0
    saved_bytes: float = 0.0
    saved_flops: float = 0.0
    gather_bytes: float = 0.0   # cached-window gather share of kv_bytes
    # requests in this pass's prefill or extend call, as the profile's
    # ``serve.admit`` span names them
    uids: Any = ()
    # recovery share of the above (DESIGN.md §17): rows re-prefilling a
    # quarantined slot's context bill their exact per-row cost here too
    recovery_tokens: int = 0
    recovery_flops: float = 0.0
    recovery_bytes: float = 0.0


@dataclasses.dataclass
class DeviceState:
    """All per-slot serving state, resident on device between ticks."""
    caches: PyTree
    tok: jnp.ndarray            # (B,)  last token per slot
    pos: jnp.ndarray            # (B,)  next cache write position per slot
    gen: jnp.ndarray            # (B,)  tokens generated per slot
    budget: jnp.ndarray         # (B,)  max_tokens per slot
    active: jnp.ndarray         # (B,)  bool
    temp: jnp.ndarray           # (B,)  per-slot sampling temperature
    rng: jnp.ndarray            # (B, 2) per-slot PRNG keys (uint32)
    out_buf: jnp.ndarray        # (B, max_len) device-side output ring buffer
    # paged mode: (B, NB) logical-block -> physical-page map (serve/pages.py
    # owns allocation; entries past a slot's pages point at the sink page).
    # dense mode: (B, 0) placeholder.
    page_table: jnp.ndarray = None
    # speculative mode: (B, max_len) full token history per slot (prompt +
    # emitted), valid through pos inclusive — hist[b, pos[b]] is the
    # pending token. The n-gram drafter's lookup corpus. (B, 0) otherwise.
    hist: jnp.ndarray = None


jax.tree_util.register_dataclass(
    DeviceState,
    data_fields=["caches", "tok", "pos", "gen", "budget", "active", "temp",
                 "rng", "out_buf", "page_table", "hist"],
    meta_fields=[])


def _batch_axis_tree(caches: PyTree) -> PyTree:
    """Batch axis per cache leaf: pattern caches carry batch at axis 1 (the
    stacked layer dim leads); tail caches at axis 0."""
    def per_key(key, sub):
        ax = 1 if key.startswith("pat") else 0
        return jax.tree.map(lambda _: ax, sub)
    return {k: per_key(k, v) for k, v in caches.items()}


def _bucket_len(n: int, cap: Optional[int] = None) -> int:
    """Pad prompt-batch length to a pow2 bucket (bounds prefill recompiles).

    ``cap`` clamps the bucket ladder at the configured maximum (max prompt
    length for dense admission, the chunk size for chunked prefill) — the
    executable cache then holds at most ``log2(cap)`` entries, and with
    chunked prefill one chunk-size bucket is the steady state
    (tests/test_serve_paged.py::TestBucketCap).
    """
    b = 4
    while b < n:
        b *= 2
    return min(b, cap) if cap is not None else b


# -- modeled traffic / compute (DESIGN.md §12) --------------------------------
# Shared with the train engine: models/costing.py is the single cost model
# (these aliases keep the engine's call sites and tests stable).

from repro.models import costing
from repro.models.costing import (attn_layers as _attn_layers,
                                  kv_bytes as _kv_bytes,
                                  matmul_weight_elems as _matmul_weight_elems,
                                  tree_bytes as _tree_bytes)


def _step_span(step):
    """Opens the profiler's step span ``serve`` (numbered by tick) around
    one call of ``ServeEngine.step``."""
    @functools.wraps(step)
    def traced(self):
        with jax.profiler.StepTraceAnnotation("serve",
                                              step_num=self._tick_idx):
            return step(self)
    return traced


class ServeEngine:
    def __init__(self, params: PyTree, cfg: tf_lib.LMConfig,
                 serve_cfg: ServeConfig,
                 accountant: Optional[accounting.CarbonAccountant] = None,
                 scheduler: Optional[Scheduler] = None):
        use_kernel = serve_cfg.decode_kernel
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        if serve_cfg.quant not in ("none", "int8"):
            raise ValueError(f"unknown quant mode {serve_cfg.quant!r}")
        if serve_cfg.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {serve_cfg.spec_k}")
        if serve_cfg.spec_k > 0 and not serve_cfg.paged:
            raise ValueError("speculative decode (spec_k > 0) runs on the "
                             "paged path only; set paged=True")
        if serve_cfg.spec_drafter not in spec_lib.DRAFTERS:
            raise ValueError(f"unknown drafter {serve_cfg.spec_drafter!r}; "
                             f"expected one of {spec_lib.DRAFTERS}")
        if serve_cfg.spec_tree_m < 1:
            raise ValueError(f"spec_tree_m must be >= 1, got "
                             f"{serve_cfg.spec_tree_m}")
        if serve_cfg.spec_tree_m > 1 and serve_cfg.spec_k <= 0:
            raise ValueError("tree speculation (spec_tree_m > 1) rides the "
                             "speculative verify pass; set spec_k > 0")
        if serve_cfg.spec_tree_m > 1 and serve_cfg.spec_drafter != "ngram":
            raise ValueError("tree speculation drafts with the ngram "
                             "drafter only (the oracle drafter is a linear "
                             "parity harness)")
        if (serve_cfg.paged and serve_cfg.prefill_chunk
                and serve_cfg.prefill_chunk % serve_cfg.page_size != 0):
            raise ValueError(
                f"prefill_chunk ({serve_cfg.prefill_chunk}) must be a "
                f"multiple of page_size ({serve_cfg.page_size}): a chunk "
                f"boundary inside a page would split block publication")
        if not 0.0 <= serve_cfg.compact_threshold <= 1.0:
            raise ValueError(f"compact_threshold must be in [0, 1], got "
                             f"{serve_cfg.compact_threshold}")
        if serve_cfg.checkpoint_interval < 0:
            raise ValueError(f"checkpoint_interval must be >= 0, got "
                             f"{serve_cfg.checkpoint_interval}")
        if (serve_cfg.checkpoint_interval > 0
                and serve_cfg.checkpoint_dir is None):
            raise ValueError("checkpoint_interval > 0 requires a "
                             "checkpoint_dir to write snapshots into")
        self.scfg = serve_cfg
        self.guard = serve_cfg.guard
        self.accountant = accountant
        self.scheduler = scheduler or Scheduler(SchedulerConfig())
        self._base_key = jax.random.PRNGKey(serve_cfg.seed)
        self._use_kernel = bool(use_kernel)
        # the fp oracle pair (pre-quantization params + config): the
        # quarantine re-decode path and the int8->fp fallback rung both
        # rebuild from it (DESIGN.md §17)
        self._oracle = (params, dataclasses.replace(
            cfg, decode_kernel=self._use_kernel))
        if serve_cfg.quant == "int8":
            # quantized fast path: int8 weight tree + int8 KV cache; the
            # already-quantized case (caller ran quantize_lm) passes through
            cfg = dataclasses.replace(cfg, quant=tf_lib.INT8_QUANT)
            params = tf_lib.quantize_lm(params)
        # host mirrors that survive a runtime rebuild
        self._uid = 0
        self._fit_checked: set = set()
        # instrumentation (tests assert the tick stays fused: one trace,
        # one host readback per tick; admission compiles once per length
        # bucket). Cumulative across fp-fallback rebuilds.
        self.tick_trace_count = 0
        self.host_readbacks = 0
        self.admit_trace_counts: Dict[int, int] = {}
        self.compact_trace_count = 0
        self.cow_trace_count = 0
        self.fork_trace_count = 0
        # n-best fork groups (DESIGN.md §18) survive runtime rebuilds: a
        # group's members may be requeued as continuations by the fp
        # fallback and finish on the rebuilt engine.
        # group uid -> {"req": parent, "k": fan-out, "streams": {idx: toks}}
        self._fork_groups: Dict[int, Dict[str, Any]] = {}
        self.last_metrics: Optional[StepMetrics] = None
        self.metrics_log: List[StepMetrics] = []
        # chaos tier state (DESIGN.md §17)
        self._injector = (FaultInjector(serve_cfg.faults)
                          if serve_cfg.faults is not None else None)
        self._tick_idx = 0
        self._cur_spec_k = serve_cfg.spec_k
        self._fell_back = False
        self._recovery: Dict[int, Dict[str, Any]] = {}
        self._recovering: set = set()
        self._pending_shed: List[Request] = []
        self._defer_counts: Dict[int, int] = {}
        self._retry_after: Dict[int, int] = {}
        self._spike_holds: List[Tuple[int, List[int]]] = []
        self._tick_wall_ewma = Ewma(alpha=self.guard.ewma_alpha)
        self._accept_ewma = Ewma(alpha=self.guard.ewma_alpha)
        self._drift_ewma = Ewma(alpha=self.guard.ewma_alpha)
        self._compact_pause_until = 0
        self._drift_rr = 0
        self._tick_shed = 0
        self._tick_quarantined = 0
        self._rb_retries_tick = 0
        self.n_quarantined = 0
        self.n_shed = 0
        self.n_finished_ok = 0
        self.spec_backoffs = 0
        self.fp_fallbacks = 0
        self.compaction_pauses = 0
        self.audit_failures = 0
        self.audit_log: List[str] = []
        self.readback_retries_total = 0
        # durability tier (DESIGN.md §19): snapshot manager + write-ahead
        # journal. Synchronous saves — a snapshot must be on disk before
        # the tick that follows it can be journaled as replayable-after.
        self._ckpt_mgr: Optional[CheckpointManager] = None
        self._journal: Optional[Journal] = None
        if serve_cfg.checkpoint_dir is not None:
            self._ckpt_mgr = CheckpointManager(CheckpointConfig(
                directory=os.path.join(serve_cfg.checkpoint_dir,
                                       "snapshots"),
                async_save=False))
            self._journal = Journal(os.path.join(serve_cfg.checkpoint_dir,
                                                 "journal.jsonl"))
        # replay mode: journaling/snapshotting suppressed, recompute billed
        # to the restore_* channels instead of silently folded into serve
        self._replaying = False
        # ticks at or before this index already fired their process_kill
        # (the crash a restore recovered from); -1 = fresh engine
        self._restore_boundary = -1
        self.snapshots_taken = 0
        self.snapshot_bytes_total = 0.0
        self.journal_bytes_total = 0.0
        self.replayed_ticks = 0
        self.restore_flops = 0.0
        self.restore_bytes = 0.0
        self._init_runtime(params, cfg)

    def _init_runtime(self, params: PyTree, cfg: tf_lib.LMConfig) -> None:
        """(Re)build every device-resident and device-coupled structure:
        pool, caches, slot state, cost-model scalars, compiled tick/admit
        executables. Called once from ``__init__`` and again by the
        int8->fp fallback rung (DESIGN.md §17), which swaps in the fp
        oracle params after capturing all live slots as continuations —
        queue, accounting, and instrumentation counters survive."""
        serve_cfg = self.scfg
        self.params = params
        self.cfg = dataclasses.replace(cfg, decode_kernel=self._use_kernel)
        cfg = self.cfg
        b, cap = serve_cfg.max_slots, serve_cfg.max_len
        base_key = self._base_key
        if serve_cfg.paged:
            # paged KV subsystem (DESIGN.md §14): a shared block pool
            # replaces the per-slot dense cache; serve/pages.py owns
            # allocation/refcounts/prefix registry on the host
            if not tf_lib.paged_supported(self.cfg):
                raise NotImplementedError(
                    "paged serving is attention-only (no SSD/hybrid) and "
                    "incompatible with ring caches")
            ps = serve_cfg.page_size
            self._blocks_per_slot = -(-cap // ps)
            n_pages = serve_cfg.num_pages
            if n_pages is None:
                n_pages = b * self._blocks_per_slot
            # block_cost is attached below, once the cost-model scalars
            # (matmul elems, attn dims, per-token KV bytes) exist
            self.pool = PagePool(n_pages, ps,
                                 evict_policy=serve_cfg.evict_policy)
            caches = tf_lib.init_paged_caches(self.cfg, n_pages, ps,
                                              serve_cfg.cache_dtype)
            page_table = jnp.full((b, self._blocks_per_slot),
                                  self.pool.sink, jnp.int32)
        else:
            self.pool = None
            caches = tf_lib.init_caches(self.cfg, b, cap,
                                        serve_cfg.cache_dtype)
            page_table = jnp.zeros((b, 0), jnp.int32)
        self.state = DeviceState(
            caches=caches,
            tok=jnp.zeros(b, jnp.int32),
            pos=jnp.zeros(b, jnp.int32),
            gen=jnp.zeros(b, jnp.int32),
            budget=jnp.zeros(b, jnp.int32),
            active=jnp.zeros(b, bool),
            temp=jnp.zeros(b, jnp.float32),
            rng=jax.vmap(lambda i: jax.random.fold_in(base_key, i))(
                jnp.arange(b)),
            out_buf=jnp.zeros((b, cap), jnp.int32),
            page_table=page_table,
            # token history only exists in speculative mode (the n-gram
            # drafter's corpus); zero-width otherwise so the tick carries
            # no dead weight
            hist=jnp.zeros((b, cap if serve_cfg.spec_k > 0 else 0),
                           jnp.int32))
        # host mirrors (admission + finished-mask readbacks keep them exact;
        # no per-slot device transfers needed)
        self.slot_req: List[Optional[Request]] = [None] * b
        self._host_gen = [0] * b
        # paged host mirrors: pages owned per slot (released at finish) and
        # in-flight chunked prefills {slot: {"req", "next", "plen", ...}}
        self._slot_pages: List[List[int]] = [[] for _ in range(b)]
        self._prefilling: Dict[int, Dict[str, Any]] = {}
        # COW fork mirrors (DESIGN.md §18), slot-scoped so a runtime
        # rebuild resets them: child slots reserved for a parent still
        # mid-prefill (excluded from the active set until the fork), and
        # parent slot -> its reserved children
        self._fork_wait: Dict[int, int] = {}
        self._fork_children: Dict[int, List[int]] = {}
        # tree speculation: this tick's staged branch windows,
        # slot -> (window_lo, window_hi, [branch pages or None] * (m-1))
        self._tree_branches: Dict[int, Tuple[int, int, list]] = {}
        # per-tick COW accumulators (reset in step(), billed via
        # StepMetrics)
        self._tick_cow_bytes = 0.0
        self._tick_cow_copies = 0
        self._tick_forks = 0
        self._tick_fork_saved_bytes = 0.0
        self._tick_fork_saved_flops = 0.0
        # any injector page holds referenced the previous pool
        self._spike_holds = []
        # cached all-zero poison vector: the fault-free tick passes it by
        # reference (no per-tick host->device churn)
        self._zero_poison = jnp.zeros(b, jnp.float32)
        # padded prefill needs causal masking to localize each row; SSM
        # states integrate over padding, so SSD archs admit equal-length
        # groups instead
        self._pad_ok = all(
            sp.kind == "attn"
            for sp in tuple(cfg.pattern) + tuple(cfg.tail))
        # per-bucket admission executables bind this runtime's impl
        self._admit_fns: Dict[int, Any] = {}
        # modeled per-tick traffic/compute (DESIGN.md §12): dtype-aware
        # bytes from the actual resident arrays — this is where the int8
        # path's 2-4x byte reduction becomes measurable
        self.weight_bytes = _tree_bytes(self.params)
        self.kv_cache_bytes = _kv_bytes(self.state.caches)
        self._matmul_elems = _matmul_weight_elems(self.params, self.cfg)
        self._n_attn = _attn_layers(self.cfg)
        self._attn_dims = self.cfg.n_heads * self.cfg.resolved_head_dim
        if serve_cfg.paged:
            # KV payload bytes per cached token (codes + scales), for the
            # page-granular traffic model (DESIGN.md §14)
            self._kv_token_bytes = self.kv_cache_bytes / float(
                (self.pool.num_pages + 1) * serve_cfg.page_size)
            # cost-aware eviction score (DESIGN.md §16): recompute FLOPs
            # per resident byte of one block at chain depth d — deeper
            # blocks imply re-prefilling their whole prefix, so they are
            # the last to go under "cost" policy
            ps = serve_cfg.page_size
            block_bytes = self._kv_token_bytes * ps
            self.pool.block_cost = lambda d: costing.block_recompute_flops(
                self._matmul_elems, self._n_attn, self._attn_dims,
                d * ps, ps) / block_bytes
        self._build_tick()
        self._build_admit()
        if serve_cfg.paged and serve_cfg.compact_threshold > 0.0:
            self._build_compact()
        if serve_cfg.paged:
            self._build_cow()
            self._build_fork()

    # -- compiled paths -------------------------------------------------------

    def _donate(self):
        # DeviceState is donated on every tick/admit: the KV cache and slot
        # arrays update in place instead of being copied each call. The old
        # state object is dead after the call (step() always reassigns).
        return (1,)

    def _build_tick(self):
        """Build the tick executable cache. One executable per spec-k in
        use: the spec-k backoff rung (DESIGN.md §17) steps k down (4 -> 2
        -> 1) when acceptance collapses, and each k is its own trace.
        Every tick takes a ``poison`` vector ((B,) float32, all zeros in
        healthy runs — a traced argument, so injection never retraces)
        and folds the numerics sentinel into the packed readback: a slot
        whose logits go non-finite commits NOTHING that tick (no token,
        no advance, no cache-visible progress beyond an idempotent KV
        write) and self-deactivates, so the host can quarantine it
        without any rewind arithmetic. Plain tick readback: (2, B) int32
        ``[done, bad]``; spec tick: (3, B) ``[done, emitted, bad]`` —
        still ONE host readback per tick."""
        self._tick_fns: Dict[int, Any] = {}
        self._tick = self._tick_for(self._cur_spec_k)

    def _tick_for(self, k: int):
        fn = self._tick_fns.get(k)
        if fn is None:
            fn = jax.jit(self._make_tick_impl(k),
                         donate_argnums=self._donate())
            self._tick_fns[k] = fn
        return fn

    def _make_tick_impl(self, spec_k: int):
        cfg, scfg = self.cfg, self.scfg
        eos_id, max_len = scfg.eos_id, scfg.max_len
        paged = scfg.paged

        def tick(params, st: DeviceState, poison
                 ) -> Tuple[DeviceState, jnp.ndarray]:
            self.tick_trace_count += 1      # python side effect: trace count
            b = st.tok.shape[0]
            if paged:
                # dead/prefilling lanes' K/V writes go to the sink page —
                # their page-table rows may reference recycled pages
                logits1, caches = tf_lib.paged_decode_step(
                    params, cfg, st.tok[:, None], st.pos, st.page_table,
                    st.caches, active=st.active)
            else:
                logits1, caches = tf_lib.decode_step(
                    params, cfg, st.tok[:, None], st.pos, st.caches)
            logits = logits1[:, 0] + poison[:, None]        # (B, V) fp32
            # numerics sentinel: a non-finite logit row means this slot's
            # output can't be trusted — it makes NO progress this tick
            # (the KV write for st.tok is value-clean and idempotent: the
            # un-advanced pos means a healthy retry rewrites it) and
            # deactivates itself for the host to quarantine
            bad = st.active & ~jnp.all(jnp.isfinite(logits), axis=-1)
            ok = st.active & ~bad
            tok_new, rng_new = _sample(logits, st.rng, st.temp)
            tok_new = jnp.where(ok, tok_new, st.tok)
            rng_new = jnp.where(ok[:, None], rng_new, st.rng)
            rows = jnp.arange(b)
            widx = jnp.clip(st.gen, 0, st.out_buf.shape[1] - 1)
            out_buf = st.out_buf.at[rows, widx].set(
                jnp.where(ok, tok_new, st.out_buf[rows, widx]))
            gen_new = st.gen + ok
            pos_new = st.pos + ok
            hit_eos = ((tok_new == eos_id) if eos_id >= 0
                       else jnp.zeros_like(st.active))
            done = ok & (hit_eos | (gen_new >= st.budget)
                         | (pos_new >= max_len - 1))
            new_st = DeviceState(
                caches=caches, tok=tok_new, pos=pos_new, gen=gen_new,
                budget=st.budget, active=st.active & ~done & ~bad,
                temp=st.temp, rng=rng_new, out_buf=out_buf,
                page_table=st.page_table, hist=st.hist)
            packed = jnp.stack([done, bad]).astype(jnp.int32)
            return new_st, packed

        def spec_tick(params, st: DeviceState, poison
                      ) -> Tuple[DeviceState, jnp.ndarray]:
            """Speculative tick (DESIGN.md §15): draft k, verify all k in
            one multi-query pass, commit the accepted prefix + one
            correction/bonus token. Returns (state, (3, B) int32 packed
            [done, emitted, bad]) — still ONE host readback per tick."""
            self.tick_trace_count += 1
            b = st.tok.shape[0]
            k = spec_k
            active = st.active
            caches = st.caches
            if scfg.spec_drafter == "oracle":
                # the target model drafts itself greedily: k plain decode
                # passes. The verify rewrite of the same positions is
                # value-identical, so the combined tick stays idempotent.
                d_list = []
                tok_j, pos_j = st.tok, st.pos
                for _ in range(k):
                    lg, caches = tf_lib.paged_decode_step(
                        params, cfg, tok_j[:, None], pos_j, st.page_table,
                        caches, active=active)
                    nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
                    d_list.append(nxt)
                    tok_j = jnp.where(active, nxt, tok_j)
                    pos_j = pos_j + active
                drafts = jnp.stack(d_list, axis=1)          # (B, K)
            else:
                drafts = spec_lib.ngram_draft(st.hist, st.pos, k)
            chunk = jnp.concatenate([st.tok[:, None], drafts], axis=1)
            logits, caches = tf_lib.paged_verify_step(
                params, cfg, chunk, st.pos, st.page_table, caches,
                active=active)                              # (B, K+1, V)
            logits = logits + poison[:, None, None]
            # numerics sentinel over the whole verify block: NaN anywhere
            # in a slot's q-block (poison, or NaN KV attended through the
            # page table) voids ALL of its lanes this tick
            bad = active & ~jnp.all(jnp.isfinite(logits), axis=(1, 2))
            ok = active & ~bad
            with jax.named_scope("sample"):
                n_acc, fix_tok, rng_new = spec_lib.speculative_accept(
                    logits, drafts, st.rng, st.temp)
            rng_new = jnp.where(ok[:, None], rng_new, st.rng)
            # emission clamps: never exceed the token budget or the context
            # cap — exactly where the plain tick would have stopped
            rem = jnp.minimum(st.budget - st.gen, max_len - 1 - st.pos)
            n_emit = jnp.clip(jnp.minimum(n_acc + 1, rem), 1, k + 1)
            t_idx = jnp.arange(k + 1, dtype=jnp.int32)[None]    # (1, K+1)
            drafts_pad = jnp.concatenate(
                [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
            emitted = jnp.where(t_idx < n_acc[:, None], drafts_pad,
                                fix_tok[:, None])               # (B, K+1)
            if eos_id >= 0:
                # an EOS anywhere in the emitted run truncates it there
                eos_lane = jnp.min(jnp.where(emitted == eos_id, t_idx,
                                             k + 1), axis=1)
                n_emit = jnp.minimum(n_emit, eos_lane + 1)
            lane = t_idx < n_emit[:, None]
            valid = lane & ok[:, None]
            rows2 = jnp.broadcast_to(jnp.arange(b)[:, None], (b, k + 1))
            cap = st.out_buf.shape[1]
            out_buf = st.out_buf.at[
                rows2, jnp.where(valid, st.gen[:, None] + t_idx, cap)
            ].set(emitted, mode="drop")
            hist = st.hist.at[
                rows2, jnp.where(valid, st.pos[:, None] + 1 + t_idx,
                                 st.hist.shape[1])
            ].set(emitted, mode="drop")
            n_step = jnp.where(ok, n_emit, 0)
            last = jnp.take_along_axis(
                emitted, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            tok_new = jnp.where(ok, last, st.tok)
            pos_new = st.pos + n_step
            gen_new = st.gen + n_step
            hit_eos = ((tok_new == eos_id) if eos_id >= 0
                       else jnp.zeros_like(active))
            done = ok & (hit_eos | (gen_new >= st.budget)
                         | (pos_new >= max_len - 1))
            new_st = DeviceState(
                caches=caches, tok=tok_new, pos=pos_new, gen=gen_new,
                budget=st.budget, active=active & ~done & ~bad,
                temp=st.temp, rng=rng_new, out_buf=out_buf,
                page_table=st.page_table, hist=hist)
            packed = jnp.stack([done.astype(jnp.int32), n_step,
                                bad.astype(jnp.int32)])
            return new_st, packed

        m = scfg.spec_tree_m

        def tree_tick(params, st: DeviceState, poison, btables, bvalid
                      ) -> Tuple[DeviceState, jnp.ndarray]:
            """Tree-speculative tick (DESIGN.md §18): draft ``m``
            independent k-token branches per slot, fold them into batch
            rows of ONE multi-query verify pass over COW-forked page
            tables, and commit the branch that accepts the longest
            prefix. Returns (state, (4, B) int32 packed
            [done, emitted, bad, winner]) — still ONE host readback."""
            self.tick_trace_count += 1
            b = st.tok.shape[0]
            k = spec_k
            active = st.active
            drafts = spec_lib.ngram_draft_tree(st.hist, st.pos, k, m)
            # branch 0 rides the slot's own table and temperature; extra
            # branches are valid only where the host staged pages AND the
            # slot is greedy
            valid = jnp.concatenate(
                [jnp.ones((b, 1), bool),
                 bvalid & (st.temp <= 0.0)[:, None]], axis=1)   # (B, M)
            tables = jnp.concatenate(
                [st.page_table[:, None], btables], axis=1)      # (B,M,NB)
            chunk = jnp.concatenate(
                [jnp.broadcast_to(st.tok[:, None, None], (b, m, 1)),
                 drafts], axis=2)                               # (B,M,K+1)
            act_f = (active[:, None] & valid).reshape(b * m)
            # branches fold into batch rows: row b*M + j carries branch
            # j's drafts over branch j's table — one weight stream scores
            # the whole tree (kernels/decode_attention.py)
            logits_f, caches = tf_lib.paged_verify_step(
                params, cfg, chunk.reshape(b * m, k + 1),
                jnp.broadcast_to(st.pos[:, None], (b, m)).reshape(b * m),
                tables.reshape(b * m, -1), st.caches, active=act_f)
            logits = (logits_f.reshape(b, m, k + 1, -1)
                      + poison[:, None, None, None])
            # sentinel: non-finite logits in ANY valid branch void the
            # slot's tick — poison and committed-KV corruption hit every
            # branch alike, and a partially-poisoned accept would be
            # unauditable
            fin = jnp.all(jnp.isfinite(logits), axis=(2, 3))    # (B, M)
            bad = active & jnp.any(valid & ~fin, axis=1)
            ok = active & ~bad
            # per-branch accept; extra branches run greedy (temp 0), and
            # branch 0 — the distribution-bearing lane — is the one whose
            # key advance the slot keeps (greedy lanes consume none)
            temp_f = jnp.concatenate(
                [st.temp[:, None],
                 jnp.zeros((b, m - 1), st.temp.dtype)], axis=1)
            keys_f = jnp.broadcast_to(st.rng[:, None], (b, m, 2))
            with jax.named_scope("sample"):
                n_acc_f, fix_f, keys_new = spec_lib.speculative_accept(
                    logits.reshape(b * m, k + 1, -1),
                    drafts.reshape(b * m, k),
                    keys_f.reshape(b * m, 2), temp_f.reshape(b * m))
            n_acc = n_acc_f.reshape(b, m)
            fix = fix_f.reshape(b, m)
            rng_new = keys_new.reshape(b, m, 2)[:, 0]
            rng_new = jnp.where(ok[:, None], rng_new, st.rng)
            rem = jnp.minimum(st.budget - st.gen, max_len - 1 - st.pos)
            n_emit = jnp.clip(jnp.minimum(n_acc + 1, rem[:, None]),
                              1, k + 1)                         # (B, M)
            t3 = jnp.arange(k + 1, dtype=jnp.int32)[None, None]  # (1,1,K+1)
            drafts_pad = jnp.concatenate(
                [drafts, jnp.zeros((b, m, 1), jnp.int32)], axis=2)
            emitted = jnp.where(t3 < n_acc[:, :, None], drafts_pad,
                                fix[:, :, None])                # (B,M,K+1)
            if eos_id >= 0:
                eos_lane = jnp.min(jnp.where(emitted == eos_id, t3,
                                             k + 1), axis=2)
                n_emit = jnp.minimum(n_emit, eos_lane + 1)
            # winner: the valid branch committing the most tokens; argmax
            # takes the FIRST max, so ties fall to branch 0 (the linear
            # stream — a tie-tick is bit-identical to spec_tick)
            n_eff = jnp.where(valid, n_emit, 0)
            w = jnp.argmax(n_eff, axis=1).astype(jnp.int32)     # (B,)
            emitted_w = jnp.take_along_axis(
                emitted, w[:, None, None], axis=1)[:, 0]        # (B, K+1)
            n_emit_w = jnp.take_along_axis(n_emit, w[:, None],
                                           axis=1)[:, 0]
            table_w = jnp.take_along_axis(tables, w[:, None, None],
                                          axis=1)[:, 0]         # (B, NB)
            t_idx = jnp.arange(k + 1, dtype=jnp.int32)[None]    # (1, K+1)
            lane = t_idx < n_emit_w[:, None]
            vmask = lane & ok[:, None]
            rows2 = jnp.broadcast_to(jnp.arange(b)[:, None], (b, k + 1))
            cap = st.out_buf.shape[1]
            out_buf = st.out_buf.at[
                rows2, jnp.where(vmask, st.gen[:, None] + t_idx, cap)
            ].set(emitted_w, mode="drop")
            hist = st.hist.at[
                rows2, jnp.where(vmask, st.pos[:, None] + 1 + t_idx,
                                 st.hist.shape[1])
            ].set(emitted_w, mode="drop")
            n_step = jnp.where(ok, n_emit_w, 0)
            last = jnp.take_along_axis(
                emitted_w, jnp.maximum(n_emit_w - 1, 0)[:, None],
                axis=1)[:, 0]
            tok_new = jnp.where(ok, last, st.tok)
            pos_new = st.pos + n_step
            gen_new = st.gen + n_step
            hit_eos = ((tok_new == eos_id) if eos_id >= 0
                       else jnp.zeros_like(active))
            done = ok & (hit_eos | (gen_new >= st.budget)
                         | (pos_new >= max_len - 1))
            # the winner's window pages become the slot's pages IN the
            # tick; the host mirrors the swap from the packed winner row
            page_table = jnp.where(ok[:, None], table_w, st.page_table)
            new_st = DeviceState(
                caches=caches, tok=tok_new, pos=pos_new, gen=gen_new,
                budget=st.budget, active=active & ~done & ~bad,
                temp=st.temp, rng=rng_new, out_buf=out_buf,
                page_table=page_table, hist=hist)
            packed = jnp.stack([done.astype(jnp.int32), n_step,
                                bad.astype(jnp.int32),
                                jnp.where(ok, w, 0)])
            return new_st, packed

        if spec_k > 0 and m > 1:
            return tree_tick
        return spec_tick if spec_k > 0 else tick

    def _build_admit(self):
        """Admission executable body. Dense: pad-and-stack prefill + all-slot
        scatter. Paged: page-table update + ``paged_extend`` over the current
        prefill chunks (suffix-after-prefix-hit and chunked admission share
        the one primitive). Either way compiled per length bucket
        (_bucket_len caps how many buckets exist); each bucket's executable
        is cached in ``_admit_fns`` and traced exactly once (asserted via
        ``admit_trace_counts`` in tests/test_serve_quant.py)."""
        if self.scfg.paged:
            self._admit_impl = self._make_extend_impl()
            return
        cfg, scfg = self.cfg, self.scfg
        base_key, max_len = self._base_key, scfg.max_len
        pad_ok = self._pad_ok

        def admit(params, st: DeviceState, toks, lens, slots, budgets, temps,
                  uids) -> Tuple[DeviceState, jnp.ndarray]:
            # one batched prefill over the padded prompt stack
            logits1, row_caches = tf_lib.prefill(
                params, cfg, toks, max_len=max_len,
                cache_dtype=scfg.cache_dtype,
                lengths=lens if pad_ok else None)
            logits = logits1[:, 0]                          # (N, V)
            keys = jax.vmap(lambda u: jax.random.fold_in(base_key, u))(uids)
            tok0, rng0 = _sample(logits, keys, temps)
            # scatter ALL admitted slots' cache rows at once (invalid rows
            # carry out-of-bounds slot ids and drop)
            axes = _batch_axis_tree(st.caches)
            def ins(batched, row, ax):
                if ax == 0:
                    return batched.at[slots].set(
                        row.astype(batched.dtype), mode="drop")
                return batched.at[:, slots].set(
                    row.astype(batched.dtype), mode="drop")
            caches = jax.tree.map(ins, st.caches, row_caches, axes)
            cap = st.out_buf.shape[1]
            out_rows = jnp.zeros((tok0.shape[0], cap), jnp.int32
                                 ).at[:, 0].set(tok0)
            # a request can finish at prefill: max_tokens == 1, prompt at
            # the length cap (total context is capped at max_len), or the
            # very first sampled token being EOS
            done = (budgets <= 1) | (lens >= max_len - 1)
            if scfg.eos_id >= 0:
                done |= tok0 == scfg.eos_id
            new_st = DeviceState(
                caches=caches,
                tok=st.tok.at[slots].set(tok0, mode="drop"),
                pos=st.pos.at[slots].set(lens, mode="drop"),
                gen=st.gen.at[slots].set(1, mode="drop"),
                budget=st.budget.at[slots].set(budgets, mode="drop"),
                active=st.active.at[slots].set(~done, mode="drop"),
                temp=st.temp.at[slots].set(temps, mode="drop"),
                rng=st.rng.at[slots].set(rng0, mode="drop"),
                out_buf=st.out_buf.at[slots].set(out_rows, mode="drop"),
                page_table=st.page_table, hist=st.hist)
            return new_st, done

        self._admit_impl = admit

    def _make_extend_impl(self):
        """Paged admission body: one ``paged_extend`` call advances every
        in-flight prefill by one chunk. Rows whose prompt *ends* in this
        chunk (``final``) sample their first token and activate their slot;
        mid-chunk rows only record progress (``pos``) and stay inactive, so
        decode ticks interleave freely with long admissions."""
        cfg, scfg = self.cfg, self.scfg
        base_key, max_len = self._base_key, scfg.max_len

        def extend(params, st: DeviceState, toks, starts, lens, slots,
                   tables, budgets, temps, uids, final
                   ) -> Tuple[DeviceState, jnp.ndarray]:
            # ``tables`` is ROW-major (row j belongs to batch row j, sink-
            # filled for unused rows) — paged_extend indexes its table by
            # batch row, NOT by slot id; handing it the slot-major state
            # table would write through some *other* slot's pages whenever
            # rows and slots misalign. The persistent slot-major table is
            # updated separately (OOB slot ids drop).
            pt = st.page_table.at[slots].set(tables, mode="drop")
            logits1, caches = tf_lib.paged_extend(
                params, cfg, toks, starts, lens, tables, st.caches)
            logits = logits1[:, 0]                          # (N, V)
            keys = jax.vmap(lambda u: jax.random.fold_in(base_key, u))(uids)
            tok0, rng0 = _sample(logits, keys, temps)
            end = starts + lens
            done = final & ((budgets <= 1) | (end >= max_len - 1))
            if scfg.eos_id >= 0:
                done |= final & (tok0 == scfg.eos_id)
            cap = st.out_buf.shape[1]
            out_rows = jnp.zeros((tok0.shape[0], cap), jnp.int32
                                 ).at[:, 0].set(jnp.where(final, tok0, 0))
            hist = st.hist
            if hist.shape[1]:
                # speculative mode: mirror the chunk (and the first sampled
                # token of final rows) into the drafter's token history —
                # invalid lanes index out of bounds and drop
                n, width = toks.shape
                rel = jnp.arange(width, dtype=jnp.int32)[None]
                hrows = jnp.broadcast_to(slots[:, None], (n, width))
                hidx = jnp.where(rel < lens[:, None],
                                 starts[:, None] + rel, hist.shape[1])
                hist = hist.at[hrows, hidx].set(toks, mode="drop")
                hist = hist.at[
                    slots, jnp.where(final, end, hist.shape[1])
                ].set(tok0, mode="drop")
            new_st = DeviceState(
                caches=caches,
                tok=st.tok.at[slots].set(jnp.where(final, tok0, 0),
                                         mode="drop"),
                pos=st.pos.at[slots].set(end, mode="drop"),
                gen=st.gen.at[slots].set(jnp.where(final, 1, 0),
                                         mode="drop"),
                budget=st.budget.at[slots].set(budgets, mode="drop"),
                active=st.active.at[slots].set(final & ~done, mode="drop"),
                temp=st.temp.at[slots].set(temps, mode="drop"),
                rng=st.rng.at[slots].set(rng0, mode="drop"),
                out_buf=st.out_buf.at[slots].set(out_rows, mode="drop"),
                page_table=pt, hist=hist)
            return new_st, done

        return extend

    def _admit_exe(self, bucket: int):
        """One jitted admit/extend executable per length bucket, built on
        first use and reused for every later admission in that bucket — no
        per-call rebuild churn."""
        fn = self._admit_fns.get(bucket)
        if fn is None:
            impl = self._admit_impl

            def admit_b(params, st, *args):
                # python side effect: per-bucket trace count
                self.admit_trace_counts[bucket] = \
                    self.admit_trace_counts.get(bucket, 0) + 1
                return impl(params, st, *args)

            fn = jax.jit(admit_b, donate_argnums=self._donate())
            self._admit_fns[bucket] = fn
        return fn

    # -- queue API ------------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_tokens: int = 16,
               temperature: Optional[float] = None,
               deadline_ticks: Optional[int] = None,
               n_best: int = 1) -> int:
        prompt = np.asarray(prompt, np.int32)
        if prompt.size >= self.scfg.max_len:
            raise ValueError(f"prompt length {prompt.size} >= max_len "
                             f"{self.scfg.max_len}")
        if deadline_ticks is not None and deadline_ticks <= 0:
            raise ValueError(f"deadline_ticks must be > 0, got "
                             f"{deadline_ticks}")
        if n_best < 1:
            raise ValueError(f"n_best must be >= 1, got {n_best}")
        if n_best > 1 and not self.scfg.paged:
            raise ValueError("n-best sampling forks the paged KV cache "
                             "(DESIGN.md §18); set paged=True")
        if n_best > self.scfg.max_slots:
            raise ValueError(f"n_best ({n_best}) exceeds max_slots "
                             f"({self.scfg.max_slots}): every fork of one "
                             f"group decodes concurrently")
        if self.pool is not None:
            # a request whose worst-case page demand can never be met would
            # livelock admission (fits() false forever) — reject it here
            need = self._pages_needed_group(prompt.size, max_tokens, n_best)
            if need > self.pool.num_pages:
                raise ValueError(
                    f"request needs {need} pages (prompt {prompt.size} + "
                    f"max_tokens {max_tokens} x n_best {n_best}) but the "
                    f"pool has only {self.pool.num_pages}; raise num_pages "
                    f"or lower max_tokens")
        self._uid += 1
        if self._journal is not None and not self._replaying:
            # WAL contract (DESIGN.md §19): the admission is durable
            # (fsync'd) BEFORE it is acked — an acked request survives any
            # crash and replays from the journal
            nb = self._journal.append_submit(
                uid=self._uid, prompt=[int(t) for t in prompt.tolist()],
                max_tokens=max_tokens, temperature=temperature,
                deadline_ticks=deadline_ticks, n_best=n_best,
                tick=self._tick_idx)
            self.journal_bytes_total += nb
            if self.accountant is not None:
                self.accountant.observe_durability(journal_bytes=nb)
        self.scheduler.submit(Request(self._uid, prompt, max_tokens,
                                      temperature,
                                      deadline_ticks=deadline_ticks,
                                      submit_tick=self._tick_idx,
                                      n_best=n_best))
        return self._uid

    @property
    def queue(self):
        return self.scheduler.pending

    # -- host readback helpers ------------------------------------------------

    def _readback(self, x) -> np.ndarray:
        """Every device->host transfer goes through here (counted: the tick
        hot path must do exactly one — the finished mask). Each is a
        ``serve.readback`` span in a profile."""
        self.host_readbacks += 1
        with jax.profiler.TraceAnnotation("serve.readback"):
            return np.asarray(x)

    def _checked_readback(self, x, validate, tick: int) -> np.ndarray:
        """Tick readback with transport-fault detection: the injector may
        drop or garble the host copy, and a real edge deployment's DMA can
        too. ``validate`` knows the packed layout's value domain; a failed
        check re-reads the (unchanged, non-donated) device buffer up to
        ``guard.readback_max_retries`` times before giving up loudly."""
        attempt = 0
        while True:
            arr = self._readback(x)
            if self._injector is not None:
                arr = self._injector.filter_readback(arr, tick, attempt)
            if arr is not None and validate(arr):
                return arr
            attempt += 1
            if attempt > self.guard.readback_max_retries:
                raise RuntimeError(
                    f"tick {tick}: readback failed validation "
                    f"{attempt} times")
            self.readback_retries_total += 1
            self._rb_retries_tick += 1

    @staticmethod
    def _validate_plain_packed(arr: np.ndarray) -> bool:
        return (arr.ndim == 2 and arr.shape[0] == 2
                and bool(np.isin(arr, (0, 1)).all()))

    def _validate_spec_packed(self, arr: np.ndarray) -> bool:
        if arr.ndim != 2 or arr.shape[0] != 3:
            return False
        flags_ok = bool(np.isin(arr[(0, 2), :], (0, 1)).all())
        emit_ok = bool(((arr[1] >= 0)
                        & (arr[1] <= self._cur_spec_k + 1)).all())
        return flags_ok and emit_ok

    def _validate_tree_packed(self, arr: np.ndarray) -> bool:
        if arr.ndim != 2 or arr.shape[0] != 4:
            return False
        flags_ok = bool(np.isin(arr[(0, 2), :], (0, 1)).all())
        emit_ok = bool(((arr[1] >= 0)
                        & (arr[1] <= self._cur_spec_k + 1)).all())
        win_ok = bool(((arr[3] >= 0)
                       & (arr[3] < self.scfg.spec_tree_m)).all())
        return flags_ok and emit_ok and win_ok

    # -- chaos tier: fault application + recovery (DESIGN.md §17) -------------

    def _apply_host_faults(self, tick: int) -> None:
        """Inject this tick's host-side fault events (device-side logit
        poison rides the tick's poison argument instead). Runs before the
        decode tick so the injected state is what the tick observes."""
        inj = self._injector
        # spike holds expire on schedule regardless of new events
        keep = []
        for expires, pages in self._spike_holds:
            if tick >= expires and self.pool is not None:
                self.pool.release_all(pages)
            else:
                keep.append((expires, pages))
        self._spike_holds = keep
        if inj is None:
            return
        stall = inj.stall_seconds(tick)
        if stall > 0.0:
            time.sleep(stall)
        for ev in inj.events_for(tick):
            if ev.kind == "pool_spike" and self.pool is not None:
                n = min(int(ev.magnitude), self.pool.available)
                if n > 0:
                    held = self.pool.alloc(n)
                    if held is not None:
                        self._spike_holds.append(
                            (tick + max(ev.duration, 1), held))
                        inj.count("pool_spike")
            elif ev.kind == "kv_bitflip" and self.scfg.paged:
                self._inject_kv_bitflip(ev)
            elif ev.kind == "process_kill":
                # simulated process death (DESIGN.md §19): the exception
                # propagates out of step() — recovery is restore(), not
                # any in-tick rung. A kill at or before the restore
                # boundary is the crash a restore already recovered from
                # and must not re-fire during or after replay.
                if ev.tick > self._restore_boundary:
                    inj.count("process_kill")
                    raise ProcessKilled(
                        f"process_kill fault at tick {tick}: engine "
                        f"state is gone; restart from checkpoint_dir "
                        f"via ServeEngine.restore()")

    def _inject_kv_bitflip(self, ev) -> None:
        """Corrupt one K page of a decoding slot — inside its attended
        window, so the sentinel (not luck) must catch it. Restricted to
        decoding slots: a mid-prefill slot's extend readback carries no
        ``bad`` lane, and its poisoned logits would go unobserved."""
        ps = self.scfg.page_size
        victims = [i for i, r in enumerate(self.slot_req)
                   if r is not None and i not in self._prefilling]
        if ev.slot in victims:
            victims = [ev.slot]
        for slot in victims:
            pages = self._slot_pages[slot]
            req = self.slot_req[slot]
            n_live = -(-(len(req.prompt) + self._host_gen[slot]) // ps)
            lo = self.pool.movable_suffix(pages)
            cand = [p for j, p in enumerate(pages)
                    if lo <= j < n_live]
            if not cand:
                continue
            self.state = dataclasses.replace(
                self.state,
                caches=corrupt_kv_page(self.state.caches, cand[0]))
            self._injector.count("kv_bitflip")
            return

    def _scrub_slot_storage(self, slot: int) -> None:
        """Zero the K/V storage a quarantined slot may have poisoned. A bad
        tick writes its non-finite activations into the slot's PRIVATE
        pages (every layer past the first NaN attention output projects
        NaN K/V), and a NaN *V* entry leaks through masked attention —
        softmax gives the masked position probability 0, but 0 * NaN is
        NaN — so a freed-then-recycled page would poison its next owner.
        Scrubbing on teardown restores the invariant the allocator relies
        on: free storage is benign garbage (zeros), never NaN. Shared
        prefix pages are immutable-clean by construction and are skipped;
        this is a rare-path device call, not tick work."""
        if self.pool is not None:
            pages = self._slot_pages[slot]
            lo = self.pool.movable_suffix(pages)
            if not pages[lo:]:
                return
            self._scrub_pages(pages[lo:])
        else:
            self._scrub_sel(jnp.asarray([slot], jnp.int32))

    def _scrub_pages(self, pages: List[int]) -> None:
        """Zero a set of pool pages about to be freed — same invariant as
        ``_scrub_slot_storage`` (free storage is never NaN), reachable for
        page lists that belong to no slot (a quarantined slot's ephemeral
        tree-branch windows, DESIGN.md §18)."""
        if pages:
            self._scrub_sel(jnp.asarray(pages, jnp.int32))

    def _scrub_sel(self, sel: jnp.ndarray) -> None:
        caches = {}
        for name, entry in self.state.caches.items():
            e2 = dict(entry)
            for key in ("kv", "kv_scale"):
                if key not in entry:
                    continue
                kv = entry[key]
                # pattern pools stack the layer dim first; tails are flat.
                # The dense layout (B where the paged pool has P) scrubs
                # the slot's whole cache row with the same indexing.
                ax = ((slice(None), sel) if name.startswith("pat")
                      else (sel,))
                e2[key] = dataclasses.replace(
                    kv, k=kv.k.at[ax].set(0), v=kv.v.at[ax].set(0))
            caches[name] = e2
        self.state = dataclasses.replace(self.state, caches=caches)

    def _capture_slot(self, slot: int) -> Request:
        """Freeze a live slot into a continuation request carrying its
        committed progress: prompt = original prompt + valid generated
        tokens, budget = remaining tokens. The original prompt/budget park
        in ``_recovery[uid]`` and are restored at finish, so the caller
        sees one seamless stream. Re-prefilling the continuation IS the
        fp32-oracle re-decode on fp engines (prefill == greedy decode
        parity, DESIGN.md §14) — and its energy is billed as recovery."""
        req = self.slot_req[slot]
        g = self._host_gen[slot]
        toks = ([int(t) for t in self._readback(self.state.out_buf[slot, :g])]
                if g > 0 else [])
        rec = self._recovery.setdefault(
            req.uid, {"prompt": req.prompt, "max_tokens": req.max_tokens,
                      "tokens": []})
        rec["tokens"].extend(toks)
        cont = Request(
            req.uid,
            np.concatenate([np.asarray(rec["prompt"], np.int32),
                            np.asarray(rec["tokens"], np.int32)]),
            max_tokens=max(rec["max_tokens"] - len(rec["tokens"]), 1),
            temperature=req.temperature,
            deadline_ticks=req.deadline_ticks,
            submit_tick=self._tick_idx,
            # a captured fork member stays a member (its finish banks into
            # the group), but never re-forks (n_best stays 1)
            fork_group=req.fork_group, fork_idx=req.fork_idx)
        self._recovering.add(req.uid)
        # teardown mirrors: the slot is free next tick (the device side
        # already deactivated it, or the runtime is being rebuilt)
        self.slot_req[slot] = None
        self._host_gen[slot] = 0
        self._prefilling.pop(slot, None)
        self._fork_wait.pop(slot, None)
        kids = self._fork_children.pop(slot, None)
        if kids is not None:
            # children reserved but never forked (parent captured
            # mid-prefill, e.g. by the fp fallback): requeue them as
            # independent admissions — their streams still bank into the
            # group, only the sharing is lost
            requeue = []
            for kid in kids:
                child = self.slot_req[kid]
                self.slot_req[kid] = None
                self._fork_wait.pop(kid, None)
                if child is not None:
                    child.submit_tick = self._tick_idx
                    requeue.append(child)
            self.scheduler.requeue_front(requeue)
        self._scrub_slot_storage(slot)
        if self.pool is not None and self._slot_pages[slot]:
            # release WITHOUT publishing: pages of a faulted slot may hold
            # corrupt KV; freeing them unkeyed means they are rewritten
            # before any future lookup can hit them
            self.pool.release_all(self._slot_pages[slot])
            self._slot_pages[slot] = []
        return cont

    def _quarantine_slot(self, slot: int) -> None:
        """Sentinel hit: tear the slot down and requeue its continuation
        head-of-line. The slot made no progress on the bad tick, so the
        continuation resumes exactly at the last committed token."""
        cont = self._capture_slot(slot)
        self.scheduler.requeue_front([cont])
        self.n_quarantined += 1
        self._tick_quarantined += 1

    def _shed_request(self, req: Request, finished: List[Request]) -> None:
        """Fail a request fast (deadline expiry / admission-retry
        exhaustion): it completes with whatever tokens recovery already
        banked — never silently vanishes."""
        rec = self._recovery.pop(req.uid, None)
        if rec is not None:
            req.prompt = rec["prompt"]
            req.max_tokens = rec["max_tokens"]
            req.generated = list(rec["tokens"])
        else:
            req.generated = []
        self._recovering.discard(req.uid)
        self._defer_counts.pop(req.uid, None)
        self._retry_after.pop(req.uid, None)
        self._fit_checked.discard(req.uid)
        req.done = True
        if req.fork_group is not None:
            # a shed fork member still reports: the group must close
            self._record_fork_stream(req.fork_group, req.fork_idx,
                                     req.generated, finished)
        elif req.n_best > 1:
            # shed before admission ever forked (no group exists): the
            # caller still sees an n-best-shaped result
            req.nbest = [list(req.generated) for _ in range(req.n_best)]
            finished.append(req)
        else:
            finished.append(req)
        self.n_shed += 1
        self._tick_shed += 1

    def _finish_slot(self, slot: int, finished: List[Request]) -> None:
        req = self.slot_req[slot]
        n = self._host_gen[slot]
        toks = self._readback(self.state.out_buf[slot, :n])
        req.generated = [int(t) for t in toks]
        req.done = True
        self.slot_req[slot] = None
        self._host_gen[slot] = 0
        if self.pool is not None and self._slot_pages[slot]:
            pages = self._slot_pages[slot]
            if self.scfg.prefix_cache and n > 0:
                # publish the finished stream's full, frozen blocks —
                # prompt AND committed generation — BEFORE releasing.
                # Order matters: release_all frees unpublished pages to
                # the free list, so publishing afterwards would certify
                # recyclable pages; and without this step the stream's
                # last exactly-full block (grown during decode) was never
                # reusable as a prefix. The cache holds positions
                # [0, prompt + n - 1): the final generated token is the
                # pending one whose K/V never landed. A recovering slot's
                # "prompt" here is the continuation prompt (original +
                # recovered tokens), which is exactly the stream content —
                # publishing under it stays correct.
                cached = np.concatenate(
                    [np.asarray(req.prompt, np.int64),
                     np.asarray(toks[:n - 1], np.int64)])
                parent = ROOT
                for bi, block in enumerate(
                        block_tokens(cached, self.scfg.page_size)):
                    if bi >= len(pages):
                        break
                    parent = self.pool.publish(pages[bi], parent, block)
            # published blocks park in the pool's LRU (still hittable);
            # private pages free immediately
            self.pool.release_all(pages)
            self._slot_pages[slot] = []
        # recovery merge LAST: restore the original prompt/budget and stitch
        # the recovered tokens in front of this leg's output — the caller
        # sees one uninterrupted stream
        rec = self._recovery.pop(req.uid, None)
        if rec is not None:
            req.prompt = rec["prompt"]
            req.max_tokens = rec["max_tokens"]
            req.generated = list(rec["tokens"]) + req.generated
            self._recovering.discard(req.uid)
        if req.fork_group is not None:
            # fork-group member (DESIGN.md §18): the stream banks into the
            # group; the caller receives the PARENT request once every
            # fork has reported
            self._record_fork_stream(req.fork_group, req.fork_idx,
                                     req.generated, finished)
        else:
            finished.append(req)
        self.n_finished_ok += 1

    # -- admission ------------------------------------------------------------

    def _admit(self, finished: List[Request]) -> "_AdmitInfo":
        if self.scfg.paged:
            return self._admit_paged(finished)
        return self._admit_dense(finished)

    def _admit_dense(self, finished: List[Request]) -> "_AdmitInfo":
        """Batched dense admission: ONE padded prefill + all-slot scatter."""
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        reqs = self.scheduler.select(len(free), now=self._tick_idx)
        if not reqs:
            return _AdmitInfo()
        if not self._pad_ok:
            # SSD/hybrid archs: only equal-length prompts share a prefill
            same = [r for r in reqs if len(r.prompt) == len(reqs[0].prompt)]
            self.scheduler.requeue_front([r for r in reqs if r not in same])
            reqs = same
        nslots = self.scfg.max_slots
        # SSD path runs prefill without per-row lengths, so the stack width
        # must equal the (shared) true prompt length — no bucket padding.
        # The bucket is capped at max_len: a wider stack would push prefill
        # into its ring branch and silently drop the oldest prompt tokens.
        lmax = (_bucket_len(max(len(r.prompt) for r in reqs),
                            cap=self.scfg.max_len)
                if self._pad_ok else len(reqs[0].prompt))
        n = len(reqs)
        toks = np.zeros((nslots, lmax), np.int32)
        lens = np.zeros(nslots, np.int32)
        slots = np.full(nslots, nslots + 1, np.int32)   # OOB rows drop
        budgets = np.ones(nslots, np.int32)
        temps = np.zeros(nslots, np.float32)
        uids = np.zeros(nslots, np.int32)
        for j, req in enumerate(reqs):
            sl = len(req.prompt)
            toks[j, :sl] = req.prompt
            lens[j] = sl
            slots[j] = free[j]
            budgets[j] = req.max_tokens
            temps[j] = (self.scfg.temperature if req.temperature is None
                        else req.temperature)
            uids[j] = req.uid
        self.state, done = self._admit_exe(lmax)(
            self.params, self.state, jnp.asarray(toks), jnp.asarray(lens),
            jnp.asarray(slots), jnp.asarray(budgets), jnp.asarray(temps),
            jnp.asarray(uids))
        done_mask = self._readback(done)
        for j, req in enumerate(reqs):
            self.slot_req[free[j]] = req
            self._host_gen[free[j]] = 1
            if done_mask[j]:
                self._finish_slot(free[j], finished)
        toks_n = int(lens.sum())
        sq = int((lens.astype(np.int64) ** 2).sum())
        # recovery billing: dense prefill is single-shot, so a recovering
        # continuation bills its whole prompt here (start = 0)
        rec_tok, rec_fl, rec_by = 0, 0.0, 0.0
        for req in reqs:
            if req.uid in self._recovering:
                plen = len(req.prompt)
                rec_tok += plen
                rec_fl += costing.prefill_span_flops(
                    self._matmul_elems, self._n_attn, self._attn_dims,
                    0, plen)
                rec_by += self.kv_cache_bytes / self.scfg.max_slots
                self._recovering.discard(req.uid)
        return _AdmitInfo(
            admitted=len(reqs), prefill_tokens=toks_n, weight_passes=1,
            uids=uids[:n],
            kv_bytes=self.kv_cache_bytes * len(reqs) / self.scfg.max_slots,
            flops=(2.0 * self._matmul_elems * toks_n
                   + 2.0 * self._n_attn * self._attn_dims * sq),
            recovery_tokens=rec_tok, recovery_flops=rec_fl,
            recovery_bytes=rec_by)

    # -- page-table compaction (DESIGN.md §16) --------------------------------

    def _build_compact(self):
        """One jitted device call per compaction: copy the moved pages in
        every layer's pool and rewrite the slot's page-table row, donated
        like the tick. ``src``/``dst`` are padded to ``blocks_per_slot``
        with sink->sink identity copies so a single executable serves
        every move count."""
        def compact(state: DeviceState, src, dst, slot, row):
            self.compact_trace_count += 1   # python side effect: trace count
            caches = tf_lib.move_pages(state.caches, src, dst)
            pt = state.page_table.at[slot].set(row)
            return dataclasses.replace(state, caches=caches, page_table=pt)
        self._compact_exe = jax.jit(compact, donate_argnums=(0,))

    def _maybe_compact(self) -> int:
        """Defragment at most ONE slot's private page suffix per tick
        (bounds tick-time work). A slot qualifies when it is decoding (not
        mid-prefill — its table is rewritten per chunk anyway), its table
        fragmentation reaches the threshold, its movable suffix (refcount
        1, unpublished — serve/pages.py:movable_suffix; shared prefix
        blocks are pinned) is itself scattered, and a contiguous free run
        exists. Returns pages moved. Because a slot's page list is fixed
        at admission, a compacted slot stays compact for its lifetime."""
        thr = self.scfg.compact_threshold
        if thr <= 0.0 or not self.scfg.paged:
            return 0
        # latency-pressure rung (DESIGN.md §17): a tick-stall trigger
        # pauses the (deferrable) defragmentation work for a window
        if self._tick_idx < self._compact_pause_until:
            return 0
        nb, sink = self._blocks_per_slot, self.pool.sink
        for slot, req in enumerate(self.slot_req):
            if req is None or slot in self._prefilling:
                continue
            pages = self._slot_pages[slot]
            if len(pages) < 2 or fragmentation(pages) < thr:
                continue
            lo = self.pool.movable_suffix(pages)
            movable = pages[lo:]
            if len(movable) < 2 or fragmentation(movable) == 0.0:
                continue
            run = self.pool.alloc_run(len(movable))
            if run is None:             # no contiguous free run: next tick
                continue
            src = np.full(nb, sink, np.int32)
            dst = np.full(nb, sink, np.int32)
            src[:len(movable)] = movable
            dst[:len(movable)] = run
            new_pages = pages[:lo] + run
            row = new_pages + [sink] * (nb - len(new_pages))
            self.state = self._compact_exe(
                self.state, jnp.asarray(src), jnp.asarray(dst),
                jnp.int32(slot), jnp.asarray(row[:nb], dtype=jnp.int32))
            self.pool.release_all(movable)  # private + unkeyed -> free list
            self._slot_pages[slot] = new_pages
            return len(movable)
        return 0

    # -- copy-on-write forks (DESIGN.md §18) ----------------------------------

    def _build_cow(self):
        """One jitted device call per COW/boundary-copy batch: copy the
        listed pages in every layer's pool and redirect the owning slots'
        page-table entries, donated like the tick. Events are padded to a
        pow2 bucket with sink->sink identity copies (OOB slot ids drop the
        table write), so a handful of executables serves every batch
        size."""
        def cow(state: DeviceState, src, dst, slot_idx, blk_idx, entry):
            self.cow_trace_count += 1   # python side effect: trace count
            caches, pt = tf_lib.cow_pages(
                state.caches, state.page_table, src, dst, slot_idx,
                blk_idx, entry)
            return dataclasses.replace(state, caches=caches, page_table=pt)
        self._cow_exe = jax.jit(cow, donate_argnums=(0,))

    def _cow_call(self, events: List[Tuple[int, int, int, int, int]]
                  ) -> None:
        """Apply a batch of ``(src, dst, slot, blk, entry)`` page events in
        ONE device call. ``src == dst == sink`` rows update only the table
        (a retain-only redirect); OOB slot rows copy only the page (an
        ephemeral branch window that lives outside any slot's table)."""
        n = _bucket_len(len(events))
        sink = self.pool.sink
        nslots = self.scfg.max_slots
        src = np.full(n, sink, np.int32)
        dst = np.full(n, sink, np.int32)
        sl = np.full(n, nslots + 1, np.int32)
        bl = np.zeros(n, np.int32)
        en = np.full(n, sink, np.int32)
        for j, (s, d, slot, blk, entry) in enumerate(events):
            src[j], dst[j], sl[j], bl[j], en[j] = s, d, slot, blk, entry
        self.state = self._cow_exe(
            self.state, jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(sl), jnp.asarray(bl), jnp.asarray(en))

    def _build_fork(self):
        """One jitted device call per fork group activation: broadcast the
        parent's slot row (pending token, position, budget, output ring,
        drafter history) to every child slot, install each child's own
        page table and PRNG key. No cache bytes move — the children READ
        the shared prompt pages through their tables; that is the whole
        point. ``dsts`` is padded to max_slots with OOB ids (dropped)."""
        def fork(state: DeviceState, src, dsts, tables, rngs):
            self.fork_trace_count += 1  # python side effect: trace count
            f = dsts.shape[0]
            def row(x):
                return x.at[dsts].set(
                    jnp.broadcast_to(x[src], (f,) + x.shape[1:]),
                    mode="drop")
            return DeviceState(
                caches=state.caches,
                tok=row(state.tok), pos=row(state.pos),
                gen=row(state.gen), budget=row(state.budget),
                active=row(state.active), temp=row(state.temp),
                rng=state.rng.at[dsts].set(rngs, mode="drop"),
                out_buf=row(state.out_buf),
                page_table=state.page_table.at[dsts].set(tables,
                                                         mode="drop"),
                hist=row(state.hist))
        self._fork_exe = jax.jit(fork, donate_argnums=(0,))

    def _fork_slots(self, parent_slot: int, kids: List[int]) -> None:
        """Activate a fork group (DESIGN.md §18): retain the parent's
        committed prompt pages into each child's table (no bytes move),
        give each child a private decode tail, and copy the parent's slot
        row to every child in ONE jitted call. A child whose tail
        allocation loses a pool race is requeued as an independent
        admission — its stream still banks into the group, only the
        sharing is lost."""
        scfg = self.scfg
        ps = scfg.page_size
        nslots, nb = scfg.max_slots, self._blocks_per_slot
        parent = self.slot_req[parent_slot]
        plen = len(parent.prompt)
        pages = self._slot_pages[parent_slot]
        # blocks holding committed prompt KV (the last may be partial —
        # shared under COW, diverging writers copy it at the barrier)
        n_shared = -(-plen // ps)
        tail = len(pages) - n_shared
        dsts, tables, rngs, requeue = [], [], [], []
        for kid in kids:
            child = self.slot_req[kid]
            self._fork_wait.pop(kid, None)
            shared = self.pool.fork(pages[:n_shared])
            fresh = self.pool.alloc(tail)
            if fresh is None:
                self.pool.release_all(shared)
                self.slot_req[kid] = None
                child.submit_tick = self._tick_idx
                requeue.append(child)
                continue
            kid_pages = shared + fresh
            self._slot_pages[kid] = kid_pages
            self._host_gen[kid] = 1
            row = kid_pages + [self.pool.sink] * (nb - len(kid_pages))
            dsts.append(kid)
            tables.append(row[:nb])
            rngs.append(np.asarray(
                jax.random.fold_in(self._base_key, child.uid)))
            # the duplicate-KV bill this fork did NOT pay: an independent
            # admission of the same stream would re-prefill the prompt
            self._tick_forks += 1
            self._tick_fork_saved_bytes += self._kv_token_bytes * plen
            self._tick_fork_saved_flops += costing.prefill_span_flops(
                self._matmul_elems, self._n_attn, self._attn_dims,
                0, plen)
        if requeue:
            self.scheduler.requeue_front(requeue)
        if not dsts:
            return
        d = np.full(nslots, nslots + 1, np.int32)
        t = np.full((nslots, nb), self.pool.sink, np.int32)
        r = np.zeros((nslots, 2), np.uint32)
        d[:len(dsts)] = dsts
        t[:len(dsts)] = tables
        r[:len(dsts)] = rngs
        self.state = self._fork_exe(
            self.state, jnp.int32(parent_slot), jnp.asarray(d),
            jnp.asarray(t), jnp.asarray(r))

    def _cancel_fork(self, parent_slot: int, kids: List[int]) -> None:
        """The parent finished AT activation (budget 1 / EOS on its first
        token): every fork would replay the identical one-token stream, so
        the reserved child slots free and the group banks mirror streams
        resolved against stream 0 when the parent's finish records it."""
        gid = self.slot_req[parent_slot].fork_group
        g = self._fork_groups.get(gid)
        for kid in kids:
            child = self.slot_req[kid]
            self.slot_req[kid] = None
            self._fork_wait.pop(kid, None)
            if g is not None and child is not None:
                g["streams"][child.fork_idx] = _FORK_MIRROR

    def _record_fork_stream(self, gid: int, idx: int, toks: List[int],
                            finished: List[Request]) -> None:
        """Bank one fork's finished stream into its group; once every fork
        has reported, the PARENT request completes with ``nbest`` holding
        all streams in fork order (``generated`` aliases stream 0)."""
        g = self._fork_groups.get(gid)
        if g is None:       # defensive: a stray continuation after a shed
            return
        g["streams"][idx] = toks
        s = g["streams"]
        if len(s) < g["k"]:
            return
        base = s.get(0, [])
        streams = [list(base) if s.get(i, []) is _FORK_MIRROR
                   else list(s.get(i, [])) for i in range(g["k"])]
        parent = g["req"]
        parent.nbest = streams
        parent.generated = streams[0]
        parent.done = True
        del self._fork_groups[gid]
        finished.append(parent)

    def _cow_barrier(self, active: List[int]) -> List[int]:
        """Pre-tick write barrier (DESIGN.md §18): every page the coming
        tick may write — the blocks covering positions
        ``[pos, pos + spec_k]`` per decoding slot — must be PRIVATE to its
        slot. A shared (forked) or published page copies first
        (``PagePool.cow_write``), billed as COW traffic; pages without
        committed content redirect table-only. A pool-exhausted copy
        quarantines its slot (with its device lane force-deactivated so
        the tick cannot touch the shared page) rather than corrupt its
        siblings' streams. Returns the surviving active list."""
        ps = self.scfg.page_size
        k = self._cur_spec_k
        events: List[Tuple[int, int, int, int, int]] = []
        drop: List[int] = []
        for slot in active:
            req = self.slot_req[slot]
            pages = self._slot_pages[slot]
            pos = len(req.prompt) + self._host_gen[slot] - 1
            wlo = pos // ps
            whi = min((pos + k) // ps, len(pages) - 1)
            for blk in range(wlo, whi + 1):
                p = pages[blk]
                if self.pool.writable(p):
                    continue
                res = self.pool.cow_write(p)
                if res is None:
                    drop.append(slot)
                    break
                new, copied = res
                pages[blk] = new
                if copied:
                    self._tick_cow_copies += 1
                    self._tick_cow_bytes += (2.0 * ps
                                             * self._kv_token_bytes)
                # committed content below pos copies; later blocks hold
                # nothing yet, so only the table entry moves
                has_content = blk * ps < pos
                events.append((p if has_content else self.pool.sink,
                               new if has_content else self.pool.sink,
                               slot, blk, new))
        for slot in drop:
            # deactivate the device lane BEFORE teardown: without this the
            # tick would still write the page its siblings share
            self.state = dataclasses.replace(
                self.state,
                active=self.state.active.at[slot].set(False))
            self._quarantine_slot(slot)
        if drop:
            events = [e for e in events if e[2] not in drop]
            active = [s for s in active if s not in drop]
        if events:
            self._cow_call(events)
        return active

    def _prepare_tree(self, active: List[int]
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Stage this tick's ephemeral branch windows (DESIGN.md §18): for
        each greedy decoding slot, each of the ``spec_tree_m - 1`` extra
        branches gets private copies of the write-window blocks in a
        forked table row. Only the boundary block holds committed KV (the
        COW barrier just privatized it), so at most one page copies per
        branch — billed as COW traffic. A pool race drops that branch lane
        (``bvalid`` False) and the slot's tick degrades to the linear
        branch-0 path. Returns the device ``(btables, bvalid)`` tick
        arguments; the staged pages park in ``_tree_branches`` for
        ``_commit_tree``."""
        scfg = self.scfg
        m, k, ps = scfg.spec_tree_m, self._cur_spec_k, scfg.page_size
        nslots, nb = scfg.max_slots, self._blocks_per_slot
        sink = self.pool.sink
        btables = np.full((nslots, m - 1, nb), sink, np.int32)
        bvalid = np.zeros((nslots, m - 1), bool)
        self._tree_branches = {}
        events: List[Tuple[int, int, int, int, int]] = []
        for slot in active:
            req = self.slot_req[slot]
            temp = (scfg.temperature if req.temperature is None
                    else req.temperature)
            if temp > 0.0:
                # temperature slots keep the distribution-exact linear
                # path on branch 0 (multi-branch rejection sampling would
                # need a joint residual scheme to stay unbiased)
                continue
            pages = self._slot_pages[slot]
            pos = len(req.prompt) + self._host_gen[slot] - 1
            wlo = pos // ps
            whi = min((pos + k) // ps, len(pages) - 1)
            width = whi - wlo + 1
            row = pages + [sink] * (nb - len(pages))
            branches: List[Optional[List[int]]] = []
            for i in range(m - 1):
                bp = self.pool.alloc(width)
                branches.append(bp)
                if bp is None:
                    continue
                brow = list(row[:nb])
                brow[wlo:whi + 1] = bp
                btables[slot, i] = brow
                bvalid[slot, i] = True
                if pos - wlo * ps > 0:
                    # the boundary block holds committed KV the branch
                    # must attend through its own table: copy it (OOB
                    # slot id — no table row owns branch pages)
                    events.append((pages[wlo], bp[0], nslots + 1, 0,
                                   sink))
                    self._tick_cow_copies += 1
                    self._tick_cow_bytes += (2.0 * ps
                                             * self._kv_token_bytes)
            self._tree_branches[slot] = (wlo, whi, branches)
        if events:
            self._cow_call(events)
        return jnp.asarray(btables), jnp.asarray(bvalid)

    def _commit_tree(self, bad_mask: np.ndarray, winners: np.ndarray
                     ) -> None:
        """Resolve this tick's staged branches from the packed winner row:
        the winning branch's window pages are adopted into the slot's page
        list (the device table already switched inside the tick), the
        replaced window pages (private + unpublished, per the barrier)
        free immediately, and every losing branch releases. A
        sentinel-flagged slot adopts nothing; its branch pages are
        scrubbed before release (the bad verify pass wrote non-finite KV
        into them, and free storage must never be NaN)."""
        for slot, (wlo, whi, branches) in self._tree_branches.items():
            w = int(winners[slot])
            bad = bool(bad_mask[slot])
            for i, bp in enumerate(branches):
                if bp is None:
                    continue
                if not bad and w == i + 1:
                    pages = self._slot_pages[slot]
                    old = pages[wlo:whi + 1]
                    pages[wlo:whi + 1] = bp
                    self.pool.release_all(old)
                else:
                    if bad:
                        self._scrub_pages(bp)
                    self.pool.release_all(bp)
        self._tree_branches = {}

    # -- paged admission (DESIGN.md §14) --------------------------------------

    def _pages_needed(self, prompt_len: int, max_tokens: int) -> int:
        """Worst-case (no-hit) page demand of a request: its full possible
        context, prompt + budget, capped at max_len. Speculative mode books
        ``spec_k`` extra tokens — a verify tick transiently writes up to k
        draft positions past the committed length, and booking them keeps
        those writes in the slot's own (private, masked-out) pages instead
        of colliding in the shared sink page."""
        ctx = min(prompt_len + max_tokens + self.scfg.spec_k,
                  self.scfg.max_len)
        return -(-ctx // self.scfg.page_size)

    def _tree_extra(self) -> int:
        """Per-slot *transient* page demand of tree speculation (DESIGN.md
        §18): each of the ``spec_tree_m - 1`` extra branches claims a
        private copy of the write window for one tick — at most
        ``(ps - 1 + k) // ps + 1`` pages, the worst alignment of a
        k+1-token span. Booked by the admission gate (so steady-state
        ticks can stage their branches) but never attached to a slot;
        a pool race at staging time degrades that slot's tick to the
        linear branch-0 path instead of failing."""
        scfg = self.scfg
        if scfg.spec_tree_m <= 1:
            return 0
        ps = scfg.page_size
        return (scfg.spec_tree_m - 1) * ((ps - 1 + scfg.spec_k) // ps + 1)

    def _pages_needed_group(self, prompt_len: int, max_tokens: int,
                            n_best: int) -> int:
        """Worst-case page demand of an ``n_best``-way fork group: the
        parent's full demand plus, per child, a private decode tail (the
        blocks past the shared committed prompt) and one COW copy of the
        partial boundary block. ``prompt_len // ps`` is exactly the shared
        FULL blocks — the partial boundary block is shared at fork time but
        each diverging writer (except the last, which owns it outright)
        pays one copy, so it counts against every child. Tree mode adds
        each decoding slot's transient branch windows on top."""
        need = self._pages_needed(prompt_len, max_tokens)
        shared_full = prompt_len // self.scfg.page_size
        return (need + (n_best - 1) * (need - shared_full)
                + n_best * self._tree_extra())

    def _defer_admission(self, req: Request, hits: List[int], n_hit0: int,
                         n_blocks: int, rest: List[Request]) -> None:
        """The one deferral path for a selected-but-unallocatable paged
        admission: release the retained hit pages, roll back the lookup's
        stats booking (the retry re-runs lookup — without the unbook each
        deferral would double-count its hits/misses and inflate
        ``PoolStats.hit_rate``), and requeue head-of-line.

        Backpressure rung (DESIGN.md §17): with ``guard.admit_max_retries``
        set, each deferral of the same uid counts; past the cap the request
        is shed (failed fast) instead of retried, and with
        ``guard.admit_backoff`` set the retry is additionally delayed by an
        exponentially growing tick window — a pool-exhaustion spike stops
        burning a full select+lookup per tick on a request that cannot fit."""
        self.pool.release_all(hits)
        self.pool.unbook_lookup(n_hit0, n_blocks)
        guard = self.guard
        n = self._defer_counts.get(req.uid, 0) + 1
        self._defer_counts[req.uid] = n
        if guard.admit_max_retries > 0 and n > guard.admit_max_retries:
            self._defer_counts.pop(req.uid, None)
            self._retry_after.pop(req.uid, None)
            self._pending_shed.append(req)
            self.scheduler.requeue_front(rest)
            return
        if guard.admit_backoff > 0:
            delay = min(guard.admit_backoff * 2 ** (n - 1), 32)
            self._retry_after[req.uid] = self._tick_idx + delay
        self.scheduler.requeue_front([req] + rest)

    def _admit_paged(self, finished: List[Request]) -> "_AdmitInfo":
        """Paged admission tick: select new requests that fit the pool,
        look up their prefix blocks, allocate suffix+decode pages, then
        advance EVERY in-flight prefill (new and continuing) by one chunk
        in a single ``paged_extend`` call. With ``prefill_chunk == 0`` the
        whole suffix lands in one call (the dense-equivalent behaviour,
        minus the shared prefix); with a chunk size, per-tick prefill work
        is bounded by ``max_slots * prefill_chunk`` tokens regardless of
        prompt length — the tick-time tail-latency bound."""
        scfg = self.scfg
        ps = scfg.page_size
        nslots, nb = scfg.max_slots, self._blocks_per_slot
        # never-fittable guard: a queued request whose worst-case demand
        # exceeds the whole pool can never be admitted (fits() false
        # forever -> FIFO head-of-line livelock). submit() rejects these,
        # but requests can reach the queue directly (scheduler.submit) or
        # predate a config that raised the demand (spec_k) — fail them
        # fast, with no stats booked (they never ran a lookup). The
        # verdict per request is immutable, so it is computed once per
        # uid (the memo is pruned at admission, bounding it to queue
        # depth).
        def never_fits(r: Request) -> bool:
            if r.uid in self._fit_checked:
                return False
            self._fit_checked.add(r.uid)
            return (self._pages_needed_group(len(r.prompt), r.max_tokens,
                                             r.n_best)
                    > self.pool.num_pages)

        for req in self.scheduler.drop(never_fits):
            self._fit_checked.discard(req.uid)
            req.done = True
            req.generated = []
            if req.n_best > 1:
                req.nbest = [[] for _ in range(req.n_best)]
            finished.append(req)
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        budget_pages = [self.pool.available]
        budget_slots = [len(free)]

        def fits(req: Request) -> bool:
            # backoff gate (DESIGN.md §17): a deferred request sits out its
            # retry window before consuming any page budget
            if self._retry_after.get(req.uid, 0) > self._tick_idx:
                return False
            # conservative: ignores hits (submit() guarantees need can be
            # met by an empty pool, so deferral always terminates). A
            # non-fitting request is NOT looked up — deferral by this gate
            # books no prefix stats to roll back. An n-best request books
            # its WHOLE fork group here — n_best slots and the group's
            # worst-case pages — so the fork at activation can only fail
            # under a later cross-tick pool race (DESIGN.md §18).
            need = self._pages_needed_group(len(req.prompt),
                                            req.max_tokens, req.n_best)
            if need > budget_pages[0] or req.n_best > budget_slots[0]:
                return False
            budget_pages[0] -= need
            budget_slots[0] -= req.n_best
            return True

        reqs = self.scheduler.select(len(free), fits=fits,
                                     now=self._tick_idx)
        admitted = len(reqs)
        hit_tokens = 0
        hit_sq = 0.0
        # slots assign from a pool, not positionally: an n-best parent
        # consumes its own slot PLUS one reserved slot per child
        slot_pool = list(free)
        for j, req in enumerate(reqs):
            self._fit_checked.discard(req.uid)
            slot = slot_pool[0]
            plen = len(req.prompt)
            blocks = (block_tokens(req.prompt, ps)
                      if scfg.prefix_cache else [])
            hits = self.pool.lookup(blocks)
            n_hit0 = len(hits)
            # at least one suffix token must run to produce the sampling
            # logits, so a fully cached prompt re-computes its last block
            while hits and len(hits) * ps >= plen:
                self.pool.release(hits.pop())
            shared = len(hits) * ps
            fresh = self.pool.alloc(
                self._pages_needed(plen, req.max_tokens) - len(hits))
            if fresh is None:       # estimate raced capacity: defer
                self._defer_admission(req, hits, n_hit0, len(blocks),
                                      reqs[j + 1:])
                admitted = j
                break
            pages = hits + fresh
            # admission succeeded: clear any backpressure bookkeeping
            self._defer_counts.pop(req.uid, None)
            self._retry_after.pop(req.uid, None)
            slot_pool.pop(0)
            self.slot_req[slot] = req
            self._slot_pages[slot] = pages
            self._prefilling[slot] = {
                "req": req, "plen": plen, "next": shared,
                "blocks": blocks, "pages": pages}
            if req.n_best > 1:
                # mint + reserve the fork children NOW (one per extra
                # stream): they hold slots — excluded from decode via
                # _fork_wait — until the parent's final chunk activates
                # and _fork_slots fans the committed pages out
                req.fork_group = req.uid
                self._fork_groups[req.uid] = {
                    "req": req, "k": req.n_best, "streams": {}}
                req.fork_idx = 0
                kids = [slot_pool.pop(0) for _ in range(req.n_best - 1)]
                self._fork_children[slot] = kids
                for i, kid in enumerate(kids):
                    self._uid += 1
                    child = Request(
                        self._uid, req.prompt, req.max_tokens,
                        req.temperature, fork_group=req.uid,
                        fork_idx=i + 1, submit_tick=self._tick_idx)
                    self.slot_req[kid] = child
                    self._fork_wait[kid] = slot
            hit_tokens += shared
            hit_sq += float(shared) ** 2
        # one extend call advances every in-flight prefill by one chunk
        work = sorted(self._prefilling.items())
        if not work:
            return _AdmitInfo(admitted=admitted,
                              prefix_hit_tokens=hit_tokens)
        # even with chunking off, cap the implicit chunk at the chunked-
        # SDPA threshold: extend's attention materializes O(C * window)
        # fp32 logits per layer, and dense prefill bounds the same blow-up
        # by switching to sdpa_q_chunked at this width
        from repro.models.layers import _CHUNKED_SDPA_THRESHOLD
        chunk_cap = scfg.prefill_chunk or min(scfg.max_len,
                                              _CHUNKED_SDPA_THRESHOLD)
        call_lens = [min(w["plen"] - w["next"], chunk_cap)
                     for _, w in work]
        # every call_len <= chunk_cap, so the bucket always covers them
        width = _bucket_len(max(call_lens), cap=chunk_cap)
        toks = np.zeros((nslots, width), np.int32)
        starts = np.zeros(nslots, np.int32)
        lens = np.zeros(nslots, np.int32)
        slots = np.full(nslots, nslots + 1, np.int32)   # OOB rows drop
        # row-major page tables for this call; unused rows write to sink
        tables = np.full((nslots, nb), self.pool.sink, np.int32)
        budgets = np.ones(nslots, np.int32)
        temps = np.zeros(nslots, np.float32)
        uids = np.zeros(nslots, np.int32)
        final = np.zeros(nslots, bool)
        for j, ((slot, w), clen) in enumerate(zip(work, call_lens)):
            req = w["req"]
            toks[j, :clen] = req.prompt[w["next"]:w["next"] + clen]
            starts[j] = w["next"]
            lens[j] = clen
            slots[j] = slot
            budgets[j] = req.max_tokens
            temps[j] = (scfg.temperature if req.temperature is None
                        else req.temperature)
            uids[j] = req.uid
            final[j] = w["next"] + clen >= w["plen"]
            row = w["pages"] + [self.pool.sink] * (nb - len(w["pages"]))
            tables[j] = row[:nb]
        self.state, done = self._admit_exe(width)(
            self.params, self.state, jnp.asarray(toks), jnp.asarray(starts),
            jnp.asarray(lens), jnp.asarray(slots), jnp.asarray(tables),
            jnp.asarray(budgets), jnp.asarray(temps), jnp.asarray(uids),
            jnp.asarray(final))
        done_mask = self._readback(done)
        computed = int(lens.sum())
        # causal-attention FLOPs of the chunk: sum over rows of
        # end^2 - start^2 (the start=0 case reduces to the dense bill)
        ends = (starts + lens).astype(np.int64)
        attn_sq = float((ends ** 2 - starts.astype(np.int64) ** 2).sum())
        # recovery billing (DESIGN.md §17): rows re-prefilling a
        # quarantined/fallback continuation bill their share of this call
        # separately — the energy a fault-free run never spends. Same
        # formulas as the aggregate bill below, factored per row.
        rec_tok, rec_fl, rec_by = 0, 0.0, 0.0
        for j, ((slot, w), clen) in enumerate(zip(work, call_lens)):
            uid = w["req"].uid
            if uid in self._recovering:
                rec_tok += clen
                rec_fl += costing.prefill_span_flops(
                    self._matmul_elems, self._n_attn, self._attn_dims,
                    int(starts[j]), clen)
                row_gather = (-(-int(starts[j]) // ps) * ps
                              if self.cfg.decode_kernel else nb * ps)
                rec_by += self._kv_token_bytes * (row_gather + clen)
                if final[j]:
                    self._recovering.discard(uid)
        for j, ((slot, w), clen) in enumerate(zip(work, call_lens)):
            if final[j]:
                del self._prefilling[slot]
                self._host_gen[slot] = 1
                # publish the prompt's full, now-frozen blocks for reuse,
                # chaining each key through the CANONICAL page publish()
                # returns — two slots computing the same prefix in the same
                # tick must converge on one chain, not register a shadow
                # chain no lookup can reach
                if scfg.prefix_cache:
                    parent = ROOT
                    for bi, block in enumerate(w["blocks"]):
                        parent = self.pool.publish(w["pages"][bi], parent,
                                                   block)
                kids = self._fork_children.pop(slot, None)
                if done_mask[j]:
                    if kids is not None:
                        self._cancel_fork(slot, kids)
                    self._finish_slot(slot, finished)
                elif kids is not None:
                    # the parent's prompt KV is committed and its first
                    # token sampled: fan the group out (DESIGN.md §18)
                    self._fork_slots(slot, kids)
            else:
                w["next"] += clen
        # cached-window gather bill (DESIGN.md §16) — what the extend path
        # ACTUALLY moves to read KV behind the chunk, not the logical
        # window. Kernel path: the page-table index_map clamps dead steps,
        # so each row fetches exactly ceil(start / page_size) pages. XLA
        # fallback: _paged_gather materializes the FULL table width for
        # every slot row, per call — the fragmented-prefill under-billing
        # this field exists to correct.
        if self.cfg.decode_kernel:
            gather_tokens = float(sum(-(-int(s) // ps) * ps
                                      for s in starts[:len(work)]))
        else:
            gather_tokens = float(nslots * nb * ps)
        gather_bytes = self._kv_token_bytes * gather_tokens
        return _AdmitInfo(
            admitted=admitted, prefill_tokens=computed, weight_passes=1,
            prefix_hit_tokens=hit_tokens,
            # extend reads the cached window behind each chunk (the gather
            # bill above) and writes the chunk's KV — page-granular
            kv_bytes=gather_bytes + self._kv_token_bytes * computed,
            gather_bytes=gather_bytes,
            flops=(2.0 * self._matmul_elems * computed
                   + 2.0 * self._n_attn * self._attn_dims * attn_sq),
            uids=uids[:len(work)],
            saved_bytes=self._kv_token_bytes * hit_tokens,
            saved_flops=(2.0 * self._matmul_elems * hit_tokens
                         + 2.0 * self._n_attn * self._attn_dims * hit_sq),
            recovery_tokens=rec_tok, recovery_flops=rec_fl,
            recovery_bytes=rec_by)

    # -- degradation ladder rungs (DESIGN.md §17) -----------------------------

    def _maybe_spec_backoff(self, accepted: int, n_ok: int) -> None:
        """Acceptance-collapse rung: EWMA the per-tick draft acceptance
        rate; when it sinks below the threshold, halve spec-k (its own
        cached executable — no retrace of healthy k). Never re-escalates
        within a run: flapping between executables would churn compiles."""
        guard = self.guard
        if guard.spec_backoff_threshold <= 0.0 or self._cur_spec_k <= 1:
            return
        if n_ok <= 0:
            return
        self._accept_ewma.update(accepted / float(self._cur_spec_k * n_ok))
        if (self._accept_ewma.n >= guard.spec_backoff_window
                and self._accept_ewma.value < guard.spec_backoff_threshold):
            self._cur_spec_k = max(1, self._cur_spec_k // 2)
            self._tick = self._tick_for(self._cur_spec_k)
            self.spec_backoffs += 1
            self._accept_ewma = Ewma(alpha=guard.ewma_alpha)

    def _maybe_pause_compaction(self, wall_s: float) -> None:
        """Latency-pressure rung: EWMA the tick wall time; a tick slower
        than ``stall_factor`` x the smoothed baseline pauses compaction
        (the one deferrable chunk of tick work) for a recovery window."""
        guard = self.guard
        prev = self._tick_wall_ewma.value
        seen = self._tick_wall_ewma.n
        self._tick_wall_ewma.update(wall_s)
        if guard.stall_factor <= 0.0 or seen < 3 or prev is None:
            return
        if (wall_s > guard.stall_factor * prev
                and self._tick_idx + 1 >= self._compact_pause_until):
            self._compact_pause_until = (self._tick_idx + 1
                                         + guard.compact_pause_ticks)
            self.compaction_pauses += 1

    def _drift_check(self) -> None:
        """Quantization-drift rung: every ``drift_check_interval`` ticks,
        replay ONE decoding greedy slot's next-token prediction through the
        fp32 oracle (teacher-forced prefill of prompt + committed tokens
        minus the last) and compare argmax to what the engine emitted —
        the serve-time sibling of quality.token_agreement. Disagreement
        EWMA above ``drift_threshold`` triggers the fp fallback."""
        guard = self.guard
        cands = [i for i, r in enumerate(self.slot_req)
                 if r is not None and i not in self._prefilling
                 and self._host_gen[i] >= 2
                 and (r.temperature if r.temperature is not None
                      else self.scfg.temperature) == 0.0]
        if not cands:
            return
        slot = cands[self._drift_rr % len(cands)]
        self._drift_rr += 1
        req = self.slot_req[slot]
        g = self._host_gen[slot]
        toks = self._readback(self.state.out_buf[slot, :g])
        o_params, o_cfg = self._oracle
        seq = np.concatenate([np.asarray(req.prompt, np.int32),
                              toks[:-1].astype(np.int32)])
        lg, _ = tf_lib.prefill(o_params, o_cfg, jnp.asarray(seq[None]),
                               cache_dtype=jnp.float32)
        want = int(jnp.argmax(lg[0, -1]))
        self._drift_ewma.update(0.0 if want == int(toks[-1]) else 1.0)
        if (self._drift_ewma.n >= guard.drift_min_checks
                and self._drift_ewma.value > guard.drift_threshold):
            self._fallback_to_fp()

    def _fallback_to_fp(self) -> None:
        """int8 -> fp fallback: capture every live slot as a continuation,
        requeue them head-of-line, and rebuild the whole runtime (pool,
        caches, executables) from the fp oracle params. A heavy, one-way
        rung — quantization drift means every future token is suspect."""
        if self._fell_back:
            return
        conts: List[Request] = []
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            conts.append(self._capture_slot(slot))
        self.scheduler.requeue_front(conts)
        self.fp_fallbacks += 1
        self._fell_back = True
        self._drift_ewma = Ewma(alpha=self.guard.ewma_alpha)
        self._init_runtime(*self._oracle)

    def _run_audit(self) -> None:
        """Page-pool integrity audit: the pool's own invariants plus the
        engine-side ownership reconciliation (every page's refcount equals
        its appearances across slot page lists and injector spike holds;
        no page listed twice by one slot). Violations are recorded, never
        raised — detection must not be the crash."""
        violations = self.pool.audit()
        violations += reconcile_ownership(self.pool, self._slot_pages,
                                          self._spike_holds)
        if violations:
            self.audit_failures += len(violations)
            self.audit_log.extend(
                f"tick {self._tick_idx}: {v}" for v in violations)

    # -- main tick ------------------------------------------------------------

    @_step_span
    def step(self) -> List[Request]:
        """Admit + one fused decode tick. Returns finished requests.

        In a profile the tick is the step span ``serve`` and its host work
        the spans ``serve.admit``, ``serve.compact``, ``serve.cow_barrier``,
        ``serve.dispatch``, ``serve.readback``, ``serve.finish``,
        ``serve.audit``, ``serve.journal`` and ``serve.snapshot``; the
        admission and finish spans carry their requests' uids."""
        t0 = time.monotonic()
        tick = self._tick_idx
        finished: List[Request] = []
        self._tick_shed = 0
        self._tick_quarantined = 0
        self._rb_retries_tick = 0
        self._tick_cow_bytes = 0.0
        self._tick_cow_copies = 0
        self._tick_forks = 0
        self._tick_fork_saved_bytes = 0.0
        self._tick_fork_saved_flops = 0.0
        inj0 = (self._injector.faults_injected
                if self._injector is not None else 0)
        # deadline shedding (DESIGN.md §17): expire queued requests whose
        # wait exceeded their deadline BEFORE spending admission work on
        # them — they complete failed-fast, never silently vanish
        for req in self.scheduler.drop(
                lambda r: (r.deadline_ticks is not None
                           and r.submit_tick >= 0
                           and tick - r.submit_tick > r.deadline_ticks)):
            self._shed_request(req, finished)
        # host-side fault events land before admission so a pool spike
        # pressures THIS tick's admission and a KV flip is what the decode
        # tick observes
        self._apply_host_faults(tick)
        with jax.profiler.TraceAnnotation("serve.admit") as span:
            adm = self._admit(finished)
            span.set_metadata(uids=adm.uids)
        # admission-retry exhaustion sheds, queued by _defer_admission
        for req in self._pending_shed:
            self._shed_request(req, finished)
        self._pending_shed = []
        with jax.profiler.TraceAnnotation("serve.compact"):
            moves = self._maybe_compact() if self.scfg.paged else 0
        # decoding slots only: mid-prefill paged slots and fork-reserved
        # child slots occupy a slot but don't produce decode tokens until
        # their final chunk / their parent's activation releases them
        active = [i for i, r in enumerate(self.slot_req)
                  if r is not None and i not in self._prefilling
                  and i not in self._fork_wait]
        if self.scfg.paged and active:
            # COW write barrier (DESIGN.md §18): every page this tick
            # writes must be private to its slot BEFORE the tick runs
            with jax.profiler.TraceAnnotation("serve.cow_barrier"):
                active = self._cow_barrier(active)
        # live context per decoding slot: the tick attends lengths pos+1 =
        # prompt + generated-so-far — captured before finishes clear the
        # slot (page-granular KV read bill)
        ctx = sum(len(self.slot_req[i].prompt) + self._host_gen[i]
                  for i in active) if self.scfg.paged else 0
        spec_k = self._cur_spec_k
        emitted = len(active)       # decode tokens this tick (plain: 1/slot)
        accepted = 0
        n_bad = 0
        if active:
            poison = self._zero_poison
            if self._injector is not None:
                pv = self._injector.logit_poison(tick, active,
                                                 self.scfg.max_slots)
                if pv is not None:
                    poison = jnp.asarray(pv)
            if spec_k > 0 and self.scfg.spec_tree_m > 1:
                # tree speculation (DESIGN.md §18): stage per-branch
                # forked windows, run the folded verify, then resolve the
                # winner's page adoption on the host
                btables, bvalid = self._prepare_tree(active)
                with jax.profiler.TraceAnnotation("serve.dispatch"):
                    self.state, packed = self._tick(self.params, self.state,
                                                    poison, btables, bvalid)
                arr = self._checked_readback(
                    packed, self._validate_tree_packed, tick)
                done_mask = arr[0].astype(bool)
                n_emit = arr[1]
                bad_mask = arr[2].astype(bool)
                self._commit_tree(bad_mask, arr[3])
                emitted = int(n_emit.sum())
                accepted = int(np.maximum(n_emit - 1, 0).sum())
                for i in active:
                    self._host_gen[i] += int(n_emit[i])
            elif spec_k > 0:
                with jax.profiler.TraceAnnotation("serve.dispatch"):
                    self.state, packed = self._tick(self.params, self.state,
                                                    poison)
                # the ONLY hot-path transfer (validated: the injector may
                # garble/drop it, and the device buffer survives a re-read)
                arr = self._checked_readback(
                    packed, self._validate_spec_packed, tick)
                done_mask = arr[0].astype(bool)
                n_emit = arr[1]
                bad_mask = arr[2].astype(bool)
                emitted = int(n_emit.sum())
                accepted = int(np.maximum(n_emit - 1, 0).sum())
                for i in active:
                    self._host_gen[i] += int(n_emit[i])
            else:
                with jax.profiler.TraceAnnotation("serve.dispatch"):
                    self.state, packed = self._tick(self.params, self.state,
                                                    poison)
                arr = self._checked_readback(
                    packed, self._validate_plain_packed, tick)
                done_mask = arr[0].astype(bool)
                bad_mask = arr[1].astype(bool)
                for i in active:
                    if not bad_mask[i]:
                        self._host_gen[i] += 1
                emitted = int(sum(1 for i in active if not bad_mask[i]))
            for i in np.nonzero(done_mask)[0]:
                req = self.slot_req[int(i)]
                if req is not None and int(i) not in self._prefilling:
                    with jax.profiler.TraceAnnotation("serve.finish",
                                                      uid=req.uid):
                        self._finish_slot(int(i), finished)
            # sentinel-flagged slots made no progress and self-deactivated
            # on device — quarantine them: teardown + head-of-line
            # continuation. Unaffected slots' streams are untouched.
            n_bad = int(sum(1 for i in active if bad_mask[i]))
            for i in np.nonzero(bad_mask)[0]:
                if (self.slot_req[int(i)] is not None
                        and int(i) not in self._prefilling):
                    self._quarantine_slot(int(i))
            if spec_k > 0:
                self._maybe_spec_backoff(accepted, len(active) - n_bad)
        # modeled traffic/compute of the tick (DESIGN.md §12/§14/§15):
        # every jitted call streams the full weight tree once; the dense
        # decode reads the whole resident KV payload, while the paged
        # decode reads only the active slots' live context (page-granular)
        # — admission terms come pre-computed from the admit path. The
        # speculative tick bills its draft and verify phases separately:
        # the drafter's cost depends on the drafter (n-gram: one history
        # scan; oracle: k more weight streams), the verify pass streams
        # the weights ONCE for k+1 positions per slot — the amortization
        # the whole design exists for.
        wb = kvb = fl = 0.0
        d_fl = d_by = v_fl = v_by = 0.0
        na = len(active)
        if active:
            if spec_k > 0:
                width = spec_k + 1
                oracle = self.scfg.spec_drafter == "oracle"
                # tree mode folds m branch rows per slot into the one
                # verify pass: m x the row compute and KV traffic, still
                # ONE weight stream — the fold's whole economy
                m_eff = self.scfg.spec_tree_m
                v_fl = costing.spec_verify_flops(
                    self._matmul_elems, self._n_attn, self._attn_dims,
                    ctx * m_eff, na * m_eff, width)
                # verify: one weight stream; KV = live context read once
                # plus the chunk's write+readback (page-granular)
                v_kv = self._kv_token_bytes * (ctx
                                               + 2.0 * width * na) * m_eff
                v_by = self.weight_bytes + v_kv
                if oracle:
                    d_fl = costing.spec_oracle_draft_flops(
                        self._matmul_elems, self._n_attn, self._attn_dims,
                        ctx, na, spec_k)
                    d_kv = self._kv_token_bytes * (
                        spec_k * ctx + na * spec_k * (spec_k - 1) / 2.0)
                    d_wb = spec_k * self.weight_bytes
                else:
                    # n-gram drafter: one int32 history scan per slot
                    # (tree mode emits m branches from the same scan —
                    # bill the extra gather lanes, still no weights)
                    d_kv = 4.0 * self.scfg.max_len * na * m_eff
                    d_wb = 0.0
                d_by = d_wb + d_kv
                wb += self.weight_bytes + d_wb
                kvb += v_kv + d_kv
                fl += v_fl + d_fl
            else:
                wb += self.weight_bytes
                if self.scfg.paged:
                    kvb += self._kv_token_bytes * ctx
                    fl += costing.decode_tick_flops(
                        self._matmul_elems, self._n_attn, self._attn_dims,
                        ctx, na)
                else:
                    kvb += self.kv_cache_bytes
                    fl += na * (2.0 * self._matmul_elems
                                + 4.0 * self._n_attn * self._attn_dims
                                * self.scfg.max_len)
        if adm.weight_passes:
            wb += self.weight_bytes * adm.weight_passes
        kvb += adm.kv_bytes
        fl += adm.flops
        if moves:
            # each relocated page is one pool read + one pool write
            kvb += 2.0 * moves * self.scfg.page_size * self._kv_token_bytes
        # COW copies (barrier + tree boundary copies) are real page
        # traffic: already accumulated per event, billed into kv_bytes AND
        # broken out first-class (DESIGN.md §18)
        kvb += self._tick_cow_bytes
        # periodic detection rungs (rare paths; their readbacks/compute are
        # off the hot tick and bounded by their intervals)
        guard = self.guard
        if (guard.drift_check_interval > 0 and not self._fell_back
                and self.scfg.quant == "int8"
                and tick % guard.drift_check_interval == 0):
            self._drift_check()
        if (guard.audit_interval > 0 and self.scfg.paged
                and tick % guard.audit_interval == 0):
            with jax.profiler.TraceAnnotation("serve.audit"):
                self._run_audit()
        degraded = int(self._cur_spec_k != self.scfg.spec_k
                       or self._fell_back
                       or tick < self._compact_pause_until)
        wall = time.monotonic() - t0
        self._maybe_pause_compaction(wall)
        m = StepMetrics(tokens=emitted, active_slots=na,
                        wall_s=wall,
                        prefill_tokens=adm.prefill_tokens,
                        admitted=adm.admitted,
                        queue_depth=len(self.scheduler),
                        weight_bytes=wb, kv_bytes=kvb, flops=fl,
                        prefix_hit_tokens=adm.prefix_hit_tokens,
                        saved_bytes=adm.saved_bytes,
                        saved_flops=adm.saved_flops,
                        spec_draft_tokens=spec_k * na,
                        spec_accepted_tokens=accepted,
                        draft_flops=d_fl, draft_bytes=d_by,
                        verify_flops=v_fl, verify_bytes=v_by,
                        prefill_gather_bytes=adm.gather_bytes,
                        compaction_moves=moves,
                        faults_injected=(
                            self._injector.faults_injected - inj0
                            if self._injector is not None else 0),
                        quarantined=self._tick_quarantined,
                        shed=self._tick_shed,
                        recovery_tokens=adm.recovery_tokens,
                        recovery_flops=adm.recovery_flops,
                        recovery_bytes=adm.recovery_bytes,
                        degraded=degraded,
                        readback_retries=self._rb_retries_tick,
                        cow_bytes=self._tick_cow_bytes,
                        cow_copies=self._tick_cow_copies,
                        forks=self._tick_forks,
                        fork_saved_bytes=self._tick_fork_saved_bytes,
                        fork_saved_flops=self._tick_fork_saved_flops)
        self.last_metrics = m
        self.metrics_log.append(m)
        if self.accountant is not None:
            self.accountant.observe_serve(m)
        self._tick_idx += 1
        if self._replaying:
            # replayed recompute is physically honest work already billed
            # via observe_serve above — restore_j breaks the SAME joules
            # out as the recovery-cost channel (DESIGN.md §19), so the
            # checkpoint-interval J/token tradeoff is first-class
            self.replayed_ticks += 1
            self.restore_flops += m.flops
            self.restore_bytes += m.bytes_moved
            if self.accountant is not None:
                self.accountant.observe_durability(
                    restore_flops=m.flops, restore_bytes=m.bytes_moved,
                    replayed_ticks=1)
        elif self._journal is not None:
            # tick record first (replay needs every tick, even idle ones:
            # fault schedules and deadlines key on absolute tick index),
            # THEN the snapshot — its journal_seq cut must sit after this
            # tick's record so replay resumes exactly at tick_idx
            with jax.profiler.TraceAnnotation("serve.journal"):
                d_journal = self._journal.append_tick(
                    tick=tick,
                    finished=[[r.uid,
                               [int(t) for t in r.generated],
                               ([[int(t) for t in s] for s in r.nbest]
                                if r.nbest is not None else None)]
                              for r in finished])
            self.journal_bytes_total += d_journal
            d_snapshot = 0
            if (self.scfg.checkpoint_interval > 0
                    and self._tick_idx % self.scfg.checkpoint_interval
                    == 0):
                with jax.profiler.TraceAnnotation("serve.snapshot"):
                    d_snapshot = self._write_snapshot()
            if self.accountant is not None:
                self.accountant.observe_durability(
                    journal_bytes=d_journal, snapshot_bytes=d_snapshot,
                    snapshots=1 if d_snapshot else 0)
        return finished

    def run_until_drained(self, max_ticks: int = 10000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_ticks):
            done.extend(self.step())
            if not len(self.scheduler) and all(r is None
                                               for r in self.slot_req):
                break
        return done

    # -- durability: crash-consistent snapshot + journal replay ---------------

    def _write_snapshot(self) -> int:
        """Persist a crash-consistent checkpoint of the full engine:
        the device tree (caches, page table, positions, RNG keys) plus the
        complete host mirror (snapshot.host_state_dict) ride one atomic
        CheckpointManager save. ``journal_seq`` marks the replay cut:
        journal records with seq below it are baked into this snapshot;
        restore replays everything at or after it. Returns bytes written
        (billed as durability DRAM traffic)."""
        step = self._tick_idx
        extra = host_state_dict(self)
        extra["journal_seq"] = self._journal.seq
        self._ckpt_mgr.save(step, self.state, extra=extra)
        d = self._ckpt_mgr._step_dir(step)
        nbytes = sum(os.path.getsize(os.path.join(d, f))
                     for f in os.listdir(d))
        self.snapshots_taken += 1
        self.snapshot_bytes_total += nbytes
        return nbytes

    def restore(self) -> List[Request]:
        """Warm restart from disk (DESIGN.md §19): load the latest
        snapshot (if any), then deterministically replay the journal tail.
        Must be called on a FRESH engine built with the same ServeConfig
        and the same ``checkpoint_dir`` as the dead one. Replayed ticks
        repeat the original run bit-identically (seeded RNG folds, sorted
        host iteration, seeded fault plans) — divergence or a corrupted
        snapshot fails loudly rather than serving wrong streams.

        Returns every request finished up to now — both pre-crash
        finishes reconstructed from the journal and finishes produced by
        replay. Delivery is at-least-once: callers that already streamed
        pre-crash results dedupe by uid."""
        if self._ckpt_mgr is None or self._journal is None:
            raise RuntimeError("restore() requires checkpoint_dir")
        if self._tick_idx != 0 or self.metrics_log or len(self.scheduler):
            raise RuntimeError("restore() must run on a fresh engine — "
                               "this one has already ticked or admitted")
        journal_seq = 0
        step = self._ckpt_mgr.latest_step()
        if step is not None:
            extra = self._ckpt_mgr.peek_extra(step)
            # config gate FIRST: a snapshot from a differently-configured
            # engine must be diagnosed as such, not as a shape mismatch
            # halfway through loading the device tree
            check_fingerprint(self.scfg, extra.get("fingerprint", {}))
            if extra.get("fell_back"):
                # the snapshot's device tree is fp — rebuild the runtime
                # from the fp oracle BEFORE restoring so dtypes line up
                self._fell_back = True
                self._init_runtime(*self._oracle)
            _, tree, extra = self._ckpt_mgr.restore(step,
                                                    target=self.state)
            self.state = tree
            install_host_state(self, extra)
            journal_seq = int(extra.get("journal_seq", 0))
            if self.scfg.paged:
                # snapshot-load shares the audit's reconciliation checker
                # (DESIGN.md §19) — but HERE violations refuse, loudly:
                # restoring inconsistent ownership would corrupt streams
                violations = self.pool.audit()
                violations += reconcile_ownership(
                    self.pool, self._slot_pages, self._spike_holds)
                if violations:
                    raise RuntimeError(
                        "snapshot failed consistency check: "
                        + "; ".join(violations))
            self._tick = self._tick_for(self._cur_spec_k)
        recovered: List[Request] = []
        submits: Dict[int, dict] = {}
        post: List[dict] = []
        for rec in self._journal.records():
            if rec["kind"] == "submit":
                submits[int(rec["uid"])] = rec
            if rec["seq"] < journal_seq:
                if rec["kind"] == "tick":
                    # pre-snapshot finishes: reconstruct the completed
                    # requests so the caller sees every result exactly as
                    # the dead engine emitted it
                    for uid, gen, nbest in rec["finished"]:
                        s = submits[int(uid)]
                        recovered.append(Request(
                            int(uid),
                            np.asarray(s["prompt"], np.int32),
                            max_tokens=int(s["max_tokens"]),
                            temperature=s["temperature"],
                            generated=[int(t) for t in gen],
                            done=True,
                            deadline_ticks=s["deadline_ticks"],
                            submit_tick=int(s["tick"]),
                            n_best=int(s["n_best"]),
                            nbest=([[int(t) for t in st] for st in nbest]
                                   if nbest is not None else None)))
            else:
                post.append(rec)
        self._replaying = True
        try:
            for rec in post:
                if rec["kind"] == "submit":
                    uid = self.submit(
                        np.asarray(rec["prompt"], np.int32),
                        max_tokens=int(rec["max_tokens"]),
                        temperature=rec["temperature"],
                        deadline_ticks=rec["deadline_ticks"],
                        n_best=int(rec["n_best"]))
                    if uid != int(rec["uid"]):
                        raise RuntimeError(
                            f"replay diverged: journaled submit uid "
                            f"{rec['uid']}, replay assigned {uid}")
                else:
                    if int(rec["tick"]) != self._tick_idx:
                        raise RuntimeError(
                            f"replay diverged: journal at tick "
                            f"{rec['tick']}, engine at {self._tick_idx}")
                    fins = self.step()
                    got = {int(r.uid): ([int(t) for t in r.generated],
                                        ([[int(t) for t in st]
                                          for st in r.nbest]
                                         if r.nbest is not None else None))
                           for r in fins}
                    want = {int(u): ([int(t) for t in g],
                                     ([[int(t) for t in st] for st in nb]
                                      if nb is not None else None))
                            for u, g, nb in rec["finished"]}
                    if got != want:
                        raise RuntimeError(
                            f"replay diverged at tick {rec['tick']}: "
                            f"journaled finishes {sorted(want)} vs "
                            f"replayed {sorted(got)} (or streams differ)")
                    recovered.extend(fins)
        finally:
            self._replaying = False
        # kills at or before this tick already happened pre-crash; a
        # surviving fault plan must not re-fire them (crash loop)
        self._restore_boundary = self._tick_idx
        return recovered

    # -- aggregate metrics ----------------------------------------------------

    def summary(self) -> Dict[str, float]:
        """Aggregate run stats. Every ratio degrades to 0.0 — never NaN or
        a ZeroDivisionError — on degenerate runs (no ticks, no emitted
        tokens, no prefix lookups, all drafts rejected): summaries are
        read by dashboards and benches that must survive empty/drained
        workloads (regression-locked in tests/test_serve_spec.py)."""
        toks = sum(m.tokens for m in self.metrics_log)
        wall = sum(m.wall_s for m in self.metrics_log)
        out = {"ticks": len(self.metrics_log),
               "decode_tokens": toks,
               "prefill_tokens": sum(m.prefill_tokens
                                     for m in self.metrics_log),
               "wall_s": wall,
               "decode_tokens_per_s": toks / wall if wall > 0 else 0.0}
        if self.scfg.paged:
            hit = sum(m.prefix_hit_tokens for m in self.metrics_log)
            total = hit + out["prefill_tokens"]
            out["prefix_hit_tokens"] = hit
            out["prefix_hit_rate"] = hit / total if total > 0 else 0.0
            out["saved_bytes"] = sum(m.saved_bytes for m in self.metrics_log)
            out["prefill_gather_bytes"] = sum(m.prefill_gather_bytes
                                              for m in self.metrics_log)
            out["compaction_moves"] = sum(m.compaction_moves
                                          for m in self.metrics_log)
            out["pool_pages"] = self.pool.num_pages
            out["pool_pages_live"] = self.pool.live
            out["pool_hit_rate"] = self.pool.stats.hit_rate
            out["pool_alloc_run_failures"] = \
                self.pool.stats.alloc_run_failures
            # COW fork economy (DESIGN.md §18): copies are paid traffic,
            # fork_saved_* the duplicate-KV bill the forks did NOT pay
            out["cow_bytes"] = sum(m.cow_bytes for m in self.metrics_log)
            out["cow_copies"] = sum(m.cow_copies for m in self.metrics_log)
            out["forks"] = sum(m.forks for m in self.metrics_log)
            out["fork_saved_bytes"] = sum(m.fork_saved_bytes
                                          for m in self.metrics_log)
            out["fork_saved_flops"] = sum(m.fork_saved_flops
                                          for m in self.metrics_log)
            out["pool_forked_pages"] = self.pool.stats.forked_pages
            out["pool_cow_copies"] = self.pool.stats.cow_copies
        if self.scfg.spec_k > 0:
            drafted = sum(m.spec_draft_tokens for m in self.metrics_log)
            accepted = sum(m.spec_accepted_tokens for m in self.metrics_log)
            slot_ticks = sum(m.active_slots for m in self.metrics_log)
            out["spec_draft_tokens"] = drafted
            out["spec_accepted_tokens"] = accepted
            out["accept_rate"] = accepted / drafted if drafted > 0 else 0.0
            # emitted decode tokens per slot-tick: the multi-token win
            # (plain decode is exactly 1.0; upper bound spec_k + 1)
            out["accepted_tokens_per_tick"] = (
                toks / slot_ticks if slot_ticks > 0 else 0.0)
            out["spec_backoffs"] = self.spec_backoffs
            out["spec_k_current"] = self._cur_spec_k
        # resilience tier (DESIGN.md §17): every ratio 0.0-guards its
        # denominator like the rest of this summary — chaos summaries are
        # read by the bench gate on empty/fully-shed runs too
        n_ticks = len(self.metrics_log)
        done_total = self.n_shed + self.n_finished_ok
        rec_tok = sum(m.recovery_tokens for m in self.metrics_log)
        rec_fl = sum(m.recovery_flops for m in self.metrics_log)
        rec_by = sum(m.recovery_bytes for m in self.metrics_log)
        out["faults_injected"] = sum(m.faults_injected
                                     for m in self.metrics_log)
        out["quarantined"] = self.n_quarantined
        out["quarantine_rate"] = (self.n_quarantined / n_ticks
                                  if n_ticks > 0 else 0.0)
        out["shed"] = self.n_shed
        out["shed_rate"] = (self.n_shed / done_total
                            if done_total > 0 else 0.0)
        out["recovery_tokens"] = rec_tok
        out["recovery_j"] = (energy.compute_energy_j(rec_fl)
                             + energy.dram_energy_j(rec_by))
        out["recovery_j_per_token"] = (out["recovery_j"] / toks
                                       if toks > 0 else 0.0)
        out["degraded_ticks"] = sum(m.degraded for m in self.metrics_log)
        out["readback_retries"] = self.readback_retries_total
        out["fp_fallbacks"] = self.fp_fallbacks
        out["compaction_pauses"] = self.compaction_pauses
        out["audit_failures"] = self.audit_failures
        # durability tier (DESIGN.md §19): all 0.0 on an engine that never
        # checkpoints — the zero-state guard benches and dashboards rely on
        out["snapshots_taken"] = self.snapshots_taken
        out["snapshot_bytes"] = self.snapshot_bytes_total
        out["journal_bytes"] = self.journal_bytes_total
        out["replayed_ticks"] = self.replayed_ticks
        out["restore_j"] = (energy.compute_energy_j(self.restore_flops)
                            + energy.dram_energy_j(self.restore_bytes))
        out["restore_j_per_token"] = (out["restore_j"] / toks
                                      if toks > 0 else 0.0)
        out["durability_write_j"] = energy.dram_energy_j(
            self.snapshot_bytes_total + self.journal_bytes_total)
        return out


def _sample(logits: jnp.ndarray, keys: jnp.ndarray, temp: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-slot sampling: greedy where temp == 0, else categorical at temp,
    each slot drawing from its own PRNG key. Returns (tokens, new keys).
    Sits under the scope ``sample`` in a profile."""
    with jax.named_scope("sample"):
        split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)  # (B, 2, 2)
        sub = split[:, 1]
        new_keys = jnp.where((temp > 0)[:, None], split[:, 0], keys)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tsafe = jnp.where(temp > 0, temp, 1.0)
        sampled = jax.vmap(jax.random.categorical)(
            sub, logits / tsafe[:, None]).astype(jnp.int32)
        return jnp.where(temp > 0, sampled, greedy), new_keys
