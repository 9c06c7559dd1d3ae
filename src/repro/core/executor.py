"""Threaded MPMD runtime: ASAP's disaggregated asynchronous pipeline with REAL
JAX compute (mechanism-level reproduction; the performance level lives in
core/simulator.py, the at-scale SPMD level in launch/).

Topology: D attention DP groups (each a thread; T configurable protocol rows)
+ E MoE device threads, wired by the shared-buffer primitives of
core/async_primitives.py. Every mechanism of the paper is present:

  * async dispatch/combine with bitmap flags + backpressure (§3.2)
  * dual-batch interleaving on attention devices (§3.3.2)
  * out-of-order MoE: devices block in `wait_any` and process whichever DP
    group's batch-layer completes first — the layer id arrives as DATA
    (metadata ①) and indexes the stacked [L, n, ...] expert weights exactly
    like the MoE Super Kernel's scalar-prefetch index (§3.4.2)
  * shared-expert compute on the attention device overlapped with the routed
    experts' remote execution (beyond-paper overlap; disable with
    `shared_on_attention=False`)
  * replica-aware dispatch: expert→device assignment comes from a
    `core.cost_model.Placement` (round_robin / greedy_balanced /
    replicated(k) / explicit), and a replicated hot expert's traffic is
    routed to its least-loaded replica — the same placement tables that
    drive the simulator's `ExpertLoadModel` (ROADMAP item d).
  * LIVE expert re-placement (ISSUE 5, ROADMAP d3): `apply_placement`
    swaps the devices' expert-id vectors + dispatch tables mid-serve —
    freeze the dispatch gate, quiesce the affected MoE devices, swap
    atomically.  Driven between polls by the `PlacementController` via
    `core.engine.ExecutorEngine`.
  * jitted combine (ROADMAP item i): the per-batch-layer weighted
    accumulation of expert outputs is ONE scatter-add jit
    (`combine_path="segsum"`); the np.add.at host loop survives as
    `combine_path="host"`, pinned bit-equal in tests.

Hot path (`moe_path="fused"`, the default — §3.4.2 made real):

  * Attention side: one shape-keyed jitted step computes attention + norms +
    router (+ shared expert) with the LAYER ID AS RUNTIME DATA — the step
    dynamic-indexes the stacked per-layer params inside the trace, so every
    layer of every batch reuses ONE compiled program (zero steady-state
    retraces; `trace_counts` proves it).
  * Dispatch: a single stable argsort over (device, expert) keys builds all
    E payloads per batch-layer — no per-device boolean scans.
  * MoE side: each drained region is packed into dropless per-expert
    capacity buffers ([n_e, C, d]; C bucketed to powers of two so the jit
    cache stays finite) by `kernels.super_gmm.ops.pack_capacity` — a
    vectorized segment-sort/scatter — then ONE jitted `super_moe_ffn` call
    runs all three expert projections against the model's stacked
    [L, n, ...] expert weights, with the layer id and the device's expert
    ids as runtime data: the layer-oblivious super-kernel semantics (global
    weight access + pre-calculated indexing + dynamic resolution), not an
    eager per-expert Python loop.  `moe_path="eager"` keeps the pre-fusion
    per-expert loop as the benchmark baseline
    (benchmarks/fig_executor_hotpath.py).

Weights are jit ARGUMENTS, never closed-over constants: a closure would
embed them in the HLO (gigabytes at published widths).  The expert weights
live on the device once — in `params` — and each MoE device addresses its
experts there through a small id vector, so a live re-placement swaps ids,
not weight copies.

Numerical contract (tested): pipeline output == lm_backbone(..., moe_mode=
"dense") for the same params — asynchrony, placement and fusion must not
change the math.  A job with `lengths` keeps its pad positions out of MoE
dispatch, so the contract holds on each row's first `lengths[i]` positions.

Lifecycle (ISSUE 4 api_redesign): the executor is a LONG-LIVED engine, not a
one-shot batch call.  `ensure_started()` spawns the D group workers + E MoE
workers once; group workers then PULL work from a shared admission queue
(`submit_job`) — an un-pinned job goes to whichever group frees a dual-batch
slot first, which is exactly least-loaded assignment and replaces the
caller-side hand partition.  Completions surface out of order through the
`on_complete` callback (per-job queue/kernel/comm timing in `clock` units —
the `core.engine.ExecutorEngine` wires a replayable `core.trace.TraceClock`
and a `RouterStatsCollector` here and exposes the `ServingEngine` protocol on
top).  `run(jobs_per_group)` survives as a thin compatibility shim: it pins
each job to its hand-chosen group, submits, and blocks until that wave
completes.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.async_primitives import (AbortedError, AttnDeviceBuffer,
                                         CombinePayload, DispatchPayload,
                                         MoEDeviceBuffer)
from repro.core.cost_model import Placement
from repro.core.faults import FaultInjector, FaultPlan, InjectedFault
from repro.kernels.super_gmm.ops import (pack_capacity, pack_capacity_multi,
                                         round_capacity, super_moe_ffn,
                                         unpack_capacity,
                                         unpack_capacity_multi)
from repro.models.attention import attention_forward, attention_prefill
from repro.models.common import ModelConfig, act_fn, apply_norm
from repro.models.moe import gated_ffn, router_topk
from repro.models.lm import embed_tokens, lm_stages


def make_attn_step(cfg: ModelConfig, *, emit_kv: bool = False,
                   shared: bool = False,
                   on_trace: Callable[[], None] = lambda: None):
    """One jitted attention+norm+router(+shared) step for ALL layers:
    `step(sp, lid, h)` takes the stacked per-layer params `sp` (keys attn,
    ln_attn, ln_ffn, router and, with `shared`, shared) as an ARGUMENT and
    the layer id as a traced scalar that indexes them, so the steady state
    performs zero retraces (jax.jit keys on shapes only).  `on_trace` runs
    at trace time only — the executor's retrace probe.

    With `emit_kv` (ISSUE 9) the attention part runs through
    `attention_prefill` and the step ALSO returns the layer's (k, v) cache —
    the raw material of the prefill->decode KV handoff.  Both flags are
    Python-level, so the jit cache still keys on shapes only.  The step's
    name is the profiler's name for its program (`jit_asap_attn_step`)."""

    def asap_attn_step(sp, lid, h):
        on_trace()
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, lid, 0, keepdims=False),
            sp)
        kv = None
        if emit_kv:
            a, cache = attention_prefill(
                lp["attn"], apply_norm(h, lp["ln_attn"], cfg), cfg,
                use_dense=True)
            h = h + a
            kv = (cache.k, cache.v)
        else:
            h = h + attention_forward(lp["attn"],
                                      apply_norm(h, lp["ln_attn"], cfg),
                                      cfg, use_dense=True)
        x = apply_norm(h, lp["ln_ffn"], cfg)
        B, S, d = x.shape
        xf = x.reshape(B * S, d)
        weights, idx, _ = router_topk(lp["router"], xf, cfg)
        out_shared = None
        if shared:
            s = lp["shared"]
            out_shared = gated_ffn(xf, s["w_gate"], s["w_up"], s["w_down"],
                                   act_fn(cfg.act))
        return h, xf, weights, idx, out_shared, kv

    return jax.jit(asap_attn_step)


def make_moe_step(cfg: ModelConfig, *, kernel: str = "pallas",
                  on_trace: Callable[[], None] = lambda: None):
    """Jitted super-kernel FFN `step(experts, ids, lid, xb)`: the stacked
    [L, n, ...] expert weights are an ARGUMENT, `ids` [n_e] names the
    experts whose capacity buffers `xb` [n_e, C, d] holds, and the layer id
    is a runtime [1] scalar — ONE trace serves every layer and every device
    with the same n_e; new traces only occur for new capacity buckets."""

    def asap_moe_step(experts, ids, lid, xb):
        on_trace()
        return super_moe_ffn(lid, experts, xb, cfg, expert_ids=ids,
                             kernel=kernel)

    return jax.jit(asap_moe_step)


@dataclasses.dataclass
class BatchJob:
    tokens: Any  # [B, S] int32
    result: Any = None  # final hidden states [B, S, d]
    bid: int = 0
    # --- engine fields (ISSUE 4) ------------------------------------------
    group: Optional[int] = None  # pinned attention group; None = least-loaded
    lengths: Optional[List[int]] = None  # per-row valid prompt lengths
    meta: Any = None  # opaque engine payload (the batched Requests)
    # timestamps/durations in `DisaggregatedExecutor.clock` units (trace
    # seconds when driven by a TraceClock, wall seconds otherwise)
    t_submitted: Optional[float] = None
    t_started: Optional[float] = None  # first attention dispatch
    t_finished: Optional[float] = None
    kernel_time: float = 0.0  # attention-side compute (this group's stream)
    comm_time: float = 0.0  # blocked in combine (MoE compute + wire + queue)
    # --- fault tolerance (ISSUE 8) ----------------------------------------
    retries: int = 0  # region-timeout replays (capped-backoff, from layer 0)
    failed: Optional[str] = None  # terminal failure reason (result stays None)
    hedged: bool = False  # a hedge clone of this job was issued
    is_hedge: bool = False  # this job IS the hedge clone
    # --- prefill/decode disaggregation (ISSUE 9) --------------------------
    # With `emit_kv=True` the pipeline also exports the batch's per-layer KV
    # caches: (k, v) stacked [L, B, S, kvh, hd] np arrays.  The engine
    # slices per-request handles out of them for decode enrollment.
    kv: Optional[tuple] = None


class DisaggregatedExecutor:
    def __init__(self, params, cfg: ModelConfig, D: int = 2, E: int = 4,
                 T: int = 1, interleave: bool = True,
                 shared_on_attention: bool = True,
                 placement: Optional[Placement] = None,
                 expert_fractions: Optional[Sequence[float]] = None,
                 moe_path: str = "fused", moe_kernel: str = "pallas",
                 combine_path: str = "segsum",
                 idle_backoff: Optional[float] = 0.05,
                 supervise: bool = True,
                 stall_timeout: Optional[float] = None,
                 max_worker_restarts: int = 3,
                 region_timeout: float = 60.0,
                 max_job_retries: int = 2,
                 emit_kv: bool = False,
                 moe_batch_window: float = 0.0,
                 moe_batch_max_tokens: Optional[int] = None):
        assert cfg.family == "moe", "executor drives MoE models"
        assert moe_path in ("fused", "eager"), moe_path
        assert moe_kernel in ("pallas", "ref"), moe_kernel
        assert combine_path in ("segsum", "host"), combine_path
        assert moe_batch_window >= 0.0, moe_batch_window
        assert not (moe_batch_window > 0 and moe_path == "eager"), \
            "cross-region batching merges regions into ONE capacity buffer " \
            "— it requires the fused super-kernel path"
        assert moe_batch_max_tokens is None or moe_batch_max_tokens >= 1
        assert not (emit_kv and moe_path == "eager"), \
            "emit_kv requires the fused attention step (the KV cache is " \
            "exported by the jitted attention_prefill path)"
        (kind, n, opts), = lm_stages(cfg)
        assert kind == "decoder" and opts["moe"]
        self.params, self.cfg = params, cfg
        self.D, self.E, self.T = D, E, T
        self.L = cfg.num_layers
        self.interleave = interleave
        self.shared_on_attention = shared_on_attention
        self.moe_path = moe_path
        self.moe_kernel = moe_kernel
        self.combine_path = combine_path
        self.emit_kv = emit_kv
        self.idle_backoff = idle_backoff  # max CV wait in the MoE workers
        # --- cross-region continuous batching (ISSUE 10) ------------------
        # window > 0 turns each MoE worker into a continuous batcher: a
        # drain takes EVERY pending region (recv_many) and keeps
        # accumulating arrivals for up to `moe_batch_window` WALL seconds
        # (bounded by `moe_batch_max_tokens` merged rows), then launches
        # the super kernel layer-major over the merged capacity buffer.
        # window == 0 preserves the per-region recv_any path bit-exactly.
        self.moe_batch_window = float(moe_batch_window)
        self.moe_batch_max_tokens = moe_batch_max_tokens
        self.stage = params["stages"][0]
        # --- replica-aware expert placement (ROADMAP item d) --------------
        # The SAME Placement.table that drives the simulator's
        # ExpertLoadModel decides which device hosts which expert here, so
        # the real runtime and the simulator agree on the routing layer.
        self.placement = placement if placement is not None else Placement()
        fr = tuple(float(x) for x in expert_fractions) \
            if expert_fractions is not None \
            else Placement.uniform_fractions(cfg.num_experts)
        assert len(fr) == cfg.num_experts
        self.expert_fractions = fr
        self.table = self.placement.table(fr, E)
        self.dev_experts = self.placement.device_experts(fr, E)
        # routing lookups: primary host per expert, replica sets, and the
        # per-device global→local expert index (shared with the live
        # re-placement swap — ONE derivation for both lifecycles)
        self._primary, self._replicated, self._g2l = \
            self._dispatch_lookups(self.table, self.dev_experts)
        self._dev_load = np.zeros(E, np.int64)  # dispatched assignments  guarded_by: _load_lock
        self._load_lock = threading.Lock()
        # buffers
        self.moe_bufs = [MoEDeviceBuffer(D, T) for _ in range(E)]
        self.attn_bufs = [[AttnDeviceBuffer(E) for _ in range(2)]
                          for _ in range(D)]  # per group x dual-batch slot
        # expert weights: ONE [L, n, ...] stack on the device (the params'
        # own); MoE device e serves the experts listed in `_moe_ids[e]`
        # (all layers resident; layer id and expert ids index at runtime).
        # Replicas are listed on every host.
        self._experts = self.stage["ffn"]["experts"]
        self._moe_ids = [self._expert_ids(self.dev_experts[e])
                         for e in range(E)]
        # --- live re-placement state (ISSUE 5) ----------------------------
        # dispatch gate: apply_placement freezes new dispatches (readers of
        # the routing tables) and waits for in-flight ones to drain before
        # swapping tables + expert ids; `_moe_active[e]` marks a device
        # mid-region (set BEFORE dispatch_recv clears the flags, so
        # "no flags set and not active" really means quiescent)
        self._gate_cv = threading.Condition()
        self._gate_frozen = False  # guarded_by: _gate_cv
        self._dispatchers = 0  # guarded_by: _gate_cv
        # guarded_by: protocol
        # (single-writer per element: only MoE worker e flips _moe_active[e];
        # the quiesce loop tolerates a stale read — it just polls again)
        self._moe_active = [False] * E
        self.migrations: List[Dict[str, Any]] = []  # live re-placement log
        self.migrated_bytes = 0.0
        # --- fault tolerance (ISSUE 8) ------------------------------------
        # One lock serializes EVERY placement swap: the engine's rebalance
        # tick and the supervisor's failover both funnel through
        # apply_placement, which would otherwise interleave their freeze/
        # quiesce/swap phases.
        self.supervise = supervise
        self.stall_timeout = stall_timeout  # clock units; None = death-only
        self.max_worker_restarts = max_worker_restarts
        self.region_timeout = region_timeout  # wall s: combine_recv bound
        self.max_job_retries = max_job_retries
        self._swap_lock = threading.Lock()
        self.fault_injector: Optional[FaultInjector] = None
        self.on_failover: Optional[Any] = None  # callable(device), post-swap
        self.failovers = 0  # guarded_by: protocol
        # (single-writer: only the supervisor thread executes failovers)
        # guarded_by: protocol
        # (single-writer per element: worker e stamps its own heartbeat;
        # the supervisor tolerates a stale read — one scan of extra latency)
        self._heartbeat = [0.0] * E
        # guarded_by: protocol
        # (worker-generation fence: bumped ONLY under the buffer's shared cv
        # via MoEDeviceBuffer.fenced, read by recv_any's admission check
        # under the same cv; a worker's unlocked loop-top read may be stale
        # one iteration — the next recv_any re-validates under the cv)
        self._moe_gen = [0] * E
        # guarded_by: protocol
        # (the regions worker e took but has not combined yet — a tuple of
        # (region, rows) entries (the continuous batcher may hold several;
        # per-region mode at most one), appended under the buffer cv by the
        # recv_any/recv_many on_take and with each entry removed by the
        # worker BEFORE that region's combine_send; after the generation
        # fence the supervisor is the cell's only reader/writer — "entry
        # still present" proves its combine never happened, so the failover
        # re-serve is exactly-once)
        self._moe_current: List[Optional[tuple]] = [None] * E
        # guarded_by: protocol
        # (written once by dying worker e, read by the supervisor after it
        # observed the thread dead — the join/is_alive edge orders the two)
        self._moe_fail_exc: List[Optional[BaseException]] = [None] * E
        self._moe_restarts = [0] * E  # guarded_by: protocol
        # (single-writer: only the supervisor restarts workers)
        self._sup_thread: Optional[threading.Thread] = None
        self._retired: List[threading.Thread] = []  # fenced-out old workers
        # jit caches (shape-keyed via jax.jit) + trace-count probes
        self.trace_counts: collections.Counter = collections.Counter()  # guarded_by: _trace_lock
        self._trace_lock = threading.Lock()  # counters bump from N threads
        self._hung: List[threading.Thread] = []  # left over by a timed-out run
        self._attn_stage = {"attn": self.stage["attn"],
                            "ln_attn": self.stage["ln_attn"],
                            "ln_ffn": self.stage["ln_ffn"],
                            "router": self.stage["ffn"]["router"]}
        if "shared" in self.stage["ffn"] and shared_on_attention:
            self._attn_stage["shared"] = self.stage["ffn"]["shared"]
        self._attn_jit = make_attn_step(
            cfg, emit_kv=emit_kv, shared="shared" in self._attn_stage,
            on_trace=functools.partial(self._count_trace, "attn"))
        self._combine_step = self._make_combine_step()
        self._moe_jit = make_moe_step(
            cfg, kernel=moe_kernel,
            on_trace=functools.partial(self._count_trace, "moe"))
        self.stop = threading.Event()
        self.errors: List[BaseException] = []
        # event log for protocol assertions in tests
        self.log: List[tuple] = []  # guarded_by: _log_lock
        self._log_lock = threading.Lock()
        # --- long-lived engine state (ISSUE 4) ----------------------------
        # `clock` is assignable: the ExecutorEngine points it at a replayable
        # TraceClock.now so every timestamp below is in trace seconds.
        self.clock = time.monotonic
        # duck-typed measured-router-stats sink: anything with
        # .record(layer, expert_ids) — see core.engine.RouterStatsCollector.
        self.router_stats: Optional[Any] = None
        self.on_complete: Optional[Any] = None  # callable(BatchJob)
        self._jobq: List[BatchJob] = []  # shared admission queue  guarded_by: _jobq_cv
        self._jobq_cv = threading.Condition()
        self._done_cv = threading.Condition()
        self._started = False
        self._g_threads: List[threading.Thread] = []
        self._moe_threads: List[threading.Thread] = []
        self._t_serving_start: Optional[float] = None
        # measured busy time per device (clock units) for EngineStats
        # guarded_by: protocol
        # (single-writer: only worker e / group g accumulates its own cell;
        # EngineStats reads after join() or tolerates a slightly stale sum)
        self.moe_busy = np.zeros(E)
        self.group_busy = np.zeros(D)  # guarded_by: protocol
        # --- super-kernel launch telemetry (ISSUE 10) ---------------------
        # All per-device cells below follow the moe_busy ownership rule:
        # only worker e (or the supervisor, after fencing e out) writes
        # device e's cell; readers (EngineStats) tolerate a stale sum.
        self.moe_launches = np.zeros(E)  # guarded_by: protocol
        # (single-writer per element: worker e / post-fence supervisor)
        self.moe_launch_regions = np.zeros(E)  # guarded_by: protocol
        # (single-writer per element — regions merged across all launches)
        self.moe_launch_rows = np.zeros(E)  # guarded_by: protocol
        # (single-writer per element — real token rows launched)
        self.moe_launch_slots = np.zeros(E)  # guarded_by: protocol
        # (single-writer per element — n_e*C capacity slots launched; rows/
        # slots is the occupancy the batcher exists to lift)
        self.bucket_hits = np.zeros(E)  # guarded_by: protocol
        # (single-writer per element — launches whose capacity bucket C was
        # already traced on this device: the zero-retrace steady state)
        self.bucket_misses = np.zeros(E)  # guarded_by: protocol
        # (single-writer per element — first sighting of a bucket: a jit
        # trace; a growing count in steady state is a retrace regression)
        self._seen_buckets: List[set] = [set() for _ in range(E)]
        # guarded_by: protocol
        # (single-writer per element: same owner as bucket_hits/misses)
        # --- host<->device copies of the served path ----------------------
        # `.nbytes` of every array the served path moves after set-up, in
        # the cell of the thread that moves it: cell g for attention group
        # g (the engine's head copies run on that thread too), cell D + e
        # for MoE device e.  Same ownership rule as moe_busy.
        self.h2d_bytes = np.zeros(D + E)  # guarded_by: protocol
        # (single-writer per element: group worker g / MoE worker e or the
        # post-fence supervisor; readers tolerate a stale sum)
        self.d2h_bytes = np.zeros(D + E)  # guarded_by: protocol
        # (single-writer per element, as h2d_bytes)
        self.moe_pad_rows = np.zeros(D)  # guarded_by: protocol
        # (single-writer per element: group worker g counts the (token, k)
        # rows at or past their prompt's length, which it kept out of
        # dispatch)


    def _logev(self, *ev):
        with self._log_lock:
            self.log.append(ev)

    def _count_trace(self, name: str):
        with self._trace_lock:  # jit tracing may run on several threads
            self.trace_counts[name] += 1

    def record_copies(self, cell: int, h2d: int = 0, d2h: int = 0):
        """Count bytes moved host->device and device->host in `cell` (see
        h2d_bytes).  The caller is the cell's single writer."""
        self.h2d_bytes[cell] += h2d  # race-ok: single-writer (the cell's own thread)
        self.d2h_bytes[cell] += d2h  # race-ok: single-writer (the cell's own thread)

    # ------------------------------------------------- placement derivation
    def _dispatch_lookups(self, table, dev_experts):
        """(primary, replicated, g2l) routing lookups for a placement table
        — used at construction AND by the live re-placement swap, so both
        lifecycles derive dispatch state identically."""
        primary = np.array([h[0] for h in table], np.int64)
        replicated = [e for e, h in enumerate(table) if len(h) > 1]
        g2l = np.full((self.E, self.cfg.num_experts), -1, np.int64)
        for e, held in enumerate(dev_experts):
            g2l[e, list(held)] = np.arange(len(held))
        return primary, replicated, g2l

    @staticmethod
    def _expert_ids(held) -> Optional[jax.Array]:
        """Device-side [n_e] int32 ids of the experts one MoE device serves
        (None for a device that holds none)."""
        return jnp.asarray(held, jnp.int32) if len(held) else None

    @property
    def expert_copy_bytes(self) -> float:
        """Bytes of ONE expert's weights for ONE layer — the per-copy unit
        the placement controller prices MigrationPlans in."""
        return float(sum(v.dtype.itemsize * int(np.prod(v.shape[2:]))
                         for v in self._experts.values()))

    # ------------------------------------------------------------ attention
    def _layer_params(self, l: int):
        return jax.tree.map(lambda a: a[l], self.stage)

    def _attn_step(self, lid, h):
        """The fused attention step (see `make_attn_step`) on this
        executor's stacked attention params."""
        return self._attn_jit(self._attn_stage, lid, h)

    def _attn_part(self, lp, h):
        """Eager (pre-fusion) attention step — the `moe_path="eager"`
        baseline: per-layer host slicing + op-by-op dispatch."""
        cfg = self.cfg
        h = h + attention_forward(lp["attn"], apply_norm(h, lp["ln_attn"], cfg),
                                  cfg, use_dense=True)
        x = apply_norm(h, lp["ln_ffn"], cfg)
        B, S, d = x.shape
        xf = x.reshape(B * S, d)
        weights, idx, _ = router_topk(lp["ffn"]["router"], xf, cfg)
        shared = None
        if "shared" in lp["ffn"] and self.shared_on_attention:
            sp = lp["ffn"]["shared"]
            shared = gated_ffn(xf, sp["w_gate"], sp["w_up"], sp["w_down"],
                               act_fn(cfg.act))
        return h, xf, np.asarray(weights), np.asarray(idx), shared

    # ------------------------------------------------------------- dispatch
    def _gate_enter(self):
        """Block while a live re-placement holds the dispatch gate.  Entered
        for the duration of one batch-layer's E sends, so a placement swap
        never observes (or splits) a half-dispatched layer.  A stop request
        falls through — shutdown must not deadlock on a frozen gate."""
        with self._gate_cv:
            while self._gate_frozen and not self.stop.is_set():
                self._gate_cv.wait(0.1)
            self._dispatchers += 1

    def _gate_exit(self):
        with self._gate_cv:
            self._dispatchers -= 1
            self._gate_cv.notify_all()

    def _route(self, flat_e: np.ndarray) -> np.ndarray:
        """Device id per (token, k) assignment under the placement table.

        Single-host experts go to their host; a replicated expert's rows are
        spread round-robin over its hosts ordered by the CURRENT dispatched
        load, so hot-expert traffic lands on the least-loaded replica first
        (MegaScale-style load-splitting, executed at dispatch time)."""
        dev = self._primary[flat_e]
        with self._load_lock:
            for e in self._replicated:
                rows = np.nonzero(flat_e == e)[0]
                if not rows.size:
                    continue
                hosts = np.asarray(self.table[e], np.int64)
                by_load = hosts[np.argsort(self._dev_load[hosts],
                                           kind="stable")]
                dev[rows] = by_load[np.arange(rows.size) % hosts.size]
            self._dev_load += np.bincount(dev, minlength=self.E)
        return dev

    def _flat_routing(self, idx: np.ndarray, layer: int = 0,
                      valid: Optional[np.ndarray] = None):
        """(expert, token, k, device) of every (token, k) assignment that
        enters dispatch.  Where `valid` masks the batch's positions, a pad
        position's assignments never do: no pad row reaches a MoE device,
        its capacity buffer or the device load `_route` balances on.
        Padding follows the prompt and attention is causal, so a pad
        position's MoE output could only reach other pad positions."""
        Tn, K = idx.shape
        flat_e = idx.reshape(-1)
        flat_t = np.repeat(np.arange(Tn), K)
        flat_k = np.tile(np.arange(K), Tn)
        if valid is not None:
            keep = np.repeat(valid, K)
            flat_e, flat_t, flat_k = flat_e[keep], flat_t[keep], flat_k[keep]
        if self.router_stats is not None:
            # MEASURED per-expert routing stats (ROADMAP d2): every real
            # router assignment is counted before placement routing, so the
            # collector sees expert popularity, not device load.
            self.router_stats.record(layer, flat_e)
        return flat_e, flat_t, flat_k, self._route(flat_e)

    def _send_device(self, g: int, slot: int, layer: int, e: int, xf_np,
                     t_rows, k_rows, local_ids):
        """Write one device's T payload rows (empty payloads included so the
        T·D bitmap regions always complete)."""
        inj = self.fault_injector
        if inj is not None and inj.should_drop_dispatch(e):
            # injected network fault: drop the WHOLE region (all T rows) —
            # never a partial region.  The region stays incomplete, the
            # group's combine_recv times out, and the batch replays through
            # the retry path (exactly-once: the injector fires per event).
            self._logev("drop-dispatch", g, slot, layer, e)
            return
        token_ids = np.stack([t_rows, k_rows], 1)  # (token, k)
        counts = np.bincount(local_ids,
                             minlength=max(len(self.dev_experts[e]), 1))
        payload_tokens = xf_np[t_rows]
        for j in range(self.T):
            sl = slice(j, None, self.T)  # row-split across TP members
            p = DispatchPayload(layer=layer, slot=slot,
                                counts=counts if j == 0 else None,
                                tokens=payload_tokens[sl],
                                token_ids=token_ids[sl],
                                expert_ids=local_ids[sl])
            self.moe_bufs[e].dispatch_send(g, j, p, stop=self.stop)
        self._logev("dispatch", g, slot, layer, e, int(len(t_rows)))

    def _count_dispatch(self, g: int, xf_np: np.ndarray,
                        valid: Optional[np.ndarray]):
        """Group worker g's counters for one batch-layer's dispatch: the
        D2H of the payload source and the (token, k) rows of pad
        positions, which dispatch skips."""
        self.record_copies(g, d2h=xf_np.nbytes)
        if valid is not None:
            pad = (valid.size - np.count_nonzero(valid)) * self.cfg.top_k
            self.moe_pad_rows[g] += pad  # race-ok: single-writer (group worker g)

    def _dispatch(self, g: int, slot: int, layer: int, xf, idx,
                  valid: Optional[np.ndarray] = None):
        """async-dispatch-send: ONE stable argsort over (device, expert)
        keys builds all E payloads — no per-device boolean scans."""
        self._gate_enter()
        try:
            xf_np = np.asarray(xf)
            self._count_dispatch(g, xf_np, valid)
            flat_e, flat_t, flat_k, dev = self._flat_routing(np.asarray(idx),
                                                             layer, valid)
            order = np.argsort(dev * max(self.cfg.num_experts, 1) + flat_e,
                               kind="stable")
            dev_s, e_s = dev[order], flat_e[order]
            t_s, k_s = flat_t[order], flat_k[order]
            bounds = np.concatenate(
                ([0], np.cumsum(np.bincount(dev_s, minlength=self.E))))
            for e in range(self.E):
                sl = slice(bounds[e], bounds[e + 1])
                self._send_device(g, slot, layer, e, xf_np, t_s[sl], k_s[sl],
                                  self._g2l[e, e_s[sl]])
        finally:
            self._gate_exit()

    def _dispatch_eager(self, g: int, slot: int, layer: int, xf, idx,
                        valid: Optional[np.ndarray] = None):
        """Pre-fusion dispatch: E boolean scans over the flat assignment
        arrays (kept as the benchmark baseline; still placement-routed so
        the numerical contract holds on every policy)."""
        self._gate_enter()
        try:
            xf_np = np.asarray(xf)
            self._count_dispatch(g, xf_np, valid)
            flat_e, flat_t, flat_k, dev = self._flat_routing(np.asarray(idx),
                                                             layer, valid)
            for e in range(self.E):
                m = dev == e
                self._send_device(g, slot, layer, e, xf_np, flat_t[m],
                                  flat_k[m], self._g2l[e, flat_e[m]])
        finally:
            self._gate_exit()

    def _make_combine_step(self):
        """Jitted weighted scatter-add for the combine (ROADMAP item (i)):
        ONE segment-sum over the concatenated expert outputs replaces the
        per-payload host `np.add.at` loop — the next profiler hotspot once
        the GEMMs were fused.  The row count is Tn·top_k for every complete
        batch-layer, so the jit cache stays keyed on the batch buckets
        already in play (no new retrace churn); scatter rows keep payload
        order, which keeps the accumulation bit-identical to the host path
        (pinned in tests/test_executor.py).  Both paths start the
        accumulator from the shared-expert output (or zeros), so neither
        leaves the compiler a trailing add to reorder."""

        def asap_combine_step(acc0, outs, t, w):
            self._count_trace("combine")
            return acc0.at[t].add(outs * w[:, None])

        return jax.jit(asap_combine_step)

    def _combine(self, g: int, slot: int, h, xf, weights, shared,
                 valid: Optional[np.ndarray] = None):
        """async-combine-recv + weighted accumulation (token-order restore).

        combine_path="segsum" (default) runs the jitted scatter-add;
        "host" keeps the pre-ISSUE-5 per-payload np.add.at loop as the
        bit-equality oracle and benchmark baseline.

        The wait is bounded by `region_timeout` (wall seconds): a region
        lost to a fault (dropped dispatch/combine, a failover window longer
        than the bound) surfaces as TimeoutError and the group worker
        replays the batch through the retry path instead of wedging for the
        240s protocol default (ISSUE 8)."""
        with spans.span("asap.group.combine_wait"):
            payloads = self.attn_bufs[g][slot].combine_recv(
                timeout=self.region_timeout, stop=self.stop)
        with spans.span("asap.group.combine"):
            return self._accumulate(g, slot, payloads, h, xf, weights,
                                    shared, valid)

    def _accumulate(self, g: int, slot: int, payloads, h, xf, weights,
                    shared, valid: Optional[np.ndarray] = None):
        """The combine's weighted accumulation of one batch-layer's expert
        outputs, added to the residual `h`.  A pad position (`valid` False)
        was never dispatched, so it gets the shared expert's output alone."""
        Tn, d = xf.shape
        layer = None
        h2d = d2h = 0
        if self.combine_path == "host":
            if shared is None:
                acc = np.zeros((Tn, d), np.float32)
            else:
                acc = np.array(shared, np.float32)
                d2h += shared.nbytes
            for p in payloads:
                if p.outputs is None or len(p.token_ids) == 0:
                    continue
                layer = p.layer
                t = p.token_ids[:, 0]
                k = p.token_ids[:, 1]
                w = weights[t, k][:, None]
                np.add.at(acc, t, np.asarray(p.outputs, np.float32) * w)
        else:
            outs, ts, ws = [], [], []
            for p in payloads:
                if p.outputs is None or len(p.token_ids) == 0:
                    continue
                layer = p.layer
                t = p.token_ids[:, 0]
                outs.append(np.asarray(p.outputs, np.float32))
                ts.append(t)
                ws.append(weights[t, p.token_ids[:, 1]])
            acc0 = jnp.zeros((Tn, d), jnp.float32) if shared is None \
                else shared.astype(jnp.float32)
            if outs:
                pad = weights.size - sum(len(t) for t in ts)
                if pad:
                    # the pad positions' rows were never dispatched: fill
                    # the sum up to its prewarmed Tn·top_k rows with zero
                    # rows of weight 0 aimed at a pad position
                    outs.append(np.zeros((pad, d), np.float32))
                    ts.append(np.full(pad, np.flatnonzero(~valid)[0]))
                    ws.append(np.zeros(pad, weights.dtype))
                args = (jnp.asarray(np.concatenate(outs, 0)),
                        jnp.asarray(np.concatenate(ts, 0)),
                        jnp.asarray(np.concatenate(ws, 0).astype(np.float32)))
                h2d += sum(a.nbytes for a in args)
                acc = np.asarray(self._combine_step(acc0, *args))
            else:
                acc = np.asarray(acc0)
            d2h += acc.nbytes
        B, S, _ = h.shape
        y = jnp.asarray(acc.astype(np.float32))
        self.record_copies(g, h2d=h2d + y.nbytes, d2h=d2h)
        y = y.astype(h.dtype)
        self._logev("combine", g, slot, layer)
        return h + y.reshape(B, S, d)

    # ----------------------------------------------------------- moe worker
    def _moe_launch(self, e: int, layer: int, xb: np.ndarray) -> jax.Array:
        """One super-kernel launch for device e: its capacity buffers
        against the shared expert stack, indexed by its expert ids."""
        return self._moe_jit(self._experts, self._moe_ids[e],
                             jnp.asarray([layer], jnp.int32), jnp.asarray(xb))

    def prewarm_buckets(self, max_rows: int):
        """Trace the fused super-kernel for EVERY capacity bucket up to
        `round_capacity(max_rows)` on every device (ISSUE 10).  Call before
        serving (single-threaded: the caller owns all cells until workers
        start): the continuous batcher's merged drains have data-dependent
        bucket sizes, so without pre-warming the first k-way merge of a new
        size pays a jit compile mid-serve.  After this, every launch whose
        merged rows stay under `max_rows` lands in an already-traced bucket —
        zero steady-state retraces by construction, visible as
        bucket_hits == launches in EngineStats."""
        assert self.moe_path == "fused", "prewarm traces the fused step"
        top = round_capacity(max(int(max_rows), 1))
        for e in range(self.E):
            if self._moe_ids[e] is None:
                continue
            n_e = len(self.dev_experts[e])
            C = round_capacity(1)
            while C <= top:
                # the serving dtype: payload rows are the attention step's
                # normed hidden states, in the model dtype
                xb = np.zeros((n_e, C, self.cfg.d_model), self.cfg.dtype)
                self._moe_launch(e, 0, xb).block_until_ready()
                self._seen_buckets[e].add(C)
                C *= 2

    def prewarm_batches(self, shapes: Sequence[tuple]):
        """Compile the attention step and the jitted combine for every
        (B, S) batch shape in `shapes` before serving, single-threaded — a
        cold compile on a group thread mid-serve races `region_timeout`."""
        assert self.moe_path == "fused", "prewarm traces the fused step"
        lid = jnp.asarray(0, jnp.int32)
        for B, S in shapes:
            h = embed_tokens(self.params, jnp.zeros((B, S), jnp.int32), None,
                             self.cfg)
            _, xf, _, _, _, _ = self._attn_step(lid, h)
            if self.combine_path == "segsum":
                K = self.cfg.top_k
                rows = B * S * K  # every (token, k) assignment combines
                self._combine_step(
                    jnp.zeros(xf.shape, jnp.float32),
                    jnp.asarray(np.zeros((rows, xf.shape[1]), np.float32)),
                    jnp.asarray(np.zeros((rows,), np.int64)),
                    jnp.asarray(np.zeros((rows,), np.float32))
                ).block_until_ready()

    def _record_launch(self, e: int, C: int, n_regions: int, n_rows: int):
        """Super-kernel launch telemetry (ISSUE 10).  Same ownership rule as
        moe_busy: the caller is worker e or the post-fence supervisor — the
        cell's single writer at that moment."""
        n_e = len(self.dev_experts[e])
        self.moe_launches[e] += 1  # race-ok: single-writer (see _record_launch contract)
        self.moe_launch_regions[e] += n_regions  # race-ok: single-writer
        self.moe_launch_rows[e] += n_rows  # race-ok: single-writer
        self.moe_launch_slots[e] += n_e * C  # race-ok: single-writer
        seen = self._seen_buckets[e]
        if C in seen:
            self.bucket_hits[e] += 1  # race-ok: single-writer
        else:
            seen.add(C)
            self.bucket_misses[e] += 1  # race-ok: single-writer

    @staticmethod
    def _join_rows(rows):
        """(tokens, token_ids, expert_ids) of one taken region: its T
        payload rows joined."""
        with spans.span("asap.moe.pack"):
            return (np.concatenate([r.tokens for r in rows], 0),
                    np.concatenate([r.token_ids for r in rows], 0),
                    np.concatenate([r.expert_ids for r in rows], 0))

    def _expert_ffn_fused(self, e: int, layer: int, tokens: np.ndarray,
                          eids: np.ndarray) -> np.ndarray:
        """Capacity-buffer pack -> one super-kernel call -> unpack."""
        n_e = len(self.dev_experts[e])
        with spans.span("asap.moe.pack"):
            xb, order, slots, C = pack_capacity(tokens, eids, n_e)
        self._record_launch(e, C, 1, len(tokens))
        yb = self._launch_and_fetch(e, layer, xb)
        with spans.span("asap.moe.unpack"):
            return unpack_capacity(yb, order, slots, len(tokens))

    def _expert_ffn_fused_multi(self, e: int, layer: int, token_list,
                                eid_list) -> List[np.ndarray]:
        """ONE super-kernel launch over several regions' rows merged into a
        shared capacity buffer (the continuous batcher's serve step).
        Returns one [n_r, d] output block per region, in input order — row
        provenance comes back through `bounds`, so each region's outputs
        scatter to its OWN combine path."""
        n_e = len(self.dev_experts[e])
        with spans.span("asap.moe.pack"):
            xb, order, slots, C, bounds = pack_capacity_multi(
                token_list, eid_list, n_e)
        self._record_launch(e, C, len(token_list), int(bounds[-1]))
        yb = self._launch_and_fetch(e, layer, xb)
        with spans.span("asap.moe.unpack"):
            return unpack_capacity_multi(yb, order, slots, bounds)

    def _launch_and_fetch(self, e: int, layer: int,
                          xb: np.ndarray) -> np.ndarray:
        """A served super-kernel launch and the fetch of its result, with
        the copies counted in device e's cell: xb and the [1] int32 layer
        id in, the result out."""
        with spans.span("asap.moe.launch"):
            yb = self._moe_launch(e, layer, xb)
        with spans.span("asap.moe.fetch"):
            y = np.asarray(yb)
        self.record_copies(self.D + e, h2d=xb.nbytes + 4, d2h=y.nbytes)
        return y

    def _expert_ffn_eager(self, e: int, layer: int, tokens: np.ndarray,
                          eids: np.ndarray) -> np.ndarray:
        """Pre-fusion per-expert loop: three un-jitted GEMMs and a
        host<->device round trip per LOCAL expert (benchmark baseline)."""
        held = self.dev_experts[e]
        act = act_fn(self.cfg.act)
        w = self._experts
        out = np.zeros((len(tokens), tokens.shape[1]), np.float32)
        xj = jnp.asarray(tokens)
        for le in np.unique(eids):
            m = eids == le
            xm = xj[np.where(m)[0]]
            g = held[int(le)]
            y = (act(xm @ w["w_gate"][layer, g])
                 * (xm @ w["w_up"][layer, g])) @ w["w_down"][layer, g]
            out[m] = np.asarray(y, np.float32)
        return out

    def _injected_sleep(self, e: int, gen: int, ev):
        """Interpret a stall_moe / delay_wake fault event: dead to the world
        for `duration` clock seconds.  A stall does NOT heartbeat (that is
        what the supervisor's stall detector keys on); a delayed wake DOES
        (benign latency — no failover)."""
        self._logev("fault", ev.kind, e, ev.duration)
        t_end = self.clock() + ev.duration
        while self.clock() < t_end and not self.stop.is_set():
            # race-ok: fence read — a failover mid-stall retired this worker;
            # exactness doesn't matter, the next recv_any re-validates
            if self._moe_gen[e] != gen:
                return
            if ev.kind == "delay_wake":
                self._heartbeat[e] = self.clock()  # race-ok: single-writer (worker e stamps its own cell)
            time.sleep(0.001)

    def _drain_window(self, e: int, gen: int, buf, on_take):
        """Continuous-batching drain (ISSUE 10): block until the first
        complete region(s) arrive — ONE atomic multi-take — then keep
        accumulating arrivals until the window closes, every one of the D
        regions is on board, or the merged row count reaches
        `moe_batch_max_tokens`.  The window is WALL seconds (like
        idle_backoff): it bounds added queueing latency, not clock-scaled
        simulated time.

        Accumulation is GAP-based inside the window: each extra wait is at
        most a quarter-window, and the first empty gap closes the batch.
        Waiting out the whole window for stragglers is self-defeating — the
        device's pending combines are what release the lagging groups' next
        regions in the first place, so a long idle wait here can stall the
        very arrivals it hopes for (the MegaScale-style ping-pong coupling).

        Returns the ordered (region, rows) list, or None on timeout (nothing
        pending), stop, or fence — on a fence, every taken entry is still
        published in `_moe_current[e]`, so the supervisor's orphan re-serve
        covers the partial drain exactly once."""
        got = buf.recv_many(
            timeout=self.idle_backoff, stop=self.stop,
            admit=lambda: self._moe_gen[e] == gen,  # race-ok: evaluated under the buffer cv by recv_many — atomic w.r.t. the fence bump
            on_take=on_take)
        if got is None:
            return None
        entries = list(got)

        def nrows(es):
            return sum(sum(len(r.tokens) for r in rows) for _, rows in es)

        cap = self.moe_batch_max_tokens
        total = nrows(entries)
        gap = self.moe_batch_window / 4.0
        deadline = time.monotonic() + self.moe_batch_window
        while len(entries) < self.D and (cap is None or total < cap):
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            more = buf.recv_many(
                max_regions=self.D - len(entries),
                timeout=min(remaining, gap), stop=self.stop,
                admit=lambda: self._moe_gen[e] == gen,  # race-ok: evaluated under the buffer cv by recv_many — atomic w.r.t. the fence bump
                on_take=on_take)
            if more is None:
                if self.stop.is_set() or self._moe_gen[e] != gen:  # race-ok: fence read — ownership of the taken entries already transferred to the supervisor with the fence
                    return None
                break  # an empty gap: no region is imminent — launch now
            entries.extend(more)
            total += nrows(more)
        return entries

    def _chunk_by_row_cap(self, entries):
        """Split a drain into sub-batches of <= `moe_batch_max_tokens` merged
        rows each (>= 1 region per chunk, so an oversized single region still
        serves).  The cap bounds the size of ONE merged launch; the first
        atomic multi-take can exceed it when several regions were already
        pending, so the bound is enforced here rather than by refusing the
        take (taken regions are already published and must be served)."""
        cap = self.moe_batch_max_tokens
        if cap is None:
            return [entries]
        chunks, chunk, rows = [], [], 0
        for ent in entries:
            n = sum(len(r.tokens) for r in ent[1])
            if chunk and rows + n > cap:
                chunks.append(chunk)
                chunk, rows = [], 0
            chunk.append(ent)
            rows += n
        if chunk:
            chunks.append(chunk)
        return chunks

    def _serve_batch(self, e: int, gen: int, entries) -> None:
        """Serve one merged drain: group regions by layer id and launch the
        super kernel ONCE per distinct layer over the merged capacity buffer
        (layer-major — at most L launches per drain, vs one per region
        before), then route every region's output block through the
        per-region exactly-once combine protocol: clear ITS `_moe_current`
        entry BEFORE its combine_send and re-check the fence per region, so
        a mid-batch failover re-serves exactly the regions whose combine
        never happened."""
        prep = []  # (region, layer, slot, tokens, token_ids, eids)
        for i, rows in entries:
            prep.append((i, rows[0].layer, rows[0].slot,
                         *self._join_rows(rows)))
        outs: Dict[int, Optional[np.ndarray]] = {}
        by_layer: Dict[int, List[int]] = {}
        for idx, p in enumerate(prep):
            if len(p[3]):
                by_layer.setdefault(p[1], []).append(idx)
            else:
                outs[idx] = None  # empty region: combine an empty marker
        for layer in sorted(by_layer):
            idxs = by_layer[layer]
            t0 = self.clock()
            blocks = self._expert_ffn_fused_multi(
                e, layer, [prep[j][3] for j in idxs],
                [prep[j][5] for j in idxs])
            self.moe_busy[e] += self.clock() - t0  # race-ok: single-writer (worker e accumulates its own cell)
            for j, blk in zip(idxs, blocks):
                outs[j] = blk
        for idx, (i, layer, slot, tokens, token_ids, eids) in enumerate(prep):
            self._logev("moe", e, i, slot, layer, len(tokens))
            # clear THIS region's entry BEFORE its combine attempt — same
            # proof obligation as the per-region path: "entry still
            # published" ⇒ the combine never happened ⇒ the failover
            # re-serve is exactly-once
            cur = self._moe_current[e]  # race-ok: single-writer until fenced (worker e)
            rest = tuple(c for c in (cur or ()) if c[0] != i)
            self._moe_current[e] = rest or None  # race-ok: single-writer until fenced; cleared before combine_send by protocol
            inj = self.fault_injector
            if inj is not None and inj.should_drop_combine(e):
                self._logev("drop-combine", e, i, slot, layer)
                continue
            # race-ok: fence re-check — fenced out mid-batch means the
            # failover already re-served the still-published regions;
            # sending a stale combine here could corrupt a LATER
            # batch-layer's segment
            if self._moe_gen[e] != gen:
                continue
            with spans.span("asap.moe.combine_send"):
                self.attn_bufs[i][slot].combine_send(
                    e, CombinePayload(layer=layer, token_ids=token_ids,
                                      expert_ids=eids, outputs=outs[idx]),
                    stop=self.stop)
        self._moe_active[e] = False  # race-ok: single-writer (worker e); the batch's combines happened-before

    def _moe_worker(self, e: int, gen: int = 0):
        buf = self.moe_bufs[e]
        ffn = self._expert_ffn_fused if self.moe_path == "fused" \
            else self._expert_ffn_eager
        batched = self.moe_batch_window > 0

        def on_take(i, rows):
            # runs UNDER the buffer cv, after the rows migrated and before
            # the flags clear (recv_any/recv_many): in-flight state is
            # published with no gap the quiesce poll or the supervisor could
            # observe.  APPENDS an entry: the continuous batcher holds
            # several taken-not-yet-combined regions at once (per-region
            # mode never sees more than one).
            # race-ok: single-writer (worker e); set before flags clear so the quiesce poll never sees a gap
            self._moe_active[e] = True
            cur = self._moe_current[e]  # race-ok: single-writer until fenced (worker e)
            self._moe_current[e] = (cur or ()) + ((i, rows),)  # race-ok: published under the buffer cv; the supervisor reads it only after fencing this worker out

        try:
            while True:
                # race-ok: fence read — cheap exit for a retired worker; the
                # authoritative check is recv_any's admit under the cv
                if self._moe_gen[e] != gen:
                    return
                self._heartbeat[e] = self.clock()  # race-ok: single-writer (worker e stamps its own cell)
                inj = self.fault_injector
                if inj is not None:
                    ev = inj.poll_worker(e)
                    if ev is not None:
                        if ev.kind == "crash_moe":
                            raise InjectedFault(
                                f"injected crash: moe device {e} "
                                f"(scheduled t={ev.t})")
                        self._injected_sleep(e, gen, ev)
                        continue
                if batched:
                    entries = self._drain_window(e, gen, buf, on_take)
                    if entries is None:
                        if self.stop.is_set():
                            return
                        continue  # timeout (nothing pending) or fence —
                        # the loop top re-validates the fence
                    for chunk in self._chunk_by_row_cap(entries):
                        self._serve_batch(e, gen, chunk)
                    continue
                # block on "any region complete" + take it in ONE atomic
                # step (the split wait_any/dispatch_recv would race the
                # supervisor's failover evacuation — ISSUE 8)
                got = buf.recv_any(
                    timeout=self.idle_backoff, stop=self.stop,
                    admit=lambda: self._moe_gen[e] == gen,  # race-ok: evaluated under the buffer cv by recv_any — atomic w.r.t. the fence bump
                    on_take=on_take)
                if got is None:
                    if self.stop.is_set():
                        return
                    continue
                i, rows = got
                layer = rows[0].layer
                slot = rows[0].slot
                tokens, token_ids, eids = self._join_rows(rows)
                if len(tokens):
                    # layer-oblivious: `layer` is runtime data indexing the
                    # resident all-layer weight stack (super-kernel semantics)
                    t0 = self.clock()
                    out = ffn(e, layer, tokens, eids)
                    self.moe_busy[e] += self.clock() - t0  # race-ok: single-writer (worker e accumulates its own cell)
                else:
                    out = None
                self._logev("moe", e, i, slot, layer, len(tokens))
                # clear BEFORE the combine attempt: "_moe_current still set"
                # is the supervisor's proof the combine never happened, which
                # makes its re-serve of a crashed worker's region exactly-once
                self._moe_current[e] = None  # race-ok: single-writer until fenced; cleared before combine_send by protocol
                inj = self.fault_injector
                if inj is not None and inj.should_drop_combine(e):
                    # injected drop: the group's combine times out and the
                    # batch retries — the region is consumed exactly once
                    self._logev("drop-combine", e, i, slot, layer)
                    self._moe_active[e] = False  # race-ok: single-writer (worker e)
                    continue
                # race-ok: fence re-check — fenced out mid-compute means the
                # failover already re-served this region; sending a stale
                # combine here could corrupt a LATER batch-layer's segment
                if self._moe_gen[e] != gen:
                    self._moe_active[e] = False  # race-ok: single-writer semantics transferred back; worker exits next loop
                    continue
                with spans.span("asap.moe.combine_send"):
                    self.attn_bufs[i][slot].combine_send(
                        e, CombinePayload(layer=layer, token_ids=token_ids,
                                          expert_ids=eids, outputs=out),
                        stop=self.stop)
                self._moe_active[e] = False  # race-ok: single-writer (worker e); combine_send above happened-before
        except AbortedError:
            return  # stop observed inside a buffer wait (shutdown/panic)
        except BaseException as ex:  # surface thread failures to the caller
            self._worker_failed(e, ex)

    # --------------------------------------------------------- group worker
    def _panic(self, ex: BaseException):
        """Surface a worker-thread failure to every waiter — the LAST
        resort: under supervision a dying MoE worker goes through
        `_worker_failed` -> failover instead (ISSUE 8)."""
        self.errors.append(ex)
        self.stop.set()
        with self._jobq_cv:
            self._jobq_cv.notify_all()
        with self._done_cv:
            self._done_cv.notify_all()
        for buf in self.moe_bufs:
            buf.wake()
        # release group workers parked in combine_recv and MoE workers
        # parked in combine_send backpressure: their stop-aware waits raise
        # AbortedError on the next wakeup instead of masking the original
        # failure with a 240s protocol timeout (ISSUE 8 satellite)
        for bufs in self.attn_bufs:
            for buf in bufs:
                buf.wake()

    def _worker_failed(self, e: int, exc: BaseException):
        """A MoE worker thread is dying.  Supervised: record the cause and
        let the thread exit — the supervisor detects the death and fails the
        device over.  Unsupervised: seed behavior (panic)."""
        if not self.supervise:
            self._panic(exc)
            return
        self._moe_fail_exc[e] = exc  # race-ok: written once by dying worker e; the supervisor reads it only after observing the thread dead
        self._logev("worker-died", e, type(exc).__name__)

    def _take_job(self, g: int, timeout: float = 0.0) -> Optional[BatchJob]:
        """Pop the oldest admitted job this group may serve (un-pinned or
        pinned to g).  `timeout` > 0 blocks until one arrives — the pull
        model IS the least-loaded assignment: whichever group frees a slot
        first takes the head of the shared queue."""
        deadline = time.monotonic() + timeout if timeout > 0 else None
        with self._jobq_cv:
            while True:
                for i, job in enumerate(self._jobq):
                    if job.group is None or job.group == g:
                        job = self._jobq.pop(i)
                        job.group = g  # record the measured assignment
                        return job
                if deadline is None or self.stop.is_set():
                    return None
                wait = deadline - time.monotonic()
                if wait <= 0:
                    return None
                self._jobq_cv.wait(wait)

    def _embed(self, g: int, job: BatchJob):
        """The job's tokens to the device and through the embedding."""
        with spans.span("asap.group.embed"):
            tokens = jnp.asarray(job.tokens)
            self.record_copies(g, h2d=tokens.nbytes)
            return embed_tokens(self.params, tokens, None, self.cfg)

    def _group_worker(self, g: int):
        """Persistent serving loop of one attention DP group (ISSUE 4): pull
        jobs from the shared admission queue into free dual-batch slots, run
        the attention+dispatch/combine state machine, report completions out
        of order via `on_complete`, repeat until the engine closes."""
        try:
            fused = self.moe_path == "fused"
            dispatch = self._dispatch if fused else self._dispatch_eager
            active: List[Dict[str, Any]] = []
            free_slots = [0, 1] if self.interleave else [0]
            seq = 0
            while not self.stop.is_set():
                # admit into free slots; block (bounded) only when idle
                while free_slots:
                    job = self._take_job(
                        g, timeout=0.0 if active else (self.idle_backoff
                                                       or 0.05))
                    if job is None:
                        break
                    if job.t_started is None:
                        job.t_started = self.clock()
                    tok = np.asarray(job.tokens)
                    # valid-position mask: pad positions run attention but
                    # never enter MoE dispatch
                    valid = None
                    if job.lengths is not None:
                        valid = (np.arange(tok.shape[1])[None, :]
                                 < np.asarray(job.lengths)[:, None]).reshape(-1)
                    h = self._embed(g, job)
                    active.append({"job": job, "h": h, "layer": 0,
                                   "phase": "attn", "slot": free_slots.pop(0),
                                   "ctx": None, "seq": 0, "valid": valid,
                                   "kv": []})
                if not active:
                    continue  # idle: loop back into the blocking take
                # run attention+dispatch for every slot that is ready
                for st in active:
                    if st["phase"] != "attn":
                        continue
                    t0 = self.clock()
                    with spans.span("asap.group.attn"):
                        if fused:
                            lid = jnp.asarray(st["layer"], jnp.int32)
                            h, xf, w, idx, shared, kv = self._attn_step(
                                lid, st["h"])
                            w, idx = np.asarray(w), np.asarray(idx)
                            if kv is not None:  # emit_kv: per-layer KV handoff
                                kv = (np.asarray(kv[0]), np.asarray(kv[1]))
                                st["kv"].append(kv)
                            self.record_copies(
                                g, h2d=lid.nbytes,
                                d2h=sum(a.nbytes for a in kv or ()))
                        else:
                            h, xf, w, idx, shared = self._attn_part(
                                self._layer_params(st["layer"]), st["h"])
                    self.record_copies(g, d2h=w.nbytes + idx.nbytes)
                    dt = self.clock() - t0
                    st["job"].kernel_time += dt
                    self.group_busy[g] += dt  # race-ok: single-writer (group worker g accumulates its own cell)
                    st["h"] = h
                    st["ctx"] = (xf, w, shared)
                    with spans.span("asap.group.dispatch"):
                        dispatch(g, st["slot"], st["layer"], xf, idx,
                                 st["valid"])
                    st["phase"] = "wait"
                    st["seq"] = seq = seq + 1
                # block on the oldest outstanding combine
                waiting = [s for s in active if s["phase"] == "wait"]
                if not waiting:
                    continue
                st = min(waiting, key=lambda s: s["seq"])
                xf, w, shared = st["ctx"]
                t0 = self.clock()
                try:
                    st["h"] = self._combine(g, st["slot"], st["h"], xf, w,
                                            shared, st["valid"])
                except TimeoutError:
                    st["job"].comm_time += self.clock() - t0
                    self._retry_or_fail(g, st, active, free_slots)
                    continue
                st["job"].comm_time += self.clock() - t0
                st["layer"] += 1
                if st["layer"] >= self.L:
                    job = st["job"]
                    t0 = self.clock()
                    with spans.span("asap.group.final_norm"):
                        job.result = np.asarray(apply_norm(
                            st["h"], self.params["final_norm"], self.cfg))
                    self.record_copies(g, d2h=job.result.nbytes)
                    if st["kv"]:
                        job.kv = (np.stack([k for k, _ in st["kv"]]),
                                  np.stack([v for _, v in st["kv"]]))
                    dt = self.clock() - t0
                    job.kernel_time += dt
                    self.group_busy[g] += dt  # race-ok: single-writer (group worker g accumulates its own cell)
                    job.t_finished = self.clock()
                    free_slots.append(st["slot"])
                    active.remove(st)
                    if self.on_complete is not None:
                        self.on_complete(job)  # streaming completion hook
                    with self._done_cv:
                        self._done_cv.notify_all()
                else:
                    st["phase"] = "attn"
        except AbortedError:
            return  # stop observed inside a buffer wait (shutdown/panic)
        except BaseException as ex:
            self._panic(ex)

    # ------------------------------------------------ fault retry (ISSUE 8)
    def _scrub_group_slot(self, g: int, slot: int):
        """Quiesce-then-scrub one (group, slot) protocol lane after a region
        timeout.  Wait until no MoE buffer holds rows for region g AND no
        device is mid-serve on region g (worker `_moe_current` set under the
        buffer cv before the flags clear, so the two checks in THIS order
        cannot miss an in-flight take); every combine_send for the lane has
        then happened-before, and whatever partial combine state is parked
        in the slot's buffer can be dropped without a late stale segment
        corrupting the replay."""
        deadline = time.monotonic() + 4 * (self.region_timeout or 60.0)
        while True:
            if self.stop.is_set():
                raise AbortedError("scrub aborted: executor stopping")
            busy = False
            for e in range(self.E):
                if self.moe_bufs[e].flags[g].any_set():
                    busy = True
                    break
                # race-ok: checked AFTER the flags — a take publishes
                # _moe_current under the cv BEFORE clearing the flags, so a
                # region-g take invisible here would still have shown set
                # flags above; a stale non-None read just polls again
                cur = self._moe_current[e]
                if cur is not None and any(c[0] == g for c in cur):
                    busy = True
                    break
            if not busy:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"scrub: region {g} did not quiesce — MoE device wedged "
                    f"with supervision unable to evacuate it")
            time.sleep(0.002)
        self.attn_bufs[g][slot].scrub()
        self._logev("scrub", g, slot)

    def _retry_or_fail(self, g: int, st: Dict[str, Any], active, free_slots):
        """A region timed out (fault-dropped dispatch/combine or a failover
        window longer than region_timeout): scrub the lane and replay the
        batch from layer 0 with capped backoff.  Replays are idempotent —
        the scrub guarantees no stale segment survives, and re-served
        regions resolve first-combine-wins.  Past `max_job_retries` the job
        fails TERMINALLY (job.failed set, result None): the engine maps this
        to RequestResult.status="failed", which keeps drain()'s definite-
        state guarantee even when a device never comes back."""
        job = st["job"]
        job.retries += 1
        self._logev("region-timeout", g, st["slot"], st["layer"], job.retries)
        self._scrub_group_slot(g, st["slot"])
        if job.retries > self.max_job_retries:
            job.failed = (f"region timeout at layer {st['layer']} after "
                          f"{job.retries - 1} replays")
            job.result = None
            job.t_finished = self.clock()
            free_slots.append(st["slot"])
            active.remove(st)
            if self.on_complete is not None:
                self.on_complete(job)
            with self._done_cv:
                self._done_cv.notify_all()
            return
        # capped exponential backoff (wall seconds): give an in-progress
        # failover time to land before redispatching into the same hole
        time.sleep(min(0.05 * (2 ** (job.retries - 1)), 0.5))
        st["h"] = self._embed(g, job)
        st["layer"] = 0
        st["phase"] = "attn"
        st["ctx"] = None
        st["kv"] = []  # replay re-emits every layer's cache from scratch

    # ------------------------------------------- live re-placement (ISSUE 5)
    def apply_placement(self, placement: Placement,
                        expert_fractions: Optional[Sequence[float]] = None,
                        timeout: float = 60.0) -> Dict[str, Any]:
        """Re-place experts LIVE, between polls, without restarting workers
        (ROADMAP item (d3) — the simulator's online rebalancer finally has a
        real-runtime counterpart).  Protocol:

          1. freeze the dispatch gate and wait for in-flight dispatches to
             finish (a placement swap must never split a batch-layer's E
             sends across two routing tables);
          2. quiesce the AFFECTED MoE devices: with no new dispatches, each
             one drains its buffered regions — payloads carry local expert
             ids of the old tables and must be served with the old expert
             ids.  Unaffected devices keep serving throughout (their
             local id mapping is unchanged), and attention groups keep
             computing/combining — this is not a global barrier;
          3. hand the receivers their new expert-id vectors (on one chip
             the weights stay where they are; the bytes accounted are the
             expert copies a placement across chips would move);
          4. atomically swap `placement`/`table`/`dev_experts` + the dispatch
             lookups (`_primary`/`_replicated`/`_g2l`) and release the gate.

        Returns the migration record also appended to `self.migrations`
        (and surfaced through `ExecutorEngine.stats()`).

        Serialized by `_swap_lock`: the engine's rebalance tick and the
        supervisor's failover (ISSUE 8) both re-place experts through here
        and must never interleave freeze/quiesce/swap phases."""
        with self._swap_lock:
            return self._apply_placement_locked(placement, expert_fractions,
                                                timeout)

    def _apply_placement_locked(self, placement: Placement,
                                expert_fractions: Optional[Sequence[float]]
                                = None,
                                timeout: float = 60.0,
                                drain_hook=None,
                                kind: str = "rebalance") -> Dict[str, Any]:
        """apply_placement body; caller holds `_swap_lock`.  `drain_hook`
        (failover path) runs between drain polls OUTSIDE the gate cv: it
        serves the dead device's buffered regions with the OLD expert
        ids, which both empties them before the swap invalidates their
        local expert ids AND un-wedges any dispatcher blocked on the dead
        device's backpressure (that dispatcher holds the gate open)."""
        fr = tuple(float(x) for x in expert_fractions) \
            if expert_fractions is not None else self.expert_fractions
        assert len(fr) == self.cfg.num_experts
        new_table = placement.table(fr, self.E)
        new_dev = placement.device_experts(fr, self.E)
        moved = [(e, d) for e, hosts in enumerate(new_table)
                 for d in hosts if d not in self.table[e]]
        affected = [e for e in range(self.E)
                    if new_dev[e] != self.dev_experts[e]]
        t0 = self.clock()
        if new_table == self.table:
            # same layout (maybe refreshed popularity) — nothing to quiesce,
            # but the no-op still lands in the log so executed controller
            # plans and `migrations` stay in one-to-one correspondence
            self.placement, self.expert_fractions = placement, fr
            rec = {"t": t0, "seconds": 0.0, "moved_copies": 0, "bytes": 0.0,
                   "devices": (), "policy": placement.policy, "kind": kind}
            self.migrations.append(rec)
            return rec

        def _check_alive(deadline: float, phase: str):
            if self.errors:
                raise RuntimeError(
                    f"apply_placement during {phase}: executor thread "
                    f"failed") from self.errors[0]
            if self.stop.is_set():
                raise RuntimeError(f"apply_placement during {phase}: "
                                   f"executor is stopping")
            if time.monotonic() > deadline:
                raise TimeoutError(f"apply_placement: {phase} did not "
                                   f"quiesce within {timeout}s")

        deadline = time.monotonic() + timeout
        with self._gate_cv:
            self._gate_frozen = True
        try:
            while True:
                with self._gate_cv:
                    if self._dispatchers == 0:
                        break
                    if drain_hook is None:
                        self._gate_cv.wait(0.05)
                _check_alive(deadline, "dispatch drain")
                if drain_hook is not None:
                    # failover: a dispatcher may be wedged on the DEAD
                    # device's backpressure — serving its regions (outside
                    # the gate cv) is what lets that dispatcher finish
                    drain_hook()
                    time.sleep(0.001)
        except BaseException:
            with self._gate_cv:
                self._gate_frozen = False
                self._gate_cv.notify_all()
            raise
        try:
            for e in affected:
                # race-ok: quiesce poll — a stale read just polls again; the
                # gate freeze guarantees no NEW dispatch can re-set either
                while self.moe_bufs[e].any_pending() or self._moe_active[e]:
                    _check_alive(deadline, f"moe device {e} drain")
                    if drain_hook is not None:
                        drain_hook()
                    time.sleep(0.001)
            # bytes a cross-chip placement would move: every MoE device
            # here reads the one shared expert stack, so nothing is copied
            nbytes = 0.0
            for e in affected:
                gained = [x for x in new_dev[e]
                          if x not in self.dev_experts[e]]
                nbytes += self.expert_copy_bytes * self.L * len(gained)
            # atomic swap: the gate is frozen and the affected devices are
            # idle, so no reader observes a mix of old and new tables
            self.placement, self.expert_fractions = placement, fr
            self.table, self.dev_experts = new_table, new_dev
            self._primary, self._replicated, self._g2l = \
                self._dispatch_lookups(new_table, new_dev)
            for e in affected:
                self._moe_ids[e] = self._expert_ids(new_dev[e])
        finally:
            with self._gate_cv:
                self._gate_frozen = False
                self._gate_cv.notify_all()
        dt = self.clock() - t0
        rec = {"t": self.clock(), "seconds": dt, "moved_copies": len(moved),
               "bytes": nbytes, "devices": tuple(affected),
               "policy": placement.policy, "kind": kind}
        self.migrations.append(rec)
        self.migrated_bytes += nbytes
        # the re-placement occupies the receiving devices (quiesce + id
        # swap); split the measured stall across them for stats()
        if affected:
            self.moe_busy[list(affected)] += dt / len(affected)  # race-ok: workers for `affected` are parked behind the frozen gate here
        self._logev("migrate", tuple(affected), len(moved))
        return rec

    # ---------------------------------------------- supervision & failover
    def arm_faults(self, plan: FaultPlan, t0: Optional[float] = None):
        """Install and arm a deterministic fault plan against this
        executor's clock (ISSUE 8).  The engine passes `t0=0.0` — its
        TraceClock is already zero-based; a bare executor anchors the plan
        at the current clock reading."""
        inj = FaultInjector(plan, self.E)
        inj.arm(self.clock, t0=t0)
        self.fault_injector = inj
        return inj

    def _fence_worker(self, e: int) -> int:
        """Bump device e's generation under its buffer cv and return the
        NEW generation.  After the bump the old worker can neither take
        another region (recv_any re-validates the fence under the same cv)
        nor send another combine (it re-checks after computing); ownership
        of `_moe_current[e]` transfers to the supervisor."""
        buf = self.moe_bufs[e]

        def bump():
            self._moe_gen[e] += 1  # race-ok: runs under the buffer cv (fenced) — atomic w.r.t. recv_any admission
            return self._moe_gen[e]  # race-ok: same fenced scope as the bump above

        return buf.fenced(bump)

    def _serve_region(self, e: int, i: int, rows) -> None:
        """Failover path: compute one orphaned region with device e's OLD
        expert ids (on the supervisor thread) and combine it to its
        group — unless the group already holds device e's segment (first
        combine wins: the worker may have sent before dying)."""
        layer = rows[0].layer
        slot = rows[0].slot
        tokens, token_ids, eids = self._join_rows(rows)
        ffn = self._expert_ffn_fused if self.moe_path == "fused" \
            else self._expert_ffn_eager
        out = None
        if len(tokens):
            t0 = self.clock()
            out = ffn(e, layer, tokens, eids)
            self.moe_busy[e] += self.clock() - t0  # race-ok: worker e is fenced out; the supervisor is the cell's only writer here
        self._logev("moe-failover", e, i, slot, layer, len(tokens))
        abuf = self.attn_bufs[i][slot]
        if abuf.has_segment(e):
            return  # the dead worker's combine landed first — keep it
        try:
            with spans.span("asap.moe.combine_send"):
                abuf.combine_send(
                    e, CombinePayload(layer=layer, token_ids=token_ids,
                                      expert_ids=eids, outputs=out),
                    timeout=1.0, stop=self.stop)
        except TimeoutError:
            # segment held by a batch-layer the group has already timed out
            # and moved past — drop it; the group's replay re-covers it
            self._logev("combine-skipped", e, i, slot, layer)

    def _serve_orphans(self, e: int) -> int:
        """Drain device e's in-flight region (taken but never combined)
        plus every full region still buffered for it, serving each exactly
        once on the supervisor thread.  Caller holds `_swap_lock` and has
        fenced worker e out.  Publishes `_moe_current[e]` while serving so
        `_scrub_group_slot` observes the supervisor's in-flight work
        exactly like a worker's."""
        served = 0
        # race-ok: worker e is fenced out — the supervisor owns the cell.
        # An "entry still present" is the proof the worker's combine for
        # that region never happened (each entry is removed BEFORE its
        # combine_send), so re-serving every remaining entry here is
        # exactly-once — a fenced continuous batcher may leave SEVERAL
        # (its partial drain); serve them all.
        cur = self._moe_current[e]
        if cur is not None:
            for i, rows in cur:
                self._serve_region(e, i, rows)
                served += 1
            self._moe_current[e] = None  # race-ok: supervisor-owned after the fence
        buf = self.moe_bufs[e]

        def on_take(i, rows):
            # race-ok: published under the buffer cv; supervisor-owned
            # after the fence (scrub protocol: set before flags clear)
            self._moe_current[e] = ((i, rows),)

        while True:
            got = buf.recv_any(timeout=0, on_take=on_take)
            if got is None:
                return served
            i, rows = got
            self._serve_region(e, i, rows)
            self._moe_current[e] = None  # race-ok: supervisor-owned after the fence
            served += 1

    def _failover(self, e: int, reason: str):
        """Supervised recovery of MoE device e (ISSUE 8): fence the old
        worker out, serve its orphaned regions exactly once, evacuate its
        experts onto survivors through the live re-placement machinery
        (replica-first — `Placement.fail` mirrors the sim's `_fail_moe`),
        then restart the worker at the new generation.  Holds `_swap_lock`
        end-to-end so a concurrent engine rebalance cannot interleave with
        the evacuation."""
        self._logev("failover-begin", e, reason)
        with self._swap_lock:
            gen = self._fence_worker(e)
            self._serve_orphans(e)
            # the fenced worker can no longer flip this; in-flight
            # ownership transferred to the supervisor and its serving is
            # done, so the quiesce poll below must not wait on it
            self._moe_active[e] = False  # race-ok: worker e fenced out; supervisor is the only writer until the restart below
            failed = self.placement.fail(e)
            self._apply_placement_locked(
                failed, expert_fractions=self.expert_fractions,
                timeout=60.0, drain_hook=lambda: self._serve_orphans(e),
                kind="failover")
            old = self._moe_threads[e]
            if old.is_alive():
                self._retired.append(old)  # a stalled (not dead) worker:
                # fenced out, it exits on its next fence check; joined at
                # close()
            self._moe_restarts[e] += 1  # race-ok: supervisor single-writer
            self.failovers += 1  # race-ok: supervisor single-writer
            self._logev("failover", e, reason, self._moe_restarts[e])  # race-ok: supervisor single-writer
        # restart OUTSIDE _swap_lock: Thread.start() blocks on the thread's
        # internal started event (a condition wait the lockdep sanitizer
        # rightly flags under a held lock).  Only the supervisor writes
        # _moe_threads[e] after startup, so the gap is single-threaded.
        nt = threading.Thread(
            target=self._moe_worker, args=(e, gen),
            name=f"moe-{e}-r{self._moe_restarts[e]}", daemon=True)  # race-ok: supervisor single-writer
        self._moe_threads[e] = nt
        nt.start()
        cb = self.on_failover
        if cb is not None:
            # OUTSIDE _swap_lock: the engine's rebalance tick nests
            # _rebalance_lock -> apply_placement -> _swap_lock; calling out
            # under _swap_lock would close that cycle (ABBA)
            cb(e)

    def _supervisor_loop(self):
        """Detect dead or stalled MoE workers and fail them over
        (ISSUE 8).  Panics only as a last resort: restart budget exhausted
        or the failover machinery itself failing."""
        try:
            while not self.stop.is_set():
                for e in range(self.E):
                    t = self._moe_threads[e]
                    dead = not t.is_alive()
                    # race-ok: heartbeat/_moe_active/any_pending reads are a
                    # detection heuristic — a stale read only delays or
                    # re-confirms detection by one 20ms tick
                    stalled = (
                        self.stall_timeout is not None
                        and self.clock() - self._heartbeat[e]
                        > self.stall_timeout
                        and (self._moe_active[e]
                             or self.moe_bufs[e].any_pending()))
                    if not (dead or stalled):
                        continue
                    if self.stop.is_set():
                        return  # shutdown, not a fault: workers exit on stop
                    if self._moe_restarts[e] >= self.max_worker_restarts:  # race-ok: supervisor single-writer
                        # race-ok: supervisor single-writer (_moe_restarts);
                        # _moe_fail_exc read after the worker was seen dead
                        raise RuntimeError(
                            f"moe device {e} {'died' if dead else 'stalled'}"
                            f" with restart budget exhausted "
                            f"({self._moe_restarts[e]}/"
                            f"{self.max_worker_restarts})"
                        ) from self._moe_fail_exc[e]
                    self._failover(e, "died" if dead else "stalled")
                self.stop.wait(0.02)
        except BaseException as ex:
            if self.stop.is_set():
                return  # racing a shutdown: close() owns the teardown
            self._panic(ex)

    # ------------------------------------------------- engine lifecycle/run
    def ensure_started(self):
        """Spawn the persistent worker set once; raise instead of racing a
        wedged engine (thread failure or a timed-out wave still in flight)."""
        if self.errors:
            raise RuntimeError("executor reused after a thread failure") \
                from self.errors[0]
        self._hung = [t for t in self._hung if t.is_alive()]
        if self._hung:
            # a timed-out wave left live threads sharing our buffers —
            # submitting more work would race them mid-protocol
            raise RuntimeError(
                "executor reused while thread(s) from a timed-out run are "
                f"still alive: {[t.name for t in self._hung]}")
        if self._started:
            return
        self.stop.clear()
        if self._t_serving_start is None:
            self._t_serving_start = self.clock()
        now = self.clock()
        for e in range(self.E):
            self._heartbeat[e] = now  # race-ok: no worker threads are running yet
        # race-ok: no worker threads are running yet — gen reads the cell a
        # prior close()'s failovers last left it at
        self._moe_threads = [
            threading.Thread(target=self._moe_worker,
                             args=(e, self._moe_gen[e]),
                             name=f"moe-{e}", daemon=True)
            for e in range(self.E)]
        self._g_threads = [
            threading.Thread(target=self._group_worker, args=(g,),
                             name=f"group-{g}", daemon=True)
            for g in range(self.D)]
        for t in self._moe_threads + self._g_threads:
            t.start()
        if self.supervise:
            # spawned LAST: every thread it monitors is already alive
            self._sup_thread = threading.Thread(
                target=self._supervisor_loop, name="moe-supervisor",
                daemon=True)
            self._sup_thread.start()
        self._started = True

    def submit_job(self, job: BatchJob) -> BatchJob:
        """Admit one batch job (engine path).  Un-pinned jobs go to the
        least-loaded group (pull model); `job.group` pins (run() shim)."""
        self.ensure_started()
        if job.t_submitted is None:
            job.t_submitted = self.clock()
        with self._jobq_cv:
            self._jobq.append(job)
            self._jobq_cv.notify_all()
        return job

    def wait_jobs(self, jobs: Sequence[BatchJob],
                  timeout: Optional[float] = None) -> bool:
        """Block until every job in `jobs` completed (or a worker died).
        Returns False on timeout."""
        with self._done_cv:
            ok = self._done_cv.wait_for(
                lambda: bool(self.errors)
                or all(j.result is not None or j.failed is not None
                       for j in jobs), timeout)
        if self.errors:
            raise RuntimeError("executor thread failed") from self.errors[0]
        return bool(ok)

    def close(self, timeout: float = 30.0):
        """Stop the persistent workers and join them.  Drain first (the
        engine does) — a close with work in flight abandons it."""
        if not self._started:
            return
        self.stop.set()
        with self._jobq_cv:
            self._jobq_cv.notify_all()
        with self._done_cv:
            self._done_cv.notify_all()
        for buf in self.moe_bufs:
            buf.wake()  # prompt exit for workers idling in wait_any
        for bufs in self.attn_bufs:
            for buf in bufs:
                buf.wake()  # release combine_recv/combine_send blockers —
                # their stop-aware waits raise AbortedError instead of
                # deadlocking close() behind a 240s protocol timeout, and a
                # close() AFTER a panic joins survivors without raising a
                # second masking exception (ISSUE 8 satellite)
        sup = [self._sup_thread] if self._sup_thread is not None else []
        threads = self._g_threads + self._moe_threads + self._retired + sup
        for t in threads:
            t.join(timeout=timeout)
        alive = [t.name for t in threads if t.is_alive()]
        self._hung += [t for t in threads if t.is_alive()]
        self._g_threads, self._moe_threads = [], []
        self._retired, self._sup_thread = [], None
        self._started = False
        if not alive:
            self.stop.clear()  # a clean close is restartable (warm jit
            # caches); with survivors, `stop` must STAY set so a zombie that
            # later escapes a blocked combine exits instead of serving again
        if alive:
            raise TimeoutError(f"executor close: thread(s) {alive} did not "
                               f"exit within {timeout}s")

    def run(self, jobs_per_group: List[List[BatchJob]],
            timeout: float = 300.0) -> List[BatchJob]:
        """One-shot compatibility shim over the engine: pin each job to its
        hand-chosen group, submit the wave, block until it completes, then
        release the worker set (pre-engine callers never close(); the jit
        caches live on the object, so warm re-runs stay warm)."""
        assert len(jobs_per_group) == self.D
        self.ensure_started()
        jobs: List[BatchJob] = []
        for g, js in enumerate(jobs_per_group):
            for j in js:
                j.group = g
                j.result = None
                j.t_started = j.t_finished = None
                j.kernel_time = j.comm_time = 0.0
                jobs.append(j)
        for j in jobs:
            self.submit_job(j)
        if self.wait_jobs(jobs, timeout):
            self.close()  # idle workers join promptly; one-shot semantics
            return [j for js in jobs_per_group for j in js]
        # a hung wave must NOT silently return jobs with result=None — stop
        # the engine, reap what exits, and refuse reuse while survivors
        # still share our buffers (they would race a new worker set
        # mid-protocol); report thread state + the protocol tail
        self.stop.set()
        with self._jobq_cv:
            self._jobq_cv.notify_all()
        for buf in self.moe_bufs:
            buf.wake()
        for bufs in self.attn_bufs:
            for buf in bufs:
                buf.wake()
        sup = [self._sup_thread] if self._sup_thread is not None else []
        threads = self._g_threads + self._moe_threads + self._retired + sup
        grace = time.monotonic() + 2.0
        for t in threads:
            t.join(timeout=max(grace - time.monotonic(), 1e-3))
        self._hung = [t for t in threads if t.is_alive()]
        hung_g = [t.name for t in self._g_threads if t.is_alive()]
        stuck_moe = [t.name for t in self._moe_threads if t.is_alive()]
        self._g_threads, self._moe_threads = [], []
        self._retired, self._sup_thread = [], None
        self._started = False
        if not self._hung:  # a late-but-clean exit leaves the executor
            self.stop.clear()  # reusable, like the pre-engine run()
        with self._log_lock:
            tail = self.log[-6:]
        raise TimeoutError(
            f"executor run exceeded {timeout}s: group thread(s) "
            f"{hung_g} still alive (moe alive: {stuck_moe or 'none'}); "
            f"last protocol events: {tail}")
