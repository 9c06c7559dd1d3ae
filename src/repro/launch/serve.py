"""Serving launcher: drives the ASAP pipeline end-to-end through the ONE
online `ServingEngine` API (core/engine.py, ISSUE 4) — timed request
arrivals, streaming out-of-order completions, measured router statistics —
over either runtime:

  --engine executor : REAL disaggregated threaded runtime (attention group
                      threads + MoE device threads + shared-buffer async
                      primitives) on a reduced MoE model.  Requests arrive
                      on a replayable TraceClock at --rps (Poisson), flow
                      through the length-aware batcher into the shared
                      admission queue, and whichever attention group frees a
                      dual-batch slot first pulls the batch (least-loaded
                      assignment — no caller-side hand partition).  Each
                      completion prints as it lands: TTFT with its
                      queue/kernel/comm decomposition and the sampled first
                      token.  Measured per-expert routing fractions are
                      reported (and saved with --save-router-stats) — the
                      vector `--placement`/`expert_fractions` consumers eat.
  --engine sim      : the same lifecycle over the discrete-event simulator
                      at production scale (virtual time).

  PYTHONPATH=src python -m repro.launch.serve --engine executor --requests 8 --rps 4
  PYTHONPATH=src python -m repro.launch.serve --engine sim --rps 4

Model: the executor engine serves the reduced qwen3-MoE smoke model (3
layers, d_model 128, 8 experts top-2), built by `model_config()` /
`init_params`.  `chip_smoke.py` builds qwen3_moe_235b_a22b at its published
widths through the same `model_config(arch, layers)` / `init_params` /
`configure_compile_cache`.

Geometry is shared by both engines: --dp-groups D attention groups and
--moe-devices E MoE devices (defaults: 2x4 executor smoke, 4x16 sim
paper-faithful).  --time-scale compresses the executor's wall-clock replay
(trace seconds per wall second).

Executor hot-path knobs (ISSUE 3): --moe-path fused|eager selects the fused
super-kernel pipeline or the pre-fusion per-expert loop; --moe-kernel
pallas|ref picks the fused backend.

Expert placement / placement-control / fault-injection knobs (ISSUE 2+5 —
the rebalance flags drive BOTH engines; on the executor they re-place
experts LIVE between polls):
  --placement {round_robin,greedy_balanced,replicated,replicated(k)}
  --replicate-hot K        split the K hottest experts across hosts
  --rebalance-interval S   placement-control tick (cold round-robin start)
  --rebalance-threshold R  busy-time max/mean imbalance trigger
  --rebalance-policy P     one_shot_threshold | hysteresis | partial | drift
  --rebalance-release R / --rebalance-cooldown N / --rebalance-max-bytes B
  --failure-at T --failure-duration W
  --fail-moe-device D      kill MoE device D at T — routed through the shared
                           `FaultPlan` (core/faults.py, ISSUE 8) so it drives
                           BOTH engines: the sim evacuates analytically, the
                           executor detects the dead worker and runs a live
                           supervised failover (quiesce + weight copy + table
                           swap), printing a "supervised failover" line

Request-lifecycle knobs (executor engine, ISSUE 8): --request-deadline S
(past-deadline requests end status=timeout), --max-queue N (overload
shedding, status=shed), --hedge-factor F (clone overdue batches; first
completion per request wins).  Every completion line carries its terminal
status; --save-stats records the status histogram and failover count.
  --measured-from PATH     drive the sim's expert-load model from router
                           stats measured on a live run (RouterStatsCollector
                           JSON, e.g. --save-router-stats output) instead of
                           the synthetic --ep-skew Zipf
  e.g. PYTHONPATH=src python -m repro.launch.serve --engine sim --rps 2 \
         --ep-skew 1.2 --replicate-hot 2 --rebalance-interval 5

Prefill/decode disaggregation (ISSUE 9): `--mode pd` runs the full
disaggregated lifecycle on EITHER engine — a dedicated prefill engine feeds
a dedicated decode engine through the KV-handoff layer (core/kv.py), the
`PDOrchestrator` streams per-token completions out of order, and every
completion line carries tokens_out/TPOT.  Knobs: --out-len-mean/--out-len-cv
(sampled decode lengths, deterministic per rid), --decode-width (decode
batch slots), --colocated (baseline: no KV transfer cost, no handoffs
logged).  The run FAILS unless every request reaches a definite status, ok
requests produced exactly out_len tokens, and (disaggregated) at least one
KV handoff happened — the CI pd-smoke gate.
  e.g. PYTHONPATH=src python -m repro.launch.serve --engine executor \
         --mode pd --requests 6 --out-len-mean 4 --out-len-cv 0.5
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.core.cost_model import V5E, Deployment, Placement
from repro.core.decode import (DecodeExecutor, ExecDecodeEngine,
                               SimDecodeEngine)
from repro.core.engine import (ExecutorEngine, RouterStatsCollector,
                               SimEngine)
from repro.core.executor import DisaggregatedExecutor
from repro.core.faults import FaultPlan
from repro.core.orchestrator import PDOrchestrator
from repro.core.placement_control import POLICIES
from repro.core.scheduler import LengthAwareBatcher
from repro.core.simulator import SimConfig
from repro.core.trace import Request, TraceClock, TraceConfig, \
    generate_requests, sample_lengths, sample_out_len
from repro.kernels.super_gmm import tuning
from repro.models.common import ModelConfig
from repro.models.lm import init_lm_params


def _fmt_decomp(d):
    return " ".join(f"{k}={v * 1000:.0f}ms" for k, v in d.items())


# ---------------------------------------------------------------------------
# Model construction shared by the CLI runs and chip_smoke.py
# ---------------------------------------------------------------------------

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", ".."))


def configure_compile_cache() -> str:
    """JAX's persistent compile cache for an entry point: the directory in
    `JAX_COMPILATION_CACHE_DIR` when it is set (JAX reads it itself), else
    the fixed `<repo>/.jax_cache` (gitignored) — a fixed path, because the
    path is part of every cache key.  Entry points call this once; nothing
    sets it at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_config(arch: str = "smoke",
                 layers: Optional[int] = None) -> ModelConfig:
    """The served model.  "smoke" is the reduced qwen3-MoE model (3 layers,
    d_model 128, 8 experts top-2, float32); any registered MoE arch serves
    its published widths, with depth cut to `layers` when given."""
    if arch == "smoke":
        cfg = get_config("qwen3_moe_235b_a22b").smoke().replace(
            num_layers=3, num_experts=8, top_k=2)
    else:
        cfg = get_config(arch)
        assert cfg.family == "moe", f"{arch} is not an MoE model"
    if layers is not None:
        cfg = cfg.replace(num_layers=layers)
    return cfg


def init_params(cfg: ModelConfig, seed: int):
    """Random weights from `seed`, initialized on the default device in one
    jitted program (no host copy of a multi-GB model)."""
    return jax.jit(init_lm_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


def smoke_batcher() -> LengthAwareBatcher:
    """The CLI runs' batcher: batches of up to 128 tokens."""
    return LengthAwareBatcher(inflection=64, max_tokens=128,
                              exclusive_cutoff=10_000, max_wait=0.05)


def run_executor(args) -> int:
    cfg = model_config()
    params = init_params(cfg, args.seed)
    D = args.dp_groups if args.dp_groups is not None else 2
    E = args.moe_devices if args.moe_devices is not None else 4
    placement = Placement.parse(args.placement,
                                replicate_hot=args.replicate_hot)
    print(f"disaggregated executor engine: D={D} attention groups, E={E} MoE "
          f"devices, {cfg.num_layers}L x {cfg.num_experts}e "
          f"d_model={cfg.d_model} model  "
          f"[moe_path={args.moe_path} kernel={args.moe_kernel} "
          f"placement={placement.policy}"
          + (f"(hot={placement.replicate_hot})" if placement.replicate_hot
             else "") + f" time-scale={args.time_scale}x]")
    if args.tuning_table:
        tuning.set_table(tuning.TuningTable.load(args.tuning_table))
        print(f"super-kernel tuning table loaded from {args.tuning_table}")
    if args.moe_batch_window:
        print(f"continuous MoE batching: window={args.moe_batch_window * 1e3:g}ms"
              + (f" max_tokens={args.moe_batch_max_tokens}"
                 if args.moe_batch_max_tokens else ""))

    # timed arrivals: Poisson at --rps on the replayable trace clock
    # (satellite: --rps now drives the executor path, not just the sim)
    rng = np.random.default_rng(args.seed + 1)
    lengths = np.clip(sample_lengths(args.requests,
                                     TraceConfig(mean_len=48, max_len=64,
                                                 seed=args.seed)), 8, 64)
    arrivals = np.cumsum(rng.exponential(1.0 / max(args.rps, 1e-9),
                                         size=args.requests))
    reqs = [Request(rid=i, arrival=float(arrivals[i]), length=int(lengths[i]))
            for i in range(args.requests)]
    print(f"{args.requests} requests, Poisson arrivals at {args.rps} req/s "
          f"(last at t={arrivals[-1]:.2f}s), lengths "
          f"{[int(x) for x in lengths]}")

    # With a rebalance interval the executor boots on the cold round-robin
    # placement (same semantics as the sim) and the placement control plane
    # migrates LIVE toward --placement once it observes imbalance (ISSUE 5).
    boot = Placement() if args.rebalance_interval else placement
    ex = DisaggregatedExecutor(params, cfg, D=D, E=E, placement=boot,
                               moe_path=args.moe_path,
                               moe_kernel=args.moe_kernel,
                               idle_backoff=args.idle_backoff,
                               moe_batch_window=args.moe_batch_window,
                               moe_batch_max_tokens=args.moe_batch_max_tokens)
    # the SAME FaultPlan format the sim interprets analytically drives the
    # executor's injector + supervised failover (ISSUE 8)
    plan = FaultPlan.from_flags(args.failure_at, args.failure_duration,
                                args.fail_moe_device)
    if plan is not None:
        plan.validate(E)
        print(f"fault plan armed (supervised failover): "
              f"{[ev.to_dict() for ev in plan.events]}")
    engine = ExecutorEngine(
        ex, clock=TraceClock(speed=args.time_scale), batcher=smoke_batcher(),
        rebalance_interval=args.rebalance_interval,
        rebalance_threshold=args.rebalance_threshold,
        rebalance_policy=args.rebalance_policy,
        rebalance_target=placement,
        rebalance_release=args.rebalance_release,
        rebalance_cooldown=args.rebalance_cooldown,
        rebalance_max_bytes=args.rebalance_max_bytes,
        fault_plan=plan,
        request_deadline=args.request_deadline,
        max_queue=args.max_queue,
        hedge_factor=args.hedge_factor)
    if args.rebalance_interval:
        print(f"placement control plane: policy={args.rebalance_policy} "
              f"interval={args.rebalance_interval}s "
              f"threshold={args.rebalance_threshold} -> target "
              f"{placement.policy}"
              + (f"(hot={placement.replicate_hot})"
                 if placement.replicate_hot else ""))
    def _print_result(r):
        print(f"  done rid={r.rid:<3d} batch={r.batch_id} "
              f"group={r.group} ttft={r.ttft:.3f}s "
              f"first_token={r.first_token} status={r.status}"
              + (f" retries={r.retries}" if r.retries else "")
              + f"  [{_fmt_decomp(r.decomposition)}]")

    t0 = time.time()
    handles = engine.submit_all(reqs)
    results = []
    while len(results) < len(reqs) and time.time() - t0 < 600:
        for r in engine.poll():
            results.append(r)
            _print_result(r)
        time.sleep(0.01)
    for r in engine.drain(timeout=120):
        results.append(r)
        _print_result(r)
    wall = time.time() - t0

    # out-of-order completion evidence (the async-serving property)
    order = [r.rid for r in results]
    ooo = sum(1 for a, b in zip(order, order[1:]) if b < a)
    st = engine.stats()
    print(f"completed {len(results)}/{len(reqs)} requests in {wall:.1f}s wall "
          f"({st.elapsed:.1f}s trace); out-of-order completions: {ooo}")
    u = st.moe_device_util
    print(f"MoE device util: mean {u.mean() * 100:.0f}%  max "
          f"{u.max() * 100:.0f}%  imbalance {st.moe_imbalance():.2f}x; "
          f"attention group util: {np.round(st.group_util, 2)}")
    if st.moe_launches:
        print(f"super-kernel launches: {st.moe_launches} "
              f"({st.regions_per_launch():.2f} regions/launch, occupancy "
              f"{st.moe_batch_occupancy * 100:.0f}%, capacity buckets "
              f"{st.bucket_hits} hit / {st.bucket_misses} traced)")
    fr = st.expert_fractions
    hot = [int(e) for e in engine.router_stats.hot_experts(3)]
    print(f"measured router stats: {st.router_assignments:.0f} assignments, "
          f"fractions sum {fr.sum():.3f}, hottest experts {hot} "
          f"({', '.join(f'{fr[e]:.3f}' for e in hot)})")
    if st.migrations:
        print(f"live re-placement: {st.migrations} migration(s), "
              f"{st.migrated_bytes / 1e6:.2f} MB of expert weights a "
              f"cross-chip placement would move (ids swapped in place), "
              f"now serving placement={st.placement_policy}")
    if st.statuses:
        print("request statuses: "
              + " ".join(f"{k}={v}" for k, v in sorted(st.statuses.items())))
    if st.failovers:
        print(f"supervised failover: {st.failovers} MoE-device "
              f"evacuation(s) executed live; dead device(s) "
              f"{list(ex.placement.dead)} evacuated onto survivors")
    if st.hedges_issued:
        print(f"hedged dispatch: {st.hedges_issued} clone(s) issued, "
              f"{st.hedge_wins} won")
    if args.save_router_stats:
        engine.router_stats.save(args.save_router_stats)
        print(f"router stats saved to {args.save_router_stats}")
    if args.save_stats:
        with open(args.save_stats, "w") as f:
            json.dump({
                "engine": st.engine, "elapsed": st.elapsed,
                "submitted": st.submitted, "completed": st.completed,
                "placement_policy": st.placement_policy,
                "migrations": st.migrations,
                "migrated_bytes": st.migrated_bytes,
                "migration_log": ex.migrations,
                "moe_device_util": [float(x) for x in st.moe_device_util],
                "group_util": [float(x) for x in st.group_util],
                "expert_fractions": [float(x) for x in st.expert_fractions],
                "router_assignments": st.router_assignments,
                "mean_ttft": float(np.mean([r.ttft for r in results]))
                if results else None,
                "statuses": st.statuses,
                "failovers": st.failovers,
                "hedges_issued": st.hedges_issued,
                "hedge_wins": st.hedge_wins,
                "moe_batch_window": args.moe_batch_window,
                "moe_launches": st.moe_launches,
                "moe_batch_regions": st.moe_batch_regions,
                "regions_per_launch": st.regions_per_launch(),
                "moe_batch_occupancy": st.moe_batch_occupancy,
                "bucket_hits": st.bucket_hits,
                "bucket_misses": st.bucket_misses,
            }, f, indent=2)
        print(f"engine stats saved to {args.save_stats}")
    engine.close()

    missing = [h.rid for h in handles if not h.done()]
    if missing:  # CI smoke gate: per-request results must all exist
        print(f"ERROR: missing results for rids {missing}", file=sys.stderr)
        return 1
    # without an injected fault or a lifecycle limit, every request must
    # end ok on its first try: a retry or failover there hides a real error
    if plan is None and args.request_deadline is None \
            and args.max_queue is None and args.hedge_factor is None:
        bad = [r.rid for r in results if r.status != "ok" or r.retries]
        if bad or st.failovers:
            print(f"ERROR: rids {bad} not ok on the first try, "
                  f"{st.failovers} failover(s)", file=sys.stderr)
            return 1
    return 0


def run_simulation(args) -> int:
    cfg = get_config("deepseek_v32")
    measured = None
    if args.measured_from:
        col = RouterStatsCollector.load(args.measured_from)
        measured = col.resampled(max(cfg.num_experts, 1))
        print(f"expert-load model driven by MEASURED fractions from "
              f"{args.measured_from} ({col.total:.0f} assignments over "
              f"{col.num_experts} experts, resampled to {cfg.num_experts})")
    sim = SimConfig(mode=args.mode, rps=args.rps, duration=args.duration,
                    ep_skew=args.ep_skew, ep_skew_mode=args.ep_skew_mode,
                    placement=args.placement,
                    replicate_hot=args.replicate_hot,
                    rebalance_interval=args.rebalance_interval,
                    rebalance_threshold=args.rebalance_threshold,
                    rebalance_policy=args.rebalance_policy,
                    rebalance_release=args.rebalance_release,
                    rebalance_cooldown=args.rebalance_cooldown,
                    rebalance_max_bytes=args.rebalance_max_bytes,
                    failure_at=args.failure_at,
                    failure_duration=args.failure_duration,
                    failure_moe_device=args.fail_moe_device,
                    measured_fractions=measured)
    deps = {}
    if args.dp_groups is not None or args.moe_devices is not None:
        D = args.dp_groups if args.dp_groups is not None else 4
        E = args.moe_devices if args.moe_devices is not None else 16
        deps = dict(asap_dep=Deployment(D=D, T=4, E=E),
                    sync_dep=Deployment(D=2 * D, T=4, E=2 * E))
    engine = SimEngine(cfg, sim, **deps)
    engine.submit_all(generate_requests(args.rps, args.duration, sim.trace))
    results = engine.drain()
    st = engine.stats()

    pl = sim.resolved_placement()
    print(f"mode={args.mode} rps={args.rps} duration={args.duration}s "
          f"ep_skew={args.ep_skew} ({args.ep_skew_mode})"
          + (" [measured fractions]" if measured else ""))
    extra = f"placement={pl.policy}"
    if pl.replicate_hot:
        extra += f"(hot={pl.replicate_hot})"
    if args.rebalance_interval:
        extra += (f" rebalance every {args.rebalance_interval}s "
                  f"({args.rebalance_policy}); {st.migrations} migration(s), "
                  f"{st.migrated_bytes / 1e6:.1f} MB moved")
    if args.fail_moe_device is not None and args.failure_at is not None:
        extra += (f"  [MoE device {args.fail_moe_device} killed at "
                  f"t={args.failure_at}s]")
    print(f"  {extra}")
    ok = [r for r in results if r.status == "ok"]
    ttfts = np.array([r.ttft for r in ok])
    print(f"  completed: {len(ok)}/{st.submitted}"
          + (f"  (timeout: {len(results) - len(ok)})"
             if len(results) > len(ok) else ""))
    if len(ttfts):
        print(f"  mean TTFT: {ttfts.mean() * 1000:.0f} ms   "
              f"p99: {np.percentile(ttfts, 99) * 1000:.0f} ms")
    if st.moe_device_util is not None:
        u = st.moe_device_util
        print(f"  MoE device util: mean {u.mean() * 100:.0f}%  "
              f"max {u.max() * 100:.0f}%  imbalance {st.moe_imbalance():.2f}x")
    return 0


def _pd_gate(results, reqs, kv_log, colocated) -> int:
    """The pd-smoke contract: every request reached a definite status, every
    ok request produced exactly its sampled out_len tokens, and the
    disaggregated path performed at least one KV handoff."""
    out_len = {r.rid: r.out_len for r in reqs}
    rc = 0
    if len(results) != len(reqs):
        print(f"ERROR: {len(reqs) - len(results)} request(s) without a "
              f"result", file=sys.stderr)
        rc = 1
    for r in results:
        if r.status not in ("ok", "timeout", "shed", "failed"):
            print(f"ERROR: rid={r.rid} indefinite status {r.status!r}",
                  file=sys.stderr)
            rc = 1
        if r.status == "ok" and r.tokens_out != out_len[r.rid]:
            print(f"ERROR: rid={r.rid} produced {r.tokens_out} tokens, "
                  f"expected out_len={out_len[r.rid]}", file=sys.stderr)
            rc = 1
    if not colocated and kv_log.count < 1:
        print("ERROR: disaggregated run performed no KV handoff",
              file=sys.stderr)
        rc = 1
    return rc


def _pd_summary(results, kv_log, colocated):
    ok = [r for r in results if r.status == "ok"]
    ttfts = np.array([r.ttft for r in ok]) if ok else np.array([0.0])
    tpots = [r.tpot for r in ok if r.tpot is not None]
    toks = sum(r.tokens_out for r in ok)
    print(f"completed {len(ok)}/{len(results)} ok, {toks} tokens out; "
          f"mean TTFT {ttfts.mean() * 1000:.0f} ms"
          + (f", mean TPOT {np.mean(tpots) * 1000:.1f} ms" if tpots else ""))
    if colocated:
        print("kv handoffs: 0 (colocated baseline)")
    else:
        print(f"kv handoffs: {kv_log.count} "
              f"({kv_log.bytes / 1e6:.2f} MB, "
              f"{kv_log.seconds * 1000:.2f} ms wire time)")


def run_pd(args) -> int:
    """Disaggregated prefill/decode serving (`--mode pd`, ISSUE 9): a
    dedicated prefill engine feeds a dedicated decode engine through the
    KV-handoff layer, federated by the PDOrchestrator."""
    out_mean = args.out_len_mean if args.out_len_mean is not None else 4.0
    out_cv = args.out_len_cv if args.out_len_cv is not None else 0.5
    label = "colocated baseline" if args.colocated else "disaggregated"

    if args.engine == "sim":
        cfg = get_config("deepseek_v32")
        tc = TraceConfig(out_len_mean=out_mean, out_len_cv=out_cv)
        sim = SimConfig(mode="asap", rps=args.rps, duration=args.duration,
                        ep_skew=args.ep_skew, ep_skew_mode=args.ep_skew_mode,
                        trace=tc)
        width = args.decode_width if args.decode_width is not None else 32
        pre = SimEngine(cfg, sim)
        dec = SimDecodeEngine(cfg, pre._sim.cm,
                              load_model=pre._sim.load_model, width=width)
        orch = PDOrchestrator([pre], [dec], hw=pre._sim.cm.hw,
                              colocated=args.colocated)
        reqs = generate_requests(args.rps, args.duration, tc)
        print(f"sim pd engine ({label}): rps={args.rps} "
              f"duration={args.duration}s out_len~lognorm(mean={out_mean}, "
              f"cv={out_cv}) decode_width={width}")
        orch.submit_all(reqs)
        results = orch.drain()
        for r in sorted(results, key=lambda x: x.completion_time
                        if x.completion_time is not None
                        else x.first_token_time)[:12]:
            print(f"  done rid={r.rid:<3d} tokens_out={r.tokens_out} "
                  f"ttft={r.ttft:.3f}s"
                  + (f" tpot={r.tpot * 1000:.1f}ms" if r.tpot else "")
                  + f" status={r.status}")
        _pd_summary(results, orch.kv_log, args.colocated)
        return _pd_gate(results, reqs, orch.kv_log, args.colocated)

    # --- real executor backend -------------------------------------------
    cfg = model_config()
    params = init_params(cfg, args.seed)
    D = args.dp_groups if args.dp_groups is not None else 2
    E = args.moe_devices if args.moe_devices is not None else 4
    slots = args.decode_width if args.decode_width is not None else 4
    max_len = 64  # decode cache rows: prompt + decode tail per request
    tc = TraceConfig(mean_len=24, max_len=32, seed=args.seed,
                     out_len_mean=out_mean, out_len_cv=out_cv)
    rng = np.random.default_rng(args.seed + 1)
    lengths = np.clip(sample_lengths(args.requests, tc), 8, 32)
    arrivals = np.cumsum(rng.exponential(1.0 / max(args.rps, 1e-9),
                                         size=args.requests))
    reqs = [Request(rid=i, arrival=float(arrivals[i]),
                    length=int(lengths[i]),
                    out_len=min(sample_out_len(i, tc),
                                max_len - int(lengths[i])))
            for i in range(args.requests)]
    print(f"executor pd engine ({label}): D={D} prefill groups, E={E} MoE "
          f"devices -> decode runtime with {slots} slots x {max_len} tokens; "
          f"{args.requests} requests, out_lens "
          f"{[r.out_len for r in reqs]}")
    if args.tuning_table:
        tuning.set_table(tuning.TuningTable.load(args.tuning_table))
        print(f"super-kernel tuning table loaded from {args.tuning_table}")
    ex = DisaggregatedExecutor(params, cfg, D=D, E=E, emit_kv=True,
                               moe_path=args.moe_path,
                               moe_kernel=args.moe_kernel,
                               idle_backoff=args.idle_backoff,
                               moe_batch_window=args.moe_batch_window,
                               moe_batch_max_tokens=args.moe_batch_max_tokens)
    clock = TraceClock(speed=args.time_scale)
    pre = ExecutorEngine(ex, clock=clock, keep_kv=True,
                         batcher=smoke_batcher())
    rt = DecodeExecutor(params, cfg, slots=slots, max_len=max_len,
                        clock=clock.now)
    dec = ExecDecodeEngine(rt)
    orch = PDOrchestrator([pre], [dec], hw=V5E, colocated=args.colocated)

    t0 = time.time()
    orch.submit_all(reqs)
    results = []
    while len(results) < len(reqs) and time.time() - t0 < 600:
        for r in orch.poll():
            results.append(r)
            print(f"  done rid={r.rid:<3d} tokens_out={r.tokens_out} "
                  f"ttft={r.ttft:.3f}s"
                  + (f" tpot={r.tpot * 1000:.1f}ms" if r.tpot else "")
                  + f" status={r.status}  [{_fmt_decomp(r.decomposition)}]")
        time.sleep(0.01)
    for r in orch.drain(timeout=120):
        results.append(r)
        print(f"  done rid={r.rid:<3d} tokens_out={r.tokens_out} "
              f"ttft={r.ttft:.3f}s status={r.status}")
    _pd_summary(results, orch.kv_log, args.colocated)
    print(f"decode runtime: {rt.trace_counts['decode_step']} trace(s) of the "
          f"jitted step (zero steady-state retraces == 1)")
    rc = _pd_gate(results, reqs, orch.kv_log, args.colocated)
    if args.save_stats:
        ok = [r for r in results if r.status == "ok"]
        tpots = [r.tpot for r in ok if r.tpot is not None]
        with open(args.save_stats, "w") as f:
            json.dump({
                "engine": f"pd:{'colocated' if args.colocated else 'remote'}",
                "requests": len(reqs),
                "completed_ok": len(ok),
                "tokens_out": int(sum(r.tokens_out for r in ok)),
                "expected_tokens": int(sum(r.out_len for r in reqs)),
                "mean_ttft": float(np.mean([r.ttft for r in ok]))
                if ok else None,
                "mean_tpot": float(np.mean(tpots)) if tpots else None,
                "kv_handoffs": orch.kv_log.count,
                "kv_bytes": orch.kv_log.bytes,
                "decode_traces": rt.trace_counts["decode_step"],
                "statuses": {r.rid: r.status for r in results},
            }, f, indent=2)
        print(f"pd stats saved to {args.save_stats}")
    orch.close()
    return rc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=["executor", "sim"], default="executor")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rps", type=float, default=4.0,
                    help="Poisson arrival rate — drives BOTH engines' timed "
                         "admission (ISSUE 4)")
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--dp-groups", type=int, default=None,
                    help="attention DP groups D, shared by both engines "
                         "(default: 2 executor / 4 sim)")
    ap.add_argument("--moe-devices", type=int, default=None,
                    help="MoE expert devices E, shared by both engines "
                         "(default: 4 executor / 16 sim)")
    ap.add_argument("--time-scale", type=float, default=50.0,
                    help="executor engine: trace seconds replayed per wall "
                         "second (TraceClock speed)")
    ap.add_argument("--save-router-stats", default=None, metavar="PATH",
                    help="write measured per-expert routing stats (JSON) "
                         "after an executor run — feed back via "
                         "--measured-from or fig_ep_skew --skew measured")
    ap.add_argument("--measured-from", default=None, metavar="PATH",
                    help="sim engine: drive expert load from measured router "
                         "stats JSON instead of synthetic --ep-skew")
    ap.add_argument("--mode", default="asap",
                    choices=["asap", "default", "chunked", "pd"],
                    help="sim baseline mode, or `pd` for the disaggregated "
                         "prefill/decode lifecycle on EITHER engine "
                         "(ISSUE 9)")
    ap.add_argument("--out-len-mean", type=float, default=None,
                    help="pd mode: mean sampled decode length (tokens, "
                         "lognormal, deterministic per rid; default 4)")
    ap.add_argument("--out-len-cv", type=float, default=None,
                    help="pd mode: coefficient of variation of the sampled "
                         "decode lengths (default 0.5)")
    ap.add_argument("--decode-width", type=int, default=None,
                    help="pd mode: decode batch width — sim continuous-batch "
                         "cap (default 32) / executor cache slots (default 4)")
    ap.add_argument("--colocated", action="store_true",
                    help="pd mode: colocated baseline — prefill and decode "
                         "share the device, KV transfer costs nothing and no "
                         "handoff is logged")
    ap.add_argument("--ep-skew", type=float, default=0.0,
                    help="Zipf exponent of expert-routing skew (0 = uniform)")
    ap.add_argument("--ep-skew-mode", default="zipf",
                    choices=["uniform", "zipf", "layer"],
                    help="hot experts per-layer (zipf) or layer-correlated")
    ap.add_argument("--placement", default="round_robin",
                    help="expert placement policy: round_robin | "
                         "greedy_balanced | replicated | replicated(k)")
    ap.add_argument("--replicate-hot", type=int, default=0,
                    help="replicate the k hottest experts across the least-"
                         "loaded MoE devices (implies --placement replicated)")
    ap.add_argument("--rebalance-interval", type=float, default=None,
                    help="seconds between placement-control ticks (BOTH "
                         "engines, ISSUE 5): start round-robin, migrate to "
                         "the target placement once the policy decides — the "
                         "executor engine re-places experts LIVE")
    ap.add_argument("--rebalance-threshold", type=float, default=1.05,
                    help="observed busy-time max/mean imbalance that "
                         "triggers a migration")
    ap.add_argument("--rebalance-policy", default=None, choices=POLICIES,
                    help="placement-control policy (default "
                         "one_shot_threshold); requires --rebalance-interval")
    ap.add_argument("--rebalance-release", type=float, default=None,
                    help="hysteresis policy: imbalance below which the "
                         "placement reverts to the boot layout")
    ap.add_argument("--rebalance-cooldown", type=int, default=1,
                    help="min windows between migrations (hysteresis/drift)")
    ap.add_argument("--rebalance-max-bytes", type=float, default=None,
                    help="partial policy: cap on expert-weight bytes "
                         "migrated per window")
    ap.add_argument("--save-stats", default=None, metavar="PATH",
                    help="executor engine: write EngineStats + the live "
                         "migration log as JSON after the run")
    ap.add_argument("--failure-at", type=float, default=None,
                    help="inject a failure at this time (seconds)")
    ap.add_argument("--failure-duration", type=float, default=5.0,
                    help="repair window of the injected failure")
    ap.add_argument("--fail-moe-device", type=int, default=None,
                    help="kill this MoE device at --failure-at (instead of "
                         "the DP-group outage); replicas fail over, orphaned "
                         "experts re-place after the repair window")
    ap.add_argument("--request-deadline", type=float, default=None,
                    help="executor engine: TTFT deadline in trace seconds — "
                         "requests that age past it expire in queue or are "
                         "marked status=timeout on completion (ISSUE 8)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="executor engine: admission-queue cap — arrivals "
                         "beyond it are shed with status=shed instead of "
                         "queueing unboundedly (ISSUE 8)")
    ap.add_argument("--hedge-factor", type=float, default=None,
                    help="executor engine: clone a batch overdue by this "
                         "factor x the EWMA batch service time onto the "
                         "shared queue; first completion per request wins "
                         "(ISSUE 8)")
    ap.add_argument("--moe-path", default="fused", choices=["fused", "eager"],
                    help="executor engine: fused super-kernel hot path or the "
                         "pre-fusion per-expert loop (benchmark baseline)")
    ap.add_argument("--moe-kernel", default="pallas",
                    choices=["pallas", "ref"],
                    help="fused path backend: Pallas super_gmm grid or the "
                         "layer-indexed einsum oracle")
    ap.add_argument("--moe-batch-window", type=float, default=0.0,
                    help="executor engine (ISSUE 10): cross-region continuous "
                         "batching — after the first drained region each MoE "
                         "worker keeps accumulating arrivals for up to this "
                         "many WALL seconds and launches the super kernel "
                         "ONCE per layer over the merged capacity buffer; 0 "
                         "(default) reproduces the per-region path bit-"
                         "exactly")
    ap.add_argument("--moe-batch-max-tokens", type=int, default=None,
                    help="cap on merged token rows per batched drain "
                         "(bounds the capacity bucket the merged launch "
                         "lands in); requires --moe-batch-window > 0")
    ap.add_argument("--tuning-table", default=None, metavar="PATH",
                    help="super-kernel autotuning table JSON (from "
                         "benchmarks/tune_superkernel.py) consulted per "
                         "launch for Pallas block sizes; absent entries fall "
                         "back to the built-in heuristic")
    ap.add_argument("--idle-backoff", type=float, default=0.05,
                    help="max seconds a MoE worker waits on its condition "
                         "variable before re-checking the stop flag")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    # flag-combination validation (ISSUE 5 satellite): a policy knob without
    # the interval that would ever tick it is a configuration mistake the
    # user should hear about, not a silent no-op
    if args.rebalance_interval is None:
        for flag, val, default in (
                ("--rebalance-policy", args.rebalance_policy, None),
                ("--rebalance-threshold", args.rebalance_threshold, 1.05),
                ("--rebalance-release", args.rebalance_release, None),
                ("--rebalance-cooldown", args.rebalance_cooldown, 1),
                ("--rebalance-max-bytes", args.rebalance_max_bytes, None)):
            if val != default:
                ap.error(f"{flag} requires --rebalance-interval (the "
                         f"control plane never ticks without an interval)")
    if args.rebalance_policy == "partial" and not args.rebalance_max_bytes:
        ap.error("--rebalance-policy partial requires --rebalance-max-bytes "
                 "(the per-window migration budget)")
    if args.rebalance_release is not None \
            and args.rebalance_release > args.rebalance_threshold:
        ap.error(f"--rebalance-release ({args.rebalance_release}) must not "
                 f"exceed --rebalance-threshold ({args.rebalance_threshold})")
    if args.rebalance_policy is None:
        args.rebalance_policy = "one_shot_threshold"
    if args.rebalance_interval is not None \
            and args.rebalance_interval <= 0:
        ap.error("--rebalance-interval must be positive")
    # fault / lifecycle flag validation (ISSUE 8 satellite): unsupported
    # combinations fail loudly instead of silently dropping the fault
    if args.fail_moe_device is not None and args.failure_at is None:
        ap.error("--fail-moe-device requires --failure-at (when should the "
                 "device die?)")
    if args.engine == "executor" and args.failure_at is not None \
            and args.fail_moe_device is None:
        ap.error("--failure-at without --fail-moe-device is the sim's "
                 "DP-group outage; the executor engine has no DP-group "
                 "failure path — pass --fail-moe-device D to kill an MoE "
                 "device instead")
    if args.engine == "sim":
        for flag, val in (("--request-deadline", args.request_deadline),
                          ("--max-queue", args.max_queue),
                          ("--hedge-factor", args.hedge_factor)):
            if val is not None:
                ap.error(f"{flag} is an executor-engine request-lifecycle "
                         f"knob; --engine sim does not consume it")
    # cross-region batching / tuning flag validation (ISSUE 10 satellite):
    # the sim has no super-kernel launches to batch or tune — these knobs
    # only exist on the REAL executor, so reject them loudly there
    if args.moe_batch_window < 0:
        ap.error("--moe-batch-window must be >= 0")
    if args.engine == "sim":
        for flag, val, default in (
                ("--moe-batch-window", args.moe_batch_window, 0.0),
                ("--moe-batch-max-tokens", args.moe_batch_max_tokens, None),
                ("--tuning-table", args.tuning_table, None)):
            if val != default:
                ap.error(f"{flag} batches/tunes the REAL executor's super-"
                         f"kernel launches; --engine sim does not consume it")
    if args.moe_batch_window > 0 and args.moe_path == "eager":
        ap.error("--moe-batch-window requires --moe-path fused (batching "
                 "merges regions into ONE capacity buffer)")
    if args.moe_batch_max_tokens is not None:
        if args.moe_batch_max_tokens < 1:
            ap.error("--moe-batch-max-tokens must be >= 1")
        if args.moe_batch_window <= 0:
            ap.error("--moe-batch-max-tokens bounds the accumulation window; "
                     "it requires --moe-batch-window > 0")
    if args.rebalance_interval is not None \
            and Placement.parse(args.placement,
                                args.replicate_hot) == Placement():
        print("warning: --rebalance-interval with the default round_robin "
              "--placement arms a control plane that is already at its "
              "target — no migration will ever fire; pass --placement/"
              "--replicate-hot to give it somewhere to go", file=sys.stderr)
    # pd-mode flag validation (ISSUE 9 satellite): decode knobs without the
    # mode that consumes them are configuration mistakes, not silent no-ops
    if args.mode != "pd":
        for flag, val in (("--out-len-mean", args.out_len_mean),
                          ("--out-len-cv", args.out_len_cv),
                          ("--decode-width", args.decode_width)):
            if val is not None:
                ap.error(f"{flag} requires --mode pd (only the "
                         f"disaggregated lifecycle runs a decode stage)")
        if args.colocated:
            ap.error("--colocated requires --mode pd (it selects the "
                     "colocated prefill+decode baseline)")
    else:
        if args.out_len_mean is not None and args.out_len_mean < 1.0:
            ap.error("--out-len-mean must be >= 1 (every request emits at "
                     "least the first token)")
        if args.out_len_cv is not None and args.out_len_cv < 0.0:
            ap.error("--out-len-cv must be >= 0")
        if args.decode_width is not None and args.decode_width < 1:
            ap.error("--decode-width must be >= 1")
        for flag, val in (("--rebalance-interval", args.rebalance_interval),
                          ("--failure-at", args.failure_at),
                          ("--request-deadline", args.request_deadline),
                          ("--max-queue", args.max_queue),
                          ("--hedge-factor", args.hedge_factor)):
            if val is not None:
                ap.error(f"{flag} is not supported with --mode pd (the "
                         f"disaggregated path runs the plain prefill "
                         f"lifecycle; run those knobs without --mode pd)")
        if args.engine == "executor":
            configure_compile_cache()
        sys.exit(run_pd(args))
    if args.engine == "executor":
        configure_compile_cache()
        sys.exit(run_executor(args))
    sys.exit(run_simulation(args))


if __name__ == "__main__":
    main()
