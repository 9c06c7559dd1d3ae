#!/usr/bin/env python3
"""Chip smoke test: the ASAP serving path at Qwen3-235B-A22B widths on one TPU.

    python3 chip_smoke.py

One process, one chip.  It refuses to run anywhere but on a TPU, then builds
the model with the construction code `repro.launch.serve` uses
(`model_config`, `init_params`) and drives the served path through the
objects a server is built from (`DisaggregatedExecutor`, `ExecutorEngine`,
`DecodeExecutor`, `PDOrchestrator`):

  * the model: qwen3_moe_235b_a22b at its published widths (d_model 4096,
    64 query / 4 KV heads of 128 with qk-norm, 128 routed experts top-8 of
    d_ff 1536, vocabulary 151,936), bf16 weights drawn from --seed, depth cut
    to one layer (every layer is an MoE layer, so one layer is a whole
    period);
  * phase 1, prefill: an `ExecutorEngine` over the `DisaggregatedExecutor`
    (2 attention groups, 4 MoE devices, fused path, compiled Pallas
    super-GMM) answers 6 requests of 128-1024 tokens; the last-position
    logits of three of them (128, 1024 and 200 tokens) are checked against
    `lm_backbone(..., moe_mode="dense")` in float32 at highest matmul
    precision, and their argmax against the reference's and the engine's
    first token;
  * phase 2, PD: a `PDOrchestrator` hands 4 requests from an `emit_kv`
    prefill executor to a `DecodeExecutor` through the KV handoff and
    decodes 4 tokens each.

Every shape either phase serves is compiled before its first arrival.  Any
failed check raises, so the exit code is non-zero; on success the last line
of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# bf16 rounds every activation it stores to 8 significant bits (unit
# roundoff 2^-9 ~ 2e-3).  The served path stores one in bf16 at each of
# about a dozen points (embedding, attention output, residual, payload rows,
# the expert FFN's gated hidden, combine, final norm, logits), so the
# float32 reference may differ by a few times 1e-2 of the logit scale.
# Sound runs read about 0.008 of it.  A planted fault (one of the four MoE
# devices running each buffer through a neighbour expert's weights) reads
# 0.39-1.04 at a reduced width (d_model 256, 16 experts top-4) and
# 0.41-1.08 at full width, on each request whose last token routes to that
# device, and flips the argmax, which is checked as well.  The readings
# are in PERF.md.
LOGIT_RTOL = 5e-2


def _gb(nbytes: float) -> str:
    return f"{nbytes / 1e9:.3f} GB"


def _param_bytes(params) -> dict:
    """Device bytes of the weights, per category."""
    import jax

    out: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if "experts" in names:
            cat = "routed experts"
        elif "router" in names:
            cat = "router"
        elif names[0] in ("embed", "lm_head"):
            cat = names[0]
        else:
            cat = "attention + norms"
        out[cat] = out.get(cat, 0) + leaf.nbytes
    return out


def _reference_logits(params, cfg, token_rows):
    """Last-position logits of `lm_backbone(..., moe_mode="dense")` in
    float32 at highest matmul precision, computed on the host CPU (the
    float32 copy of the weights does not fit next to the bf16 one on the
    chip)."""
    import jax
    import jax.numpy as jnp

    from repro.models.lm import lm_backbone, lm_head

    cpu = jax.devices("cpu")[0]
    cfg32 = cfg.replace(dtype=jnp.float32)
    p32 = jax.tree.map(lambda a: jax.device_put(a, cpu).astype(jnp.float32),
                       params)

    @jax.jit
    def last_logits(p, tokens):
        h, _ = lm_backbone(p, cfg32, tokens, moe_mode="dense")
        return lm_head(p, h[:, -1], cfg32)

    out = []
    with jax.default_matmul_precision("highest"):
        for toks in token_rows:
            t = jax.device_put(jnp.asarray(toks, jnp.int32)[None], cpu)
            out.append(jax.device_get(last_logits(p32, t))[0])
    del p32
    return out


def _check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def _moe_step_has_kernel(ex, cfg) -> bool:
    """Compile the executor's MoE step at its smallest capacity bucket and
    look for the Pallas kernel in the compiled HLO."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.super_gmm.ops import round_capacity

    n_e = len(ex.dev_experts[0])
    xb = jax.ShapeDtypeStruct((n_e, round_capacity(1), cfg.d_model),
                              cfg.dtype)
    lid = jax.ShapeDtypeStruct((1,), jnp.int32)
    hlo = ex._moe_jit.lower(ex._experts, ex._moe_ids[0], lid,
                            xb).compile().as_text()
    return "tpu_custom_call" in hlo


def run(cfg, *, seed: int = 0, prefill_lengths=(128, 1024, 200, 512, 384,
                                                 777),
        reference_rids=(0, 1, 2), pd_lengths=(128, 256, 160, 240),
        out_len: int = 4, max_rows: int = 512, decode_max_len: int = 512,
        D: int = 2, E: int = 4) -> None:
    """Both phases on the default device; raises on any failed check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cost_model import V5E
    from repro.core.decode import DecodeExecutor, ExecDecodeEngine
    from repro.core.engine import ExecutorEngine, _pad_bucket
    from repro.core.executor import DisaggregatedExecutor
    from repro.core.orchestrator import PDOrchestrator
    from repro.core.scheduler import LengthAwareBatcher
    from repro.core.trace import Request, TraceClock
    from repro.kernels.super_gmm import tuning
    from repro.launch import serve
    from repro.models.lm import lm_head

    tuning.set_table(None)  # the heuristic blocking; no table is read
    dev = jax.devices()[0]

    def one_per_batch():
        # every request is its own batch: B=1 and the S bucket of its length
        return LengthAwareBatcher(inflection=1, max_tokens=1 << 30,
                                  exclusive_cutoff=1 << 30, max_wait=0.0)

    t0 = time.perf_counter()
    params = serve.init_params(cfg, seed)
    jax.block_until_ready(params)
    print(f"weights: random from seed {seed}, initialized in "
          f"{time.perf_counter() - t0:.1f}s")
    pb = _param_bytes(params)
    for cat, n in sorted(pb.items(), key=lambda kv: -kv[1]):
        print(f"  {cat:<18} {_gb(n)}")
    total = sum(pb.values())
    print(f"  {'total':<18} {_gb(total)}")
    rng = np.random.default_rng(seed + 1)

    # ---------------------------------------------------------- phase 1 --
    ex = DisaggregatedExecutor(params, cfg, D=D, E=E)
    live = sum(a.nbytes for a in jax.live_arrays())
    print(f"device arrays after building the executor: {_gb(live)} "
          f"(weights {_gb(total)}); routed experts held once")
    _check(live - total < pb["routed experts"] / 4,
           "expert weights are held on the device more than once")
    shapes = sorted({(1, _pad_bucket(n)) for n in prefill_lengths})
    t0 = time.perf_counter()
    ex.prewarm_buckets(max_rows)
    t_moe = time.perf_counter() - t0
    t0 = time.perf_counter()
    ex.prewarm_batches(shapes)
    t_attn = time.perf_counter() - t0
    print(f"phase 1 compile: MoE step {t_moe:.1f}s "
          f"({ex.trace_counts['moe']} capacity buckets up to "
          f"{max_rows} rows), attention step + combine {t_attn:.1f}s "
          f"(batch shapes {shapes})")
    if dev.platform == "tpu":
        _check(_moe_step_has_kernel(ex, cfg),
               "compiled MoE step has no tpu_custom_call")
        print("compiled MoE step HLO contains tpu_custom_call (Pallas "
              "super-GMM compiled, not interpreted)")
    warm = dict(ex.trace_counts)

    clock = TraceClock(speed=1.0)
    engine = ExecutorEngine(ex, clock=clock, batcher=one_per_batch())
    h_last = {}
    on_done = ex.on_complete

    def keep_last_hidden(job):
        if job.result is not None:
            for i, r in enumerate(job.meta):
                h_last[r.rid] = np.asarray(job.result[i, job.lengths[i] - 1])
        on_done(job)

    ex.on_complete = keep_last_hidden
    reqs = [Request(rid=i, arrival=0.05 * i, length=int(n))
            for i, n in enumerate(prefill_lengths)]
    tokens = {r.rid: rng.integers(0, cfg.vocab_size, r.length,
                                  dtype=np.int32) for r in reqs}
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r, tokens[r.rid])
    results = engine.drain(timeout=600)
    wall = time.perf_counter() - t0
    st = engine.stats()
    engine.close()
    for r in sorted(results, key=lambda x: x.rid):
        print(f"  prefill rid={r.rid} len={r.length} status={r.status} "
              f"retries={r.retries} first_token={r.first_token}")
    statuses = {s: sum(r.status == s for r in results)
                for s in sorted({r.status for r in results})}
    print(f"phase 1: {len(results)}/{len(reqs)} requests, statuses "
          f"{statuses}, retries {sum(r.retries for r in results)}, "
          f"failovers {st.failovers}, hedges {st.hedges_issued}, "
          f"{st.moe_launches} MoE launches, capacity buckets "
          f"{st.bucket_hits} hit / {st.bucket_misses} new, {wall:.1f}s wall")
    _check(len(results) == len(reqs), "phase 1: missing results")
    _check(all(r.status == "ok" and r.retries == 0 for r in results),
           "phase 1: a request did not end ok on its first try")
    _check(st.failovers == 0 and st.hedges_issued == 0 and not ex.errors,
           f"phase 1: failovers={st.failovers} hedges={st.hedges_issued} "
           f"errors={ex.errors}")
    _check(dict(ex.trace_counts) == warm and st.bucket_misses == 0,
           f"phase 1 compiled while serving: {warm} -> "
           f"{dict(ex.trace_counts)}, {st.bucket_misses} new buckets")

    # prefill correctness against the float32 dense reference: every
    # reading is printed before any check fails
    t0 = time.perf_counter()
    refs = _reference_logits(params, cfg,
                             [tokens[rid] for rid in reference_rids])
    first = {r.rid: r.first_token for r in results}
    bad = []
    for rid, ref in zip(reference_rids, refs):
        got = np.asarray(lm_head(params, jnp.asarray(h_last[rid])[None],
                                 cfg)[0], np.float32)
        err = float(np.max(np.abs(got - ref)))
        scale = float(np.max(np.abs(ref)))
        top2 = np.sort(ref)[-2:]
        print(f"  logits rid={rid} (len {len(tokens[rid])}): max abs err "
              f"{err:.4g}, max rel err {err / scale:.4g} of max|ref| "
              f"{scale:.4g} (tolerance {LOGIT_RTOL}: bf16 activations vs "
              f"the float32 reference); argmax {int(got.argmax())} vs "
              f"reference {int(ref.argmax())} (reference top-2 margin "
              f"{(top2[1] - top2[0]) / scale:.4g} of max|ref|), engine "
              f"first_token {first[rid]}")
        if not np.all(np.isfinite(got)):
            bad.append(f"rid {rid}: non-finite logits")
        if err > LOGIT_RTOL * scale:
            bad.append(f"rid {rid}: logits off the float32 reference by "
                       f"{err / scale:.4g} of max|ref|")
        if not int(got.argmax()) == int(ref.argmax()) == first[rid]:
            bad.append(f"rid {rid}: argmax {int(got.argmax())}, reference "
                       f"{int(ref.argmax())}, first_token {first[rid]}")
    print(f"float32 reference on the host CPU: "
          f"{time.perf_counter() - t0:.1f}s")
    _check(not bad, "; ".join(bad))
    del ex, engine

    # ---------------------------------------------------------- phase 2 --
    ex = DisaggregatedExecutor(params, cfg, D=D, E=E, emit_kv=True)
    shapes = sorted({(1, _pad_bucket(n)) for n in pd_lengths})
    t0 = time.perf_counter()
    ex.prewarm_buckets(max_rows)
    ex.prewarm_batches(shapes)
    t_pre = time.perf_counter() - t0
    clock = TraceClock(speed=1.0)
    rt = DecodeExecutor(params, cfg, slots=len(pd_lengths),
                        max_len=decode_max_len, clock=clock.now)
    t0 = time.perf_counter()
    rt.prewarm()
    t_dec = time.perf_counter() - t0
    print(f"phase 2 compile: emit_kv prefill steps {t_pre:.1f}s "
          f"(batch shapes {shapes}), decode step {t_dec:.1f}s")
    warm = dict(ex.trace_counts)
    pre = ExecutorEngine(ex, clock=clock, batcher=one_per_batch(),
                         keep_kv=True)
    orch = PDOrchestrator([pre], [ExecDecodeEngine(rt)], hw=V5E)
    reqs = [Request(rid=100 + i, arrival=0.05 * i, length=int(n),
                    out_len=out_len) for i, n in enumerate(pd_lengths)]
    t0 = time.perf_counter()
    for r in reqs:
        orch.submit(r, rng.integers(0, cfg.vocab_size, r.length,
                                    dtype=np.int32))
    results = []
    while len(results) < len(reqs) and time.perf_counter() - t0 < 600:
        results += orch.poll()
        time.sleep(0.005)
    results += orch.drain(timeout=60)
    wall = time.perf_counter() - t0
    pst = pre.stats()
    orch.close()
    for r in sorted(results, key=lambda x: x.rid):
        print(f"  pd rid={r.rid} len={r.length} status={r.status} "
              f"retries={r.retries} tokens_out={r.tokens_out} "
              f"token_times={len(r.token_times or [])}")
    statuses = {s: sum(r.status == s for r in results)
                for s in sorted({r.status for r in results})}
    print(f"phase 2: {len(results)}/{len(reqs)} requests, statuses "
          f"{statuses}, kv handoffs {orch.kv_log.count} "
          f"({orch.kv_log.bytes / 1e6:.2f} MB), decode_step traces "
          f"{rt.trace_counts['decode_step']}, failovers {pst.failovers}, "
          f"{wall:.1f}s wall")
    _check(len(results) == len(reqs), "phase 2: missing results")
    _check(all(r.status == "ok" and r.retries == 0
               and r.tokens_out == out_len
               and len(r.token_times or []) == out_len for r in results),
           "phase 2: a request did not end ok with out_len tokens")
    _check(orch.kv_log.count >= 1, "phase 2: no KV handoff")
    _check(rt.trace_counts["decode_step"] == 1,
           f"decode_step traced {rt.trace_counts['decode_step']} times")
    _check(pst.failovers == 0 and pst.hedges_issued == 0 and not ex.errors,
           f"phase 2: failovers={pst.failovers} errors={ex.errors}")
    _check(dict(ex.trace_counts) == warm,
           f"phase 2 compiled while serving: {warm} -> "
           f"{dict(ex.trace_counts)}")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"device peak_bytes_in_use: {stats['peak_bytes_in_use']} "
              f"({_gb(stats['peak_bytes_in_use'])} of "
              f"{_gb(stats.get('bytes_limit', 0))})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax sees {devs[0].platform!r}); "
              f"this check runs only on a TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch import serve

    print(f"compile cache: {serve.configure_compile_cache()}")
    full = serve.model_config("qwen3_moe_235b_a22b")
    cfg = serve.model_config("qwen3_moe_235b_a22b", layers=1)
    print(f"model {cfg.name} at published widths: d_model {cfg.d_model}, "
          f"{cfg.num_heads} query / {cfg.num_kv_heads} KV heads x "
          f"{cfg.head_dim} (qk_norm={cfg.qk_norm}), {cfg.num_experts} "
          f"routed experts top-{cfg.top_k} of d_ff {cfg.moe_d_ff}, vocab "
          f"{cfg.vocab_size}, {jax.numpy.dtype(cfg.dtype).name} weights; "
          f"depth cut {full.num_layers} -> {cfg.num_layers} layer (every "
          f"layer is MoE: one layer is one whole period)")
    t0 = time.perf_counter()
    run(cfg, seed=args.seed)
    print(f"chip smoke passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
