import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Perf-iteration driver: lower one cell with a set of optimization knobs,
report the three roofline terms + deltas vs baseline, append to
results/perf_iterations.jsonl. Used by the EXPERIMENTS.md §Perf loop.

  PYTHONPATH=src python -m repro.launch.hillclimb --arch deepseek_v32 \
      --shape prefill_32k --opts attn_dp_constraint,inner_remat \
      --label "H1+H2" [--breakdown]
"""
import argparse
import json

import jax

from repro.launch.dryrun import PEAK_FLOPS, HBM_BW, LINK_BW, run_cell
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--opts", default="")
    ap.add_argument("--label", default="")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--breakdown", action="store_true")
    ap.add_argument("--out", default="results/perf_iterations.jsonl")
    args = ap.parse_args()

    opts = {}
    for item in args.opts.split(","):
        item = item.strip()
        if not item:
            continue
        if "=" in item:
            k, v = item.split("=", 1)
            if v.lower() in ("true", "false"):
                opts[k] = v.lower() == "true"
            else:
                try:
                    opts[k] = int(v)
                except ValueError:
                    opts[k] = v
        else:
            opts[item] = True

    rec = run_cell(args.arch, args.shape, args.multi_pod, opts=opts)
    rec["label"] = args.label or ",".join(opts) or "baseline"
    if rec.get("status") != "ok":
        print(json.dumps(rec)[:2000])
        raise SystemExit(1)
    brief = dict(label=rec["label"], arch=args.arch, shape=args.shape,
                 compute_s=round(rec["compute_s"], 3),
                 memory_s=round(rec["memory_s"], 3),
                 collective_s=round(rec["collective_s"], 3),
                 dominant=rec["dominant"],
                 useful=round(rec["useful_flops_ratio"], 4),
                 peak_hbm_gb=round(rec["mem"]["peak_hbm_gb"], 1),
                 compile_s=rec["compile_s"])
    print(json.dumps(brief))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")

    if args.breakdown:
        from repro.launch.dryrun import build_cell
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        cfg, fn, cell_args, in_sh, meta = build_cell(args.arch, args.shape,
                                                     mesh, opts)
        with jax.set_mesh(mesh):
            hlo = jax.jit(fn, in_shardings=in_sh).lower(
                *cell_args).compile().as_text()
        hc = analyze(hlo, breakdown=True, top_k=8)
        print("\n-- top dots (flops) --")
        for f_, d in hc.top_dots:
            print(f"{f_/PEAK_FLOPS:9.3f}s  {d[:120]}")
        print("-- top memory --")
        for b, d in hc.top_memory:
            print(f"{b/HBM_BW:9.3f}s  {d[:120]}")
        print("-- top collectives --")
        for b, d in hc.top_collectives:
            print(f"{b/LINK_BW:9.3f}s  {d[:120]}")


if __name__ == "__main__":
    main()
