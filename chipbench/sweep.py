#!/usr/bin/env python3
"""Find a configuration's knee: the highest Poisson rate it sustains.

    python3 chipbench/sweep.py --config qwen3-235b-a22b \\
        --traffic <mix> --rates 2,4,6,8 --seconds 20 --seed 7

One process on one chip: the server is built once, then each rate's
schedule (the mix's lengths and arrival process at that rate) runs for
`--seconds` and is drained before the next.  A rate is sustained when the
requests still outstanding at the window's close are no more than at its
middle plus max(2, 5% of the requests): a queue that grows through the
window is past the knee.  One JSON line per rate and the knee at the end.
A cell's rate is a fraction of the knee, written into its traffic file.
The server's host memory grows with the backlog it holds, so near the knee
give one rate per process (`--rates 1.2`), as the knee in PERF.md was found.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RID_STRIDE = 1 << 20


def outstanding(reqs, t: float) -> int:
    return sum(r.due <= t and (r.done is None or r.done > t) for r in reqs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second, ascending")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import contextlib

    import jax
    import numpy as np

    from chipbench import bench, manifest, system, traffic

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    man = manifest.load_manifest(ROOT)
    config = manifest.load_config(man, args.config, ROOT)
    base = manifest.load_traffic(args.traffic, ROOT)
    rates = [float(r) for r in args.rates.split(",")]
    mixes = []
    for i, rate in enumerate(rates):
        mix = copy.deepcopy(base)
        mix["arrivals"]["rate_rps"] = rate
        # one server serves every rate: each rate's rids are its own
        arr = [dataclasses.replace(a, rid=a.rid + i * RID_STRIDE)
               for a in traffic.schedule(mix, args.seconds)]
        mixes.append((rate, arr, traffic.prompt_tokens(
            arr, config["model"]["vocab_size"], args.seed)))
    w = manifest.reference_for(config, ROOT).weights(config["model"],
                                                     args.seed)
    server = system.Server(config, w, [a.length for _, arr, _ in mixes
                                       for a in arr])
    print(f"set-up {time.perf_counter() - T_PROCESS:.1f}s", flush=True)
    knee, misses = None, 0
    for rate, arr, toks in mixes:
        reqs = bench.drive(server, arr, toks, args.seconds,
                           lambda _: contextlib.nullcontext())
        half = outstanding(reqs, args.seconds / 2)
        end = outstanding(reqs, args.seconds)
        sustained = end <= half + max(2, 0.05 * len(reqs))
        ttft = np.array([r.ttft for r in reqs]) * 1e3
        done_in = [r for r in reqs if r.done is not None
                   and r.done <= args.seconds]
        print(json.dumps({
            "rate_rps": rate, "requests": len(reqs),
            "outstanding_mid": half, "outstanding_close": end,
            "sustained": sustained,
            "ttft_p50_ms": bench.percentile(ttft, 50),
            "ttft_p95_ms": bench.percentile(ttft, 95),
            "completed_in_window": len(done_in),
            "prefill_tokens_per_s": sum(r.length for r in done_in)
            / args.seconds}), flush=True)
        if sustained:
            knee, misses = rate, 0
        else:
            misses += 1
            if misses == 2:
                break  # two rates past the knee: the rest only queue
    server.close()
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "knee_rps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
