#!/usr/bin/env python3
"""Readings that a cell's `correct` limit is set from.

    python3 chipbench/control.py --workload <cell> --seeds 101,102,... \\
        --control-seeds 3 --seconds 30

One process on one chip.  For each seed: the weights and the schedule of
that seed, the program served at the cell's own load for `--seconds` (as
many requests as a run compares), and the run's own comparison
(`bench.check`).  For the first `--control-seeds` seeds, the control goes
through the same comparison: the configuration's reference module computed
in the precision below the served one (float8 e4m3 for bfloat16) on the
same prompts, its final hidden state and first token put in place of each
served one.  One JSON line per seed, with each compared number and the
per-prompt readings it was taken from.

Each limit sits above the largest program reading (the lower reading) and
below the smallest control reading (the upper reading); PERF.md gives both
and the limit.  The benchmark's own runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_verdict(ref, model, weights, reqs, tokens, limits):
    """`bench.check` with the control of the reference module `ref` in the
    program's place: each answered request's final hidden states are the
    control's, and its first token the one the control's head puts first.
    Returns the verdict and the requests as the control answered them."""
    import dataclasses

    import numpy as np

    from chipbench import bench

    swapped = []
    for r in reqs:
        if r.first_token is None:
            swapped.append(dataclasses.replace(r))
            continue
        h = ref.hidden_states(model, weights, tokens[r.rid], control=True)
        tok = int(np.argmax(ref.head_logits(model, weights, h[-1],
                                            control=True)))
        swapped.append(dataclasses.replace(r, hidden=h, first_token=tok))
    return bench.check(ref, model, weights, swapped, tokens, limits), swapped


def readings(v, reqs):
    """The compared numbers of a verdict, and quantiles of the per-position
    and per-prompt readings they were taken from."""
    import numpy as np

    reqs = [r for r in reqs if r.pos_err is not None]
    pos = np.concatenate([r.pos_err for r in reqs])
    last = np.array([r.pos_err[-1] for r in reqs])
    q = (50, 90, 99, 99.9, 100)
    return {"correct": v["correct"],
            **{k: c["value"] for k, c in v["checks"].items()},
            "positions": int(pos.size),
            "pos_err_q": {str(x): float(np.percentile(pos, x)) for x in q},
            "pos_err_share_over": {str(t): float(np.mean(pos > t))
                                   for t in (0.005, 0.01, 0.02, 0.05)},
            "last_err_q": {str(x): float(np.percentile(last, x)) for x in q},
            "head_gaps_nonzero": sum(r.head_gap > 0 for r in reqs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from chipbench import bench, manifest, system, traffic

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    cell = manifest.load_cell(args.workload, ROOT)
    m, ref = cell.config["model"], cell.reference
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        arr = traffic.schedule(cell.traffic, args.seconds)
        toks = traffic.prompt_tokens(arr, m["vocab_size"], seed)
        w = ref.weights(m, seed)
        server = system.Server(cell.config, w, [a.length for a in arr],
                               log=lambda _: None)
        reqs = bench.drive(server, arr, toks, args.seconds,
                           lambda _: contextlib.nullcontext())
        server.close()
        del server
        limits = cell.config["correct"]
        v = bench.check(ref, m, w, reqs, toks, limits)
        line = {"seed": seed, "compared": v["compared"],
                "program": readings(v, reqs)}
        if i < args.control_seeds:
            line["control"] = readings(*control_verdict(ref, m, w, reqs,
                                                        toks, limits))
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        del w
    return 0


if __name__ == "__main__":
    sys.exit(main())
