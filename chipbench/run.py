#!/usr/bin/env python3
"""The chip benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs one cell of BENCHMARK.json as one process on one chip and prints, as
the last line of standard output, one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics with --trace 0, its
per-layer metrics with --trace 1), `device`, with --trace 1 `breakdown`,
and last `checks`, each compared number beside its limit.  Those numbers
are also the last lines of standard error.  Off a TPU, or with fewer chips
than the cell asks for, it exits 3 and prints no result.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # JAX's persistent compilation cache, at a fixed path in the checkout
    # (the path is part of every cache key); set before JAX is imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from chipbench import bench, manifest

    cell = manifest.load_cell(args.workload, ROOT)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX sees {len(devs)} {devs[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    from repro.launch import serve

    print(f"compile cache: {serve.configure_compile_cache()}", flush=True)
    out = bench.measure(cell, args.seed, args.seconds, bool(args.trace),
                        T_PROCESS)
    bench.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
