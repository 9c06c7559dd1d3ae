"""Capacity/block autotuning sweep for the MoE super kernel (ISSUE 10).

For each model geometry (n_experts, d_model, d_ff, dtype) x capacity bucket
C, measures every candidate (block_c, block_n, block_k) grid blocking for the
two GMM shapes `super_moe_ffn` launches — up/gate ([E,C,d] @ [E,d,f]) and
down ([E,C,f] @ [E,f,d]) — and persists the winners as a versioned JSON
`repro.kernels.super_gmm.tuning.TuningTable`.  The two GMMs are swept
independently: they are separate Pallas launches with independent grids, so
the best blocking for one says nothing about the other.

Usage:

  PYTHONPATH=src python -m benchmarks.tune_superkernel [--quick]
      [--out results/superkernel_tuning.json] [--buckets 8,16,32]

Serve with the result via `serve.py --tuning-table <path>` or
`ASAP_TUNING_TABLE=<path>`.  The kernels run compiled on a TPU and in the
Pallas interpreter on the CPU (`repro.kernels.interpret`); the table records
`meta.platform`, `meta.device_kind` and `meta.interpret`, and a TPU run
refuses a table that was not swept on the same TPU kind.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import fmt_table
from repro.kernels.interpret import resolve_interpret
from repro.kernels.super_gmm import tuning
from repro.kernels.super_gmm.super_gmm import super_gmm

OUT = os.path.join(os.path.dirname(__file__), "..", "results",
                   "superkernel_tuning.json")

# geometries matching the executor benchmarks' smoke models: (E, d_model,
# d_ff, layers).  The full sweep adds the wider-FFN variant used by the
# hot-path figure; --quick keeps one geometry so CI stays fast.
GEOMETRIES = [
    dict(n_experts=8, d_model=128, d_ff=64, num_layers=3),
    dict(n_experts=8, d_model=128, d_ff=256, num_layers=3),
]


def _time_blocking(lid, w, xb, blocks, reps: int) -> float:
    """Best-of-`reps` microseconds for one jitted super_gmm launch with the
    given (block_c, block_n, block_k); compile time excluded by a warmup
    call."""
    bc, bn, bk = blocks
    def launch():
        return super_gmm(lid, w, xb, block_c=bc, block_n=bn, block_k=bk)
    launch().block_until_ready()  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        launch().block_until_ready()
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def _sweep_gmm(E, C, K, N, num_layers, limit, reps):
    """Winner (blocks, us) over the candidate grid for one [E,C,K]@[E,K,N]
    GMM shape (weights stacked over `num_layers`, layer id runtime data —
    the same launch signature the executor issues)."""
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (num_layers, E, K, N), jnp.float32)
    xb = jax.random.normal(key, (E, C, K), jnp.float32)
    lid = jnp.asarray([0], jnp.int32)
    best, best_us = None, float("inf")
    for blocks in tuning.candidate_blockings(C, N, K, limit=limit):
        us = _time_blocking(lid, w, xb, blocks, reps)
        if us < best_us:
            best, best_us = blocks, us
    return best, best_us


def run(quick: bool = False, buckets=None, out: str = OUT) -> dict:
    geos = GEOMETRIES[:1] if quick else GEOMETRIES
    buckets = buckets or ([8, 16] if quick else [8, 16, 32, 64])
    limit = 6 if quick else 12
    reps = 2 if quick else 3

    dev = jax.devices()[0]
    table = tuning.TuningTable(meta=dict(
        platform=dev.platform, device_kind=dev.device_kind,
        interpret=resolve_interpret(),
        buckets=list(buckets), candidates_per_gmm=limit))
    rows = []
    for g in geos:
        E, d, f, L = (g["n_experts"], g["d_model"], g["d_ff"],
                      g["num_layers"])
        key = tuning.config_key(E, d, f, jnp.float32)
        for C in buckets:
            up, up_us = _sweep_gmm(E, C, d, f, L, limit, reps)
            down, down_us = _sweep_gmm(E, C, f, d, L, limit, reps)
            table.put(key, C, up, down, us=up_us + down_us)
            rows.append((key, C, str(up), f"{up_us:.0f}", str(down),
                         f"{down_us:.0f}"))
    table.save(out)
    return dict(table=table, rows=rows, out=out)


def main(quick: bool = False, buckets=None, out: str = OUT):
    r = run(quick, buckets, out)
    print("== Super-kernel block autotuning sweep ==")
    print(fmt_table(r["rows"], ["geometry", "C", "up blocks", "up us",
                                "down blocks", "down us"]))
    print(f"wrote {os.path.relpath(r['out'])}")
    # round-trip sanity: the persisted table must reproduce every winner
    loaded = tuning.TuningTable.load(r["out"])
    for key, C, up, _, down, _ in r["rows"]:
        got = loaded.lookup(key, int(C))
        assert got is not None and (str(got[0]), str(got[1])) == (up, down), \
            f"table round-trip mismatch at {key} C={C}"
    return r


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="one geometry, 2 buckets, truncated candidate list")
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--buckets", default=None,
                    help="comma-separated capacity buckets (powers of two)")
    args = ap.parse_args()
    bl = [int(b) for b in args.buckets.split(",")] if args.buckets else None
    main(quick=args.quick, buckets=bl, out=args.out)
