"""Arithmetic the metric readers share (`chipbench/metrics/<name>.py`).

Each reader is `read(run) -> float | None`; None leaves the metric out of
the result line, as when a run has nothing for it to read.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

from chipbench import counts
from chipbench.bench import Run, percentile

# the super-GMM's Pallas launches: the HLO name of their ops in the trace
# (`<module>/super_gmm.5 f32[32,2048,4096]`)
SUPER_GMM_OPS = re.compile(r"(^|/)super_gmm(\.\d+)? ")


def ttft_percentile(run: Run, q: float) -> Optional[float]:
    """The q-th percentile of TTFT (ms) over every request due in the
    window; one that never came counts beyond every finished one."""
    return percentile(run.ttft_ms(), q)


def real_pairs(run: Run) -> int:
    """Real routed (token, expert) rows that this chip's experts serve for
    every finished prompt, from the reference module's own routing."""
    return sum(run.reference.routed_rows(run.model, run.weights,
                                         run.tokens[r.rid])
               for r in run.finished())


def super_gmm_roofline(run: Run) -> Optional[float]:
    """Least time of the window's super-GMM work over its device time, %.
    A traced run whose executor launched the kernel but whose trace holds
    no op of that name is an error: the name has moved."""
    if run.reduced is None or run.counters["moe_launches"] <= 0:
        return None
    r = run.reduced
    kernel_s = sum(v for n, v in r["ops"].items() if SUPER_GMM_OPS.search(n))
    if kernel_s <= 0:
        raise ValueError(
            f"the executor launched the super-GMM "
            f"{run.counters['moe_launches']:g} times, but no device op in the "
            f"trace has a name matching {SUPER_GMM_OPS.pattern!r}")
    flops, nbytes = counts.super_gmm_work(
        run.model, real_pairs(run), int(run.counters["moe_launches"]),
        run.experts_per_launch)
    least, _ = counts.least_time(flops, nbytes, run.peak)
    return 100.0 * least / kernel_s


def mfu_ttft(run: Run) -> Optional[float]:
    """Model FLOPs of the finished prompts at the bf16 peak, over the sum
    of their TTFTs, %."""
    done = run.finished()
    if not done:
        return None
    flops = sum(run.reference.prompt_flops(run.model, r.length)
                for r in done)
    return 100.0 * flops / run.peak["bf16_flops_per_s"] \
        / sum(r.ttft for r in done)


def queue_ms(run: Run, q: float) -> Optional[float]:
    """The q-th percentile of the program's own queue span, ms."""
    v = np.array([np.inf if r.queue_s is None else r.queue_s
                  for r in run.requests]) * 1e3
    return percentile(v, q)
