"""The program's own spans in a profiler trace, against the device's ops.

The served path opens a `jax.profiler.TraceAnnotation` named `asap.*` around
each host stage (`src/repro/core/spans.py` holds the table), so its spans
sit in the trace's host events on the same clock as the device's ops.  This
module works on `tracing.extract`'s output.  A span whose name ends in
`_wait` is a wait; every other is work.  The program's spans do not nest on
a thread, so a span's time in the window is its self time.

    python3 -m chipbench.spans [trace dir]

prints, for the newest trace under the directory (default: the harness's
own, `.chipbench/trace`), the program spans with the most time in the
window and the window's idle time by program span, as JSON.
"""
from __future__ import annotations

import collections
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

from chipbench import tracing

PREFIX = "asap."
WAIT_SUFFIX = "_wait"
NO_SPAN = "no program span"

Interval = Tuple[int, int]


def is_wait(name: str) -> bool:
    return name.endswith(WAIT_SUFFIX)


def program_spans(ev: Dict[str, Any]) -> List[Tuple[str, int, int]]:
    """(name, start, end) of every `asap.*` span on any host thread."""
    return [(name, s, s + d) for _, name, s, d in ev["host"]
            if name.startswith(PREFIX)]


def _measure(ivs: List[Interval]) -> int:
    return sum(e - s for s, e in ivs)


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle(ev: Dict[str, Any], plane: str, t0: int, t1: int) -> List[Interval]:
    """The intervals of [t0, t1) in which no op runs on `plane`."""
    busy = tracing.union(tracing.clip(
        [(s, s + d) for _, s, d, _ in ev["device"][plane]], t0, t1))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_host_path_share(ev: Dict[str, Any]) -> Optional[float]:
    """100 x the window's time in which the device is idle and some thread
    is inside an `asap.*` work span, over the window, averaged over the
    device planes; None for a trace that holds no program span."""
    found = program_spans(ev)
    if not found:
        return None
    t0, t1 = tracing.window(ev)
    planes = sorted(ev["device"])
    if not planes:
        raise ValueError("the trace holds no device op events")
    work = tracing.union(tracing.clip(
        [(s, e) for n, s, e in found if not is_wait(n)], t0, t1))
    covered = sum(_measure(_intersect(idle(ev, p, t0, t1), work))
                  for p in planes)
    return 100.0 * covered / len(planes) / (t1 - t0)


def span_times(ev: Dict[str, Any], top: int = 10) -> List[list]:
    """The `top` program spans by their summed time in the window, s."""
    t0, t1 = tracing.window(ev)
    total: Dict[str, float] = collections.Counter()
    for name, s, e in program_spans(ev):
        total[name] += max(0, min(e, t1) - max(s, t0)) / 1e9
    return [[n, v] for n, v in
            sorted(total.items(), key=lambda x: -x[1])[:top]]


def _attribute(marks: List[tuple], gaps: List[Interval]
               ) -> Dict[str, float]:
    """Nanoseconds of `gaps` by what covers each instant: a harness span,
    else the program's work spans (split evenly among those running at
    once), else its wait spans (likewise), else NO_SPAN.  `marks` are
    (time, +1 or -1, tier, name), tier 0 harness, 1 work, 2 wait."""
    out: Dict[str, float] = collections.Counter()
    events = sorted(marks + [(s, 1, 3, "") for s, _ in gaps]
                    + [(e, -1, 3, "") for _, e in gaps])
    active = [collections.Counter() for _ in range(4)]
    for (t, step, tier, name), nxt in zip(events, events[1:]):
        active[tier][name] += step
        if not active[3][""] or nxt[0] <= t:
            continue
        seg = nxt[0] - t
        for names in active[:3]:
            live = [n for n, c in names.items() if c > 0]
            if live:
                for n in live:
                    out[n] += seg / len(live)
                break
        else:
            out[NO_SPAN] += seg
    return out


def idle_by_span(ev: Dict[str, Any], top: int = 10) -> List[list]:
    """The window's idle device time, s, averaged over the device planes,
    by what the host was doing in it (see `_attribute`); gaps under
    tracing.SHORT_GAP_NS go under one label.  The `top` labels."""
    t0, t1 = tracing.window(ev)
    planes = sorted(ev["device"])
    marks = []
    for _, name, s, d in ev["host"]:
        if name.startswith(tracing.HARNESS_PREFIX):
            tier = 0 if name != tracing.WINDOW_SPAN else None
        elif name.startswith(PREFIX):
            tier = 2 if is_wait(name) else 1
        else:
            tier = None
        if tier is not None:
            marks += [(s, 1, tier, name), (s + d, -1, tier, name)]
    total: Dict[str, float] = collections.Counter()
    for p in planes:
        gaps = idle(ev, p, t0, t1)
        short = [g for g in gaps if g[1] - g[0] < tracing.SHORT_GAP_NS]
        if short:
            total[tracing.SHORT_GAP] += _measure(short) / 1e9 / len(planes)
        longer = [g for g in gaps if g[1] - g[0] >= tracing.SHORT_GAP_NS]
        for name, ns in _attribute(marks, longer).items():
            total[name] += ns / 1e9 / len(planes)
    return [[n, v] for n, v in
            sorted(total.items(), key=lambda x: -x[1])[:top]]


def load(trace_dir: str) -> Dict[str, Any]:
    """The events of the newest trace under `trace_dir`."""
    return tracing.extract(tracing.newest_xplane(trace_dir))


def main(argv: List[str]) -> int:
    from chipbench import bench

    ev = load(argv[0] if argv else bench.TRACE_DIR)
    print(json.dumps({
        "idle_host_path_share": idle_host_path_share(ev),
        "program_spans": span_times(ev),
        "idle_by_span": idle_by_span(ev)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
