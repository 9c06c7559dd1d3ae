"""Profiler traces: capture, extraction and the reduction to numbers.

`extract` turns a JAX profiler trace (`*.xplane.pb`) into plain events:

    {"device": {plane: [[name, start_ns, dur_ns, module], ...]},
     "host": [[thread, name, start_ns, dur_ns], ...]}

`device` holds each accelerator plane's op line ("XLA Ops").  On a TPU an
op event is named by its HLO text (`%super_gmm.5 = f32[32,2048,4096]{...}
custom-call(...), ...`) and carries no module or JAX-name stat; `name` is
the instruction's name and its result shape (`super_gmm.5
f32[32,2048,4096]`), and `module` the program run on the plane's "XLA
Modules" line around it (`jit_step(<fingerprint>)`).  `host` holds every
host thread's events.  `reduce` works on that form alone, so it is tested
on a slice of a trace recorded on the chip (tests/chipbench/fixtures).

The window is the harness's own host span `harness.window`, opened after
the profiler starts and closed before it stops.  Busy time is the union of
the intervals in which an op runs on a device, averaged over the chips
used; an idle gap is time inside the window with no op running, labelled
by the harness span that covers its middle, else by the host event that
overlaps it most, else "unattributed".
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Any, Dict, List, Sequence, Tuple

WINDOW_SPAN = "harness.window"
HARNESS_PREFIX = "harness."
_OP_LINE = "XLA Ops"
_MODULE_LINE = "XLA Modules"
_LAYOUT = re.compile(r"\{[^{}]*\}")
SHORT_GAP_NS = 50_000  # shorter gaps are summed under one label
SHORT_GAP = "between ops (gaps < 50us)"


def start(log_dir: str) -> None:
    """Start the profiler with the Python tracer off (it would time every
    Python call of a host-bound server)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def newest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """`name shape` of an op event named by its HLO text, layouts dropped
    (`%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)` -> `fusion.3
    bf16[8,128]`); a name that is not HLO text is kept as it is."""
    lhs, eq, rhs = hlo.partition(" = ")
    if not eq:
        return hlo
    depth, end = 0, len(rhs)
    for i, ch in enumerate(rhs):
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            end = i
            break
    return f"{lhs.lstrip('%')} {_LAYOUT.sub('', rhs[:end])}"


def _modules(line) -> Tuple[List[int], List[Tuple[int, str]]]:
    spans = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                    ev.name) for ev in line.events)
    return [s for s, _, _ in spans], [(e, n) for _, e, n in spans]


def _module_at(starts, ends, t: int) -> str:
    i = bisect.bisect_right(starts, t) - 1
    return ends[i][1] if i >= 0 and t < ends[i][0] else ""


def extract(path: str) -> Dict[str, Any]:
    """Device op events and host events of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if _OP_LINE not in lines:
                continue
            starts, ends = _modules(lines[_MODULE_LINE]) \
                if _MODULE_LINE in lines else ([], [])
            device[plane.name] = [
                [op_name(ev.name), int(ev.start_ns), int(ev.duration_ns),
                 _module_at(starts, ends, int(ev.start_ns))]
                for ev in lines[_OP_LINE].events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend([line.name, ev.name, int(ev.start_ns),
                             int(ev.duration_ns)]
                            for ev in line.events if ev.duration_ns > 0)
    return {"device": device, "host": host}


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, t0: int, t1: int) -> List[Tuple[int, int]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def window(ev: Dict[str, Any]) -> Tuple[int, int]:
    """[t0, t1) of the harness's window span, in trace nanoseconds."""
    spans = [(s, s + d) for _, name, s, d in ev["host"]
             if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return max(spans, key=lambda x: x[1] - x[0])


def _overlap(a: Tuple[int, int], b: Tuple[int, int]) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def _labeller(harness: list, host: list):
    """label(gap): the harness span over its middle, else the host event
    that overlaps it most, else "unattributed"."""
    import numpy as np

    hs = np.array([s for _, s, _ in harness], np.int64)
    he = np.array([e for _, _, e in harness], np.int64)
    names = [n for n, _, _ in host]
    s_ = np.array([s for _, s, _ in host], np.int64)
    e_ = np.array([e for _, _, e in host], np.int64)

    def label(gap: Tuple[int, int]) -> str:
        if gap[1] - gap[0] < SHORT_GAP_NS:
            return SHORT_GAP
        mid = (gap[0] + gap[1]) // 2
        hit = np.nonzero((hs <= mid) & (mid < he))[0]
        if hit.size:
            return harness[int(hit[0])][0]
        if not names:
            return "unattributed"
        o = np.minimum(e_, gap[1]) - np.maximum(s_, gap[0])
        i = int(np.argmax(o))
        return names[i] if o[i] > 0 else "unattributed"

    return label


def reduce(ev: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Busy and window seconds, device seconds of every op (keyed
    `<module>/<name>`), the `top` ops that took most time and the idle
    time by what the host was doing."""
    t0, t1 = window(ev)
    planes = sorted(ev["device"])
    if not planes:
        raise ValueError("the trace holds no device op events")
    busy = 0.0
    per_op: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[int, int]] = []
    for plane in planes:
        ops = [e for e in ev["device"][plane] if e[1] + e[2] > t0
               and e[1] < t1]
        ivs = union(clip([(e[1], e[1] + e[2]) for e in ops], t0, t1))
        busy += sum(e - s for s, e in ivs) / 1e9
        edges = [t0] + [x for iv in ivs for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for n, s, d, mod in ops:
            key = f"{mod}/{n}" if mod else n
            per_op[key] += _overlap((s, s + d), (t0, t1)) / 1e9
    n_planes = len(planes)
    harness = [(name, s, s + d) for _, name, s, d in ev["host"]
               if name.startswith(HARNESS_PREFIX) and name != WINDOW_SPAN]
    host = [(name, s, s + d) for _, name, s, d in ev["host"]
            if not name.startswith(HARNESS_PREFIX) and d < t1 - t0]
    label = _labeller(harness, host)
    idle: Dict[str, float] = collections.Counter()
    for g in gaps:
        idle[label(g)] += (g[1] - g[0]) / 1e9 / n_planes
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy / n_planes,
        "ops": {n: v / n_planes for n, v in per_op.items()},
        "device_ops": [[n, v / n_planes] for n, v in
                       sorted(per_op.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, v] for n, v in
                      sorted(idle.items(), key=lambda x: -x[1])[:top]],
    }
