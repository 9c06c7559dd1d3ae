"""One run of one cell: set-up, the measured window, the check, the metrics.

    setup     weights from the seed (the configuration's reference module,
              one jitted call), the server built and
              prewarmed for the cell's own prompt shapes, one warm-up
              request per shape; `setup_s` runs from process start to here
    window    the schedule offered open-loop for `seconds`: each request is
              submitted when due and timed from when it was due to when its
              first token is on the host; after the close every request is
              waited for, up to DRAIN_S
    check     once the device's peak memory is read and the server freed:
              the configuration's float32 reference module over every
              finished prompt: how far the
              served final hidden states lie from the reference's, and how
              far below the best logit of the head (in float32, on the
              served state at the last position) the served token lies
    metrics   each of the cell's metrics from its reader
"""
from __future__ import annotations

import dataclasses
import math
import os
import shutil
import sys
import time
import types
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from chipbench import counts, manifest, tracing, traffic

DRAIN_S = 60.0  # a request unanswered this long after the close never came
TRACE_DIR = os.path.join(manifest.ROOT, ".chipbench", "trace")


@dataclasses.dataclass
class Request:
    rid: int
    length: int
    due: float  # seconds after the window opened
    late: float = 0.0  # seconds the generator submitted it after `due`
    done: Optional[float] = None  # first token on the host, s after open
    status: Optional[str] = None
    queue_s: Optional[float] = None  # the program's own queue span
    first_token: Optional[int] = None
    hidden: Optional[np.ndarray] = None  # served final hidden states [n, d]
    pos_err: Optional[np.ndarray] = None  # per position: |h - ref| / |ref|
    err_sq: Optional[float] = None  # sum over positions of |h - ref|^2
    ref_sq: Optional[float] = None  # sum over positions of |ref|^2
    head_gap: Optional[float] = None  # best logit minus the served token's

    @property
    def ttft(self) -> float:
        """Seconds from due to first token; inf for one that never came."""
        return math.inf if self.done is None else self.done - self.due


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    model: Dict[str, Any]
    peak: Dict[str, Any]
    setup_s: float
    requests: List[Request]
    window_s: float  # open to close
    counters: Dict[str, float]  # program counters, open to last answer
    experts_per_launch: int
    reduced: Optional[Dict[str, Any]] = None  # trace reduction
    reference: Optional[types.ModuleType] = None  # the reference module
    weights: Any = None  # the weights it made
    tokens: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)

    def finished(self) -> List[Request]:
        return [r for r in self.requests if r.done is not None]

    def ttft_ms(self) -> np.ndarray:
        return np.array([r.ttft for r in self.requests]) * 1e3


def percentile(values: np.ndarray, q: float) -> Optional[float]:
    """numpy's linear percentile; None where it would land on a miss."""
    v = float(np.percentile(values, q))
    return v if math.isfinite(v) else None


class CompileCounter:
    """Counts the programs JAX lowers and compiles while `armed`."""

    def __init__(self):
        import jax

        self.lowered = self.compiled = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if not self.armed:
            return
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


def _log(msg: str) -> None:
    print(msg, flush=True)


def drive(server, arrivals: List[traffic.Arrival],
          tokens: Dict[int, np.ndarray], seconds: float,
          annotate: Callable[[str], Any]) -> List[Request]:
    """Offer the schedule open-loop, hold to the window's close, then wait
    for every answer up to DRAIN_S.  Returns the requests with host times
    relative to the window's open."""
    reqs = [Request(rid=a.rid, length=a.length, due=a.due) for a in arrivals]
    t_open = time.perf_counter()
    for r in reqs:
        due = t_open + r.due
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        r.late = time.perf_counter() - due
        with annotate("harness.submit"):
            server.submit(r.rid, tokens[r.rid], due)
    rest = t_open + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    server.wait(t_open + seconds + DRAIN_S)
    for r in reqs:
        t = server.done_at.get(r.rid)
        res = server.result(r.rid)
        if t is not None and res is not None:
            r.done = t - t_open
            r.status, r.queue_s = res["status"], res["queue_s"]
            r.first_token, r.hidden = res["first_token"], res["hidden"]
    return reqs


def check(ref: types.ModuleType, model: Dict[str, Any], w,
          reqs: List[Request], tokens: Dict[int, np.ndarray],
          limits: Dict[str, float]) -> Dict[str, Any]:
    """The comparison that decides `correct`, against the reference module
    `ref`: every request due in the window has an answer, ok, and

      hidden_err  sqrt(sum |h - h_ref|^2 / sum |h_ref|^2) over every
                  position of every answered prompt, h the final hidden
                  states the program handed to its head and h_ref the
                  reference's: every layer up to the head
      head_gap    max(logits) - logits[served token], logits the float32
                  head on h at the prompt's last position: the head and
                  the choice of the token; compared at its widest
    """
    for r in reqs:
        if r.done is not None and r.first_token is not None \
                and r.hidden is not None:
            h = np.asarray(r.hidden, np.float32)
            h_ref = ref.hidden_states(model, w, tokens[r.rid])
            d = np.sum((h - h_ref) ** 2, axis=-1)
            nr = np.sum(h_ref ** 2, axis=-1)
            r.pos_err = np.sqrt(d / nr)
            r.err_sq, r.ref_sq = float(d.sum()), float(nr.sum())
            logits = ref.head_logits(model, w, h[-1])
            r.head_gap = float(logits.max() - logits[r.first_token])
    done = [r for r in reqs if r.err_sq is not None]
    unanswered = sum(r.done is None or r.first_token is None
                     or r.hidden is None for r in reqs)
    not_ok = sum(r.done is not None and r.status != "ok" for r in reqs)
    err = math.sqrt(sum(r.err_sq for r in done)
                    / sum(r.ref_sq for r in done)) if done else math.inf
    gap = max(r.head_gap for r in done) if done else math.inf
    return {
        "checks": {
            "hidden_err": {"value": err, "limit": limits["hidden_err"]},
            "head_gap_max": {"value": gap, "limit": limits["head_gap_max"]},
            "unanswered": {"value": unanswered, "limit": 0},
            "not_ok": {"value": not_ok, "limit": 0},
        },
        "compared": len(done),
        "correct": bool(done) and err <= limits["hidden_err"]
        and gap <= limits["head_gap_max"] and unanswered == 0
        and not_ok == 0,
    }


def measure(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
            t_process: float) -> Dict[str, Any]:
    """One run on the default device; returns the result line's fields."""
    import jax

    from chipbench import system

    dev = jax.devices()[0]
    peak = counts.peaks(dev.device_kind)
    model = cell.config["model"]
    ref = cell.reference
    arrivals = traffic.schedule(cell.traffic, seconds)
    tokens = traffic.prompt_tokens(arrivals, model["vocab_size"], seed)
    lengths = [a.length for a in arrivals]
    _log(f"schedule: {len(arrivals)} requests in {seconds:g}s "
         f"({len(arrivals) / seconds:.3f} req/s), prompt lengths "
         f"{min(lengths)}-{max(lengths)} (mean {np.mean(lengths):.1f})")
    t0 = time.perf_counter()
    w = ref.weights(model, seed)
    jax.block_until_ready(w)
    _log(f"weights from seed {seed}: {time.perf_counter() - t0:.2f}s")
    server = system.Server(cell.config, w, lengths, log=_log)
    compiles = CompileCounter()
    setup_s = time.perf_counter() - t_process
    _log(f"setup_s {setup_s:.3f}")

    annotate = jax.profiler.TraceAnnotation
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracing.start(TRACE_DIR)
    before = server.counters()
    programs = server.compiled_programs()
    compiles.armed = True
    with annotate(tracing.WINDOW_SPAN):
        reqs = drive(server, arrivals, tokens, seconds, annotate)
    compiles.armed = False
    if trace:
        tracing.stop()
    after = server.counters()
    new_programs = {k: v - programs.get(k, 0)
                    for k, v in server.compiled_programs().items()
                    if v != programs.get(k, 0)}
    late = np.array([r.late for r in reqs])
    _log(f"generator lateness: median {np.median(late) * 1e3:.3f} ms, "
         f"max {late.max() * 1e3:.3f} ms over {len(reqs)} submissions")
    _log(f"compilations inside the window: {compiles.lowered} programs "
         f"lowered, {compiles.compiled} compiled, executor retraces "
         f"{new_programs or 0}")
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    counters = {k: after[k] - before[k] for k in after}
    experts_per_launch = server.experts_per_launch()
    server.close()
    del server

    t0 = time.perf_counter()
    verdict = check(ref, model, w, reqs, tokens, cell.config["correct"])
    _log(f"reference over {verdict['compared']} prompts: "
         f"{time.perf_counter() - t0:.2f}s")

    reduced = None
    if trace:
        ev = tracing.extract(tracing.newest_xplane(TRACE_DIR))
        reduced = tracing.reduce(ev)
    run = Run(model=model, peak=peak, setup_s=setup_s, requests=reqs,
              window_s=seconds, counters=counters,
              experts_per_launch=experts_per_launch, reduced=reduced,
              reference=ref, weights=w, tokens=tokens)
    metrics = {}
    for m in cell.metrics(trace):
        v = manifest.load_reader(m["name"], cell.root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    out = {"correct": verdict["correct"], "attempted": len(reqs),
           "failed": sum(r.done is None or r.status != "ok" for r in reqs),
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = verdict["checks"]
    return out


def print_result(out: Dict[str, Any]) -> None:
    """Each compared number beside its limit as the last lines on standard
    error, then the result as the last line on standard output."""
    import json

    for c in out["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = None  # JSON has no infinity: no answer to compare
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
