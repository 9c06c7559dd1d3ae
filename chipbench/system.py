"""The system under test, built the way a server builds it.

This is the one module that imports the program (`repro`, from the
checkout's `src/`).  It builds the configuration with
`repro.launch.serve.model_config`, serves it through an `ExecutorEngine`
over a `DisaggregatedExecutor` (D attention groups and E MoE devices, all
threads on one chip; the fused path with the compiled Pallas super-GMM), and
hands the harness what it measures from outside: a submit call, the moment
each first token exists on the host, and the program's own counters.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, List, Optional

import numpy as np

# a warm-up request's rid: far above any rid of a schedule
_WARM_RID = 1 << 40


def pad_bucket(n: int) -> int:
    """The (1, S) attention shape the engine pads a prompt of n tokens to
    (the engine's own power-of-two bucket, floor 8)."""
    return 1 << (max(int(n), 8) - 1).bit_length()


# what the program is held to where the configuration file's `model` block
# states nothing: none of these departures from a plain decoder MoE
_UNSTATED = {"num_shared_experts": 0, "tie_embeddings": False,
             "scale_embeddings": False, "act": "silu",
             "nonparametric_norm": False, "qkv_bias": False,
             "logit_softcap": None, "window_size": None}


def _plain(value: Any) -> Any:
    """A config attribute as a file states it: a dtype by its name."""
    return np.dtype(value).name if isinstance(value, (type, np.dtype)) \
        else value


def program_config(config: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: the program's
    `arch` cut to the file's `num_layers`, checked key by key against the
    file's `model` block: the file states what is run.  A `model` key that
    the program's ModelConfig lacks is refused, since the program cannot
    run what it states."""
    from repro.launch import serve

    m, prog = config["model"], config["program"]
    cfg = serve.model_config(prog["arch"], layers=m["num_layers"])
    want = dict(_UNSTATED, **m)
    lacking = [k for k in want if not hasattr(cfg, k)]
    if lacking:
        raise ValueError(f"the program's ModelConfig has no {lacking}: it "
                         f"cannot run what the configuration file states")
    got = {k: _plain(getattr(cfg, k)) for k in want}
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise ValueError(f"the program's {prog['arch']} differs from the "
                         f"configuration file (program, file): {bad}")
    return cfg


def check_layout(weights, cfg) -> None:
    """The benchmark's weights have the program's parameter layout: the
    same tree, shapes and dtypes as its own initializer gives."""
    import jax

    from repro.launch import serve

    want = jax.eval_shape(lambda: serve.init_params(cfg, 0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       weights)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("the benchmark's weights do not have the program's "
                         f"layout:\n{got}\nwant\n{want}")


class Server:
    """The engine and executor, prewarmed for a set of prompt lengths."""

    def __init__(self, config: Dict[str, Any], weights,
                 lengths: Iterable[int], log: Callable[[str], None] = print):
        from repro.core.engine import ExecutorEngine
        from repro.core.executor import DisaggregatedExecutor
        from repro.core.scheduler import LengthAwareBatcher
        from repro.core.trace import TraceClock

        prog = config["program"]
        self.cfg = program_config(config)
        check_layout(weights, self.cfg)
        self.ex = DisaggregatedExecutor(weights, self.cfg,
                                        D=prog["attention_groups"],
                                        E=prog["moe_devices"])
        lengths = sorted(set(int(n) for n in lengths))
        buckets = sorted({pad_bucket(n) for n in lengths})
        t0 = time.perf_counter()
        # dropless: no expert gets more rows than the longest padded prompt
        self.ex.prewarm_buckets(max(buckets))
        self.ex.prewarm_batches([(1, S) for S in buckets])
        log(f"prewarm: MoE capacity buckets up to {max(buckets)} rows and "
            f"attention shapes {[(1, S) for S in buckets]} in "
            f"{time.perf_counter() - t0:.2f}s")
        # one request per batch: the engine builds [len(batch), S], and an
        # unbucketed batch size would compile a new program mid-serve
        batcher = LengthAwareBatcher(inflection=1, max_tokens=1 << 30,
                                     exclusive_cutoff=1 << 30, max_wait=0.0)
        self.clock = TraceClock(speed=1.0)
        self.engine = ExecutorEngine(self.ex, clock=self.clock,
                                     batcher=batcher)
        self.done_at: Dict[int, float] = {}
        self.hidden: Dict[int, np.ndarray] = {}
        hand_over = self.ex.on_complete

        def stamped(job):
            hand_over(job)  # the engine samples the first token here
            t = time.perf_counter()
            for i, r in enumerate(job.meta or ()):
                self.done_at.setdefault(r.rid, t)
                if job.result is not None and r.rid not in self.hidden:
                    # the final hidden states the head read from: a view of
                    # the job's host array, so nothing is copied here
                    self.hidden[r.rid] = job.result[i, :job.lengths[i]]

        self.ex.on_complete = stamped
        self.handles: Dict[int, Any] = {}
        # every eager op of the served path (embedding, casts, final norm,
        # head, argmax) compiles on its first shape: serve one prompt of
        # each bucket's longest length before the window
        t0 = time.perf_counter()
        warm = {}
        for n in lengths:
            warm[pad_bucket(n)] = n
        for i, n in enumerate(sorted(warm.values())):
            self.submit(_WARM_RID + i, np.zeros(n, np.int32),
                        time.perf_counter())
        self.wait(time.perf_counter() + 600)
        log(f"warm-up: {len(warm)} requests of lengths "
            f"{sorted(warm.values())} served in "
            f"{time.perf_counter() - t0:.2f}s")
        self.handles.clear()
        self.done_at.clear()
        self.hidden.clear()

    def submit(self, rid: int, tokens: np.ndarray, due: float) -> None:
        """Offer one prompt, due at host time `due` (time.perf_counter)."""
        from repro.core.trace import Request

        arrival = self.clock.now() - max(time.perf_counter() - due, 0.0)
        self.handles[rid] = self.engine.submit(
            Request(rid=rid, arrival=arrival, length=len(tokens)), tokens)

    def pending(self) -> List[int]:
        return [rid for rid, h in self.handles.items() if not h.done()]

    def wait(self, deadline: float) -> bool:
        """Wait until every submitted request has its result or the host
        clock passes `deadline`; surfaces a crashed worker as an error."""
        while self.pending():
            self.engine.poll()
            if time.perf_counter() >= deadline:
                return False
            time.sleep(0.002)
        self.engine.poll()
        return True

    def result(self, rid: int) -> Optional[Dict[str, Any]]:
        """The program's record of a finished request: its status, queue
        time (seconds, the program's own span), first token and the final
        hidden states [n, d] of its prompt, the last of which the head
        read."""
        h = self.handles[rid]
        if not h.done():
            return None
        r = h.result(timeout=0)
        return {"status": r.status, "queue_s": r.decomposition.get("queue"),
                "first_token": r.first_token, "hidden": self.hidden.get(rid)}

    def counters(self) -> Dict[str, float]:
        """The executor's cumulative super-GMM launch count."""
        return {"moe_launches": float(self.ex.moe_launches.sum())}

    def experts_per_launch(self) -> int:
        """Experts a super-GMM launch holds (the widest MoE device)."""
        return max(len(h) for h in self.ex.dev_experts)

    def compiled_programs(self) -> Dict[str, int]:
        """The executor's own trace counts per jitted step."""
        return dict(self.ex.trace_counts)

    def close(self) -> None:
        self.engine.close()
        self.ex.on_complete = None
        self.engine = self.ex = None
