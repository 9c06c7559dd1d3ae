"""Decode subsystem (ISSUE 9 tentpole): the token-generation stage behind
the prefill/decode disaggregation.

Two runtimes behind ONE poll-driven interface (mirroring the prefill side's
SimEngine/ExecutorEngine split):

  SimDecodeEngine  — `DecodeSim` (simulator.py): analytic continuous
                     batching in VIRTUAL time; per-step cost is KV-bytes-
                     read dominated and batch-width amortized
                     (`CostModel.decode_step_latency`), expert routing per
                     step through the same `ExpertLoadModel` as prefill.
  ExecDecodeEngine — `DecodeExecutor` (this module): REAL single-token
                     decode steps, jitted once over preallocated ragged KV
                     slots.  The layer stack runs under `lax.scan`, row
                     validity/lengths are traced DATA, so the steady state
                     performs zero retraces no matter how requests join and
                     leave between steps (the `trace_counts["decode_step"]`
                     probe pins this in tests).

Both engines share the flow: `enroll(KVHandle, steps, t_ready)` registers a
request whose prefill KV landed at `t_ready` (admission order + width cap
via `DecodeAdmissionQueue`); `pump()` runs decode steps and returns
`DecodeCompletion`s; `drain()` finishes everything enrolled.  The
`PDOrchestrator` (core/orchestrator.py) is the only driver.

Every class here is single-threaded by design — one orchestrator drives one
decode engine from its own poll loop (same caller-thread discipline as
SimEngine); `trace_counts` alone takes a lock because jit tracing is the
one re-entrant path.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import CostModel, ExpertLoadModel
from repro.core.kv import KVHandle
from repro.core.scheduler import DecodeAdmissionQueue
from repro.core.simulator import DecodeSim
from repro.models.blocks import decoder_block_decode_ragged
from repro.models.common import ModelConfig, apply_norm
from repro.models.lm import embed_tokens, lm_head, lm_stages


@dataclasses.dataclass
class DecodeCompletion:
    """One request's finished decode tail (tokens 2..out_len)."""
    rid: int
    t_admitted: float
    token_times: List[float]  # engine-time stamps, one per decode token
    tokens: Optional[List[int]] = None  # sampled ids (real executor only)


# ---------------------------------------------------------------------------
# Simulator decode runtime
# ---------------------------------------------------------------------------


class SimDecodeEngine:
    """`DecodeSim` behind the decode-engine interface (virtual time)."""

    virtual = True  # pump() takes a causality frontier in virtual seconds

    def __init__(self, cfg: ModelConfig, cm: CostModel,
                 load_model: Optional[ExpertLoadModel] = None,
                 width: int = 32):
        self.cfg, self.cm = cfg, cm
        self.sim = DecodeSim(cfg, cm, load_model, width=width)

    @property
    def load(self) -> int:
        return self.sim.load

    def enroll(self, handle: KVHandle, steps: int, t_ready: float,
               first_token: Optional[int] = None):
        self.sim.enroll(handle.rid, handle.prompt_len, steps, t_ready)

    def _collect(self) -> List[DecodeCompletion]:
        out = [DecodeCompletion(rid=e.rid, t_admitted=e.t_admitted,
                                token_times=list(e.token_times))
               for e in self.sim.completed]
        self.sim.completed = []
        return out

    def pump(self, t_limit: float) -> List[DecodeCompletion]:
        """Advance virtual time to `t_limit` — the orchestrator passes its
        prefill frontier so decode never outruns known prefill progress."""
        self.sim.advance(t_limit)
        return self._collect()

    def drain(self) -> Tuple[List[DecodeCompletion], List[int]]:
        """Finish everything enrolled (all enrollments are known by drain
        time — the orchestrator drains prefill first).  The internal bound
        only catches a wedged cost model; normal runs never hit it."""
        s = self.sim
        remaining, kv_max = s.remaining_work()
        if remaining:
            horizon = s.now + 4.0 * remaining \
                * self.cm.decode_step_latency([kv_max]) + 60.0
            leftovers = s.drain(horizon)
        else:
            leftovers = s.drain(s.now)
        return self._collect(), [e.rid for e in leftovers]

    def close(self):
        pass


# ---------------------------------------------------------------------------
# Real decode runtime
# ---------------------------------------------------------------------------


def make_decode_step(cfg: ModelConfig,
                     on_trace: Callable[[], None] = lambda: None):
    """ONE jitted single-token decode step over ragged KV slots:
    `step(params, k, v, tokens, lengths, active)` embeds the last sampled
    ids, `lax.scan`s the stacked decoder layers through
    `decoder_block_decode_ragged` (per-row cache append + ragged mask),
    takes the lm_head argmax and freezes inactive rows.  The params are an
    ARGUMENT (a closure would bake every weight into the HLO); `on_trace`
    runs at trace time only — the retrace probe."""
    moe = cfg.family == "moe"

    def step(params, k, v, tokens, lengths, active):
        on_trace()
        h = embed_tokens(params, tokens[:, None], None, cfg)

        def body(hh, xs):
            lp, kc, vc = xs
            hh, ck, cv = decoder_block_decode_ragged(
                lp, hh, kc, vc, lengths, cfg, moe=moe)
            return hh, (ck, cv)

        h, (nk, nv) = jax.lax.scan(body, h, (params["stages"][0], k, v))
        hN = apply_norm(h[:, 0], params["final_norm"], cfg)
        nxt = jnp.argmax(lm_head(params, hN, cfg), -1).astype(jnp.int32)
        new_tokens = jnp.where(active, nxt, tokens)
        new_lengths = jnp.where(active, lengths + 1, lengths)
        return nk, nv, new_tokens, new_lengths

    return jax.jit(step)


class DecodeExecutor:
    """Jitted continuous-batching decode runtime over preallocated ragged
    KV slots.

    State is `slots` cache rows of `max_len` tokens ([L, slots, max_len,
    kvh, hd] K and V), per-row lengths/last-token ids, and a host-side
    active mask.  ONE `jax.jit` step advances every row a token: embed the
    last sampled ids, `lax.scan` the stacked decoder layers through
    `decoder_block_decode_ragged` (per-row cache append + ragged mask),
    final norm + lm_head argmax, then freeze inactive rows with
    `jnp.where(active, ...)`.  All shapes are static and row occupancy is
    DATA, so joins/leaves between steps never retrace — pinned by the
    `trace_counts["decode_step"]` probe.

    Enrollment is a real device-buffer move: the prefill executor's
    exported per-layer (k, v) arrays land in the slot's cache rows via
    `.at[:, slot, :Lp].set(...)` between steps.
    """

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 8,
                 max_len: int = 256, clock=None):
        stages = lm_stages(cfg)
        assert len(stages) == 1 and stages[0][0] == "decoder", \
            "DecodeExecutor supports the uniform decoder family only"
        assert slots >= 1 and max_len >= 2
        self.params, self.cfg = params, cfg
        self.slots, self.max_len = slots, max_len
        self.clock = clock if clock is not None else time.monotonic
        L = cfg.num_layers
        shape = (L, slots, max_len, cfg.num_kv_heads, cfg.head_dim)
        self._k = jnp.zeros(shape, cfg.dtype)
        self._v = jnp.zeros(shape, cfg.dtype)
        self._tokens = jnp.zeros((slots,), jnp.int32)
        self._lengths = jnp.zeros((slots,), jnp.int32)
        self._active = np.zeros((slots,), bool)  # host mirror of occupancy
        self.trace_counts: Dict[str, int] = {"decode_step": 0}
        self._trace_lock = threading.Lock()
        self._step = make_decode_step(cfg, on_trace=self._count_trace)

    def _count_trace(self):
        with self._trace_lock:  # runs at trace time only (retrace probe)
            self.trace_counts["decode_step"] += 1

    def prewarm(self):
        """Compile the decode step before serving: one step over the empty
        slots (no row is active, so the state is left as it was)."""
        jax.block_until_ready(self._step(
            self.params, self._k, self._v, self._tokens, self._lengths,
            jnp.asarray(self._active)))

    def occupy(self, slot: int, handle: KVHandle, first_token: int):
        """Enroll one request into `slot`: device move of its prefill KV
        plus the first sampled token (its decode input)."""
        assert handle.payload is not None, \
            "DecodeExecutor needs a real KV payload (keep_kv prefill)"
        k_np, v_np = handle.payload
        Lp = handle.prompt_len
        assert k_np.shape[1] == Lp and Lp < self.max_len
        self._k = self._k.at[:, slot, :Lp].set(
            jnp.asarray(k_np, self.cfg.dtype))
        self._v = self._v.at[:, slot, :Lp].set(
            jnp.asarray(v_np, self.cfg.dtype))
        self._tokens = self._tokens.at[slot].set(int(first_token))
        self._lengths = self._lengths.at[slot].set(Lp)
        self._active[slot] = True

    def release(self, slot: int):
        self._active[slot] = False

    def step_once(self) -> Tuple[float, np.ndarray]:
        """One batched decode step; returns (t_done, per-slot token ids)."""
        self._k, self._v, self._tokens, self._lengths = self._step(
            self.params, self._k, self._v, self._tokens, self._lengths,
            jnp.asarray(self._active))
        toks = np.asarray(self._tokens)
        return self.clock(), toks


class ExecDecodeEngine:
    """Poll-driven decode engine over `DecodeExecutor` (wall/trace time).

    No background threads: the orchestrator's poll loop calls `pump()`,
    which admits every ready request into a free slot (real KV device move)
    and runs batched steps while any slot is occupied.  Requests leave the
    instant their step budget is spent — continuous batching, slots turn
    over between steps.
    """

    virtual = False  # pump() runs against the runtime's own clock

    def __init__(self, runtime: DecodeExecutor):
        self.rt = runtime
        self.q = DecodeAdmissionQueue(runtime.slots)
        self._free = list(range(runtime.slots))
        self._by_slot: Dict[int, Dict[str, Any]] = {}

    @property
    def load(self) -> int:
        return self.q.active + len(self.q)

    def enroll(self, handle: KVHandle, steps: int, t_ready: float,
               first_token: Optional[int] = None):
        assert steps >= 1
        assert handle.prompt_len + steps <= self.rt.max_len, \
            f"rid {handle.rid}: {handle.prompt_len}+{steps} tokens exceed " \
            f"the decode cache ({self.rt.max_len})"
        self.q.push(t_ready, {
            "handle": handle, "remaining": steps,
            "first_token": int(first_token) if first_token is not None else 0,
            "t_admitted": None, "token_times": [], "tokens": [],
            "slot": None})

    def _admit(self, now: float):
        for e in self.q.admit(now):
            slot = self._free.pop()
            e["slot"], e["t_admitted"] = slot, now
            self.rt.occupy(slot, e["handle"], e["first_token"])
            self._by_slot[slot] = e

    def pump(self, max_steps: Optional[int] = None) -> List[DecodeCompletion]:
        """Admit + step until no slot is occupied (or `max_steps`).  Pending
        entries whose `t_ready` is still in the future stay queued — the
        caller re-pumps on its next poll."""
        done: List[DecodeCompletion] = []
        steps = 0
        while True:
            self._admit(self.rt.clock())
            if not self._by_slot:
                return done
            t, toks = self.rt.step_once()
            for slot in list(self._by_slot):
                e = self._by_slot[slot]
                e["token_times"].append(t)
                e["tokens"].append(int(toks[slot]))
                e["remaining"] -= 1
                if e["remaining"] <= 0:
                    del self._by_slot[slot]
                    self.rt.release(slot)
                    self._free.append(slot)
                    self.q.release()
                    done.append(DecodeCompletion(
                        rid=e["handle"].rid, t_admitted=e["t_admitted"],
                        token_times=e["token_times"], tokens=e["tokens"]))
            steps += 1
            if max_steps is not None and steps >= max_steps:
                return done

    def drain(self, timeout: Optional[float] = None) \
            -> Tuple[List[DecodeCompletion], List[int]]:
        """Pump until everything enrolled finished (waiting out future
        `t_ready` stamps) or the WALL `timeout` passed; unfinished rids are
        returned for the orchestrator to mark `timeout`."""
        deadline = None if timeout is None else time.monotonic() + timeout
        done: List[DecodeCompletion] = []
        while self._by_slot or len(self.q):
            done += self.pump()
            if not self._by_slot and len(self.q):
                if deadline is not None and time.monotonic() > deadline:
                    break
                time.sleep(0.001)  # next t_ready is still in the future
        leftovers = [e["handle"].rid for e in self._by_slot.values()]
        leftovers += [e["handle"].rid for e in self.q.drain_all()]
        for slot in list(self._by_slot):
            self.rt.release(slot)
            self._free.append(slot)
            del self._by_slot[slot]
        self.q.release(self.q.active)
        return done, leftovers

    def close(self):
        pass
