"""Capacity-bucket / Pallas-block autotuning table for the MoE super kernel
(ISSUE 10, ROADMAP item 3).

`super_moe_ffn` picks its grid blocking with a static heuristic
(`_pick_blocks`: largest power-of-two divisor ≤ 128 per dim).  On real
hardware the best (block_c, block_n, block_k) triple depends on the model
geometry AND the capacity bucket C, so — following the sweep-and-persist
pattern of sglang's deepep tuning harnesses — `benchmarks/tune_superkernel.py`
measures every candidate blocking per (n_experts, d_model, d_ff, dtype)
config × capacity bucket and persists the winners here as JSON.

At serve time the table is consulted per launch:

  * `set_table(TuningTable.load(path))` — explicit (serve.py --tuning-table);
  * `ASAP_TUNING_TABLE=<path>` — env fallback, loaded lazily once;
  * no table / no entry → the `_pick_blocks` heuristic, unchanged.

A table records where it was swept (`meta.platform`, `meta.device_kind`,
`meta.interpret`).  Installing one on a TPU run requires a table swept on
that same TPU kind: interpret-mode CPU timings say nothing about the chip.

The lookup key is fully determined by the launch's jit cache key (shapes +
dtype), so a table hit maps each cache key to ONE blocking deterministically —
tuned launches retain the zero-steady-state-retrace property (pinned by
tests/test_tuning.py).  The `ref` einsum path never consults the table.

Table schema (versioned):

  {"version": 1,
   "entries": {"e8_d128_f64_float32": {"16": {"up": [16, 64, 128],
                                              "down": [16, 128, 64],
                                              "us": 123.4}, ...}, ...}}

`us` (measured microseconds per launch for the winning blocking) is carried
for provenance only; lookups ignore it.
"""
from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Blocks = Tuple[int, int, int]

ENV_VAR = "ASAP_TUNING_TABLE"
TABLE_VERSION = 1


def config_key(n_experts: int, d_model: int, d_ff: int, dtype) -> str:
    """Canonical key for one super-kernel geometry.  `dtype` is anything
    numpy/jax can name (np.float32, jnp.bfloat16, "float32", ...)."""
    import numpy as np

    name = np.dtype(dtype).name if not isinstance(dtype, str) else dtype
    return f"e{n_experts}_d{d_model}_f{d_ff}_{name}"


@dataclass
class TuningTable:
    """Best-known (up, down) grid blockings per geometry × capacity bucket."""

    entries: Dict[str, Dict[str, dict]] = field(default_factory=dict)
    meta: Dict[str, object] = field(default_factory=dict)

    def put(self, key: str, capacity: int, up: Blocks, down: Blocks,
            us: Optional[float] = None) -> None:
        rec: dict = {"up": list(up), "down": list(down)}
        if us is not None:
            rec["us"] = us
        self.entries.setdefault(key, {})[str(int(capacity))] = rec

    def lookup(self, key: str, capacity: int
               ) -> Optional[Tuple[Blocks, Blocks]]:
        """Exact (key, bucket) hit or None — no nearest-bucket guessing: a
        blocking tuned for one C may not even divide another."""
        rec = self.entries.get(key, {}).get(str(int(capacity)))
        if rec is None:
            return None
        return tuple(rec["up"]), tuple(rec["down"])  # type: ignore[return-value]

    def save(self, path: str) -> None:
        payload = {"version": TABLE_VERSION, "meta": self.meta,
                   "entries": self.entries}
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)

    @classmethod
    def load(cls, path: str) -> "TuningTable":
        with open(path) as f:
            payload = json.load(f)
        if payload.get("version") != TABLE_VERSION:
            raise ValueError(
                f"tuning table {path!r}: version {payload.get('version')!r} "
                f"!= supported {TABLE_VERSION} — re-run "
                f"benchmarks/tune_superkernel.py to re-baseline")
        return cls(entries=payload.get("entries", {}),
                   meta=payload.get("meta", {}))


# ---------------------------------------------------------------------------
# Active-table registry (process-global, set-once at engine setup)
# ---------------------------------------------------------------------------

_table_lock = threading.Lock()
_active: Optional[TuningTable] = None  # guarded_by: _table_lock
_env_checked = False  # guarded_by: _table_lock


def check_provenance(table: TuningTable) -> None:
    """Refuse, on a TPU run, a table that was not swept on this TPU kind."""
    import jax

    if jax.default_backend() != "tpu":
        return
    kind = jax.devices()[0].device_kind
    where = (table.meta.get("platform"), table.meta.get("device_kind"))
    if where != ("tpu", kind) or table.meta.get("interpret", True):
        raise ValueError(
            f"tuning table swept on {where[0]!r}/{where[1]!r} (interpret="
            f"{table.meta.get('interpret')!r}) cannot tune kernels on "
            f"{kind!r} — re-run benchmarks/tune_superkernel.py on the chip")


def set_table(table: Optional[TuningTable]) -> None:
    """Install (or clear, with None) the process-wide active table.  Called
    at engine construction, BEFORE worker threads trace any kernels."""
    global _active, _env_checked
    if table is not None:
        check_provenance(table)
    with _table_lock:
        _active = table
        _env_checked = True  # explicit install wins over the env fallback


def get_table() -> Optional[TuningTable]:
    """The active table; on first call honours ASAP_TUNING_TABLE if no table
    was installed explicitly.  A broken env path raises — a tuned run that
    silently falls back to the heuristic would invalidate the measurement."""
    global _active, _env_checked
    with _table_lock:
        if not _env_checked:
            _env_checked = True
            path = os.environ.get(ENV_VAR)
            if path:
                _active = TuningTable.load(path)
                check_provenance(_active)
        return _active


def lookup_blocks(n_experts: int, d_model: int, d_ff: int, dtype,
                  capacity: int) -> Optional[Tuple[Blocks, Blocks]]:
    """One-stop consult for `super_moe_ffn`: returns ((bc, bn, bk) for the
    up/gate GMMs, (bc, bn, bk) for the down GMM) on a hit, else None."""
    table = get_table()
    if table is None:
        return None
    return table.lookup(config_key(n_experts, d_model, d_ff, dtype), capacity)


# ---------------------------------------------------------------------------
# Sweep-space helpers (shared by benchmarks/tune_superkernel.py and tests)
# ---------------------------------------------------------------------------


def block_candidates(dim: int, cap: int = 128) -> List[int]:
    """Power-of-two divisors of `dim` up to `cap`, descending — the TPU lane
    width is 128 so larger blocks never help, and non-divisors are rejected
    by `super_gmm`'s grid math (see /opt guide: last-dim tiles are 128 lanes,
    sublane tiles are 8/16/32 by dtype, all powers of two)."""
    return [b for b in (128, 64, 32, 16, 8, 4, 2, 1)
            if b <= cap and dim % b == 0]


def candidate_blockings(C: int, N: int, K: int,
                        limit: Optional[int] = None) -> List[Blocks]:
    """The (block_c, block_n, block_k) sweep space for one GMM shape,
    heuristic-first so a truncated sweep (`limit`) still contains today's
    default blocking."""
    out = [(bc, bn, bk)
           for bc in block_candidates(C)
           for bn in block_candidates(N)
           for bk in block_candidates(K)]
    return out if limit is None else out[:limit]
