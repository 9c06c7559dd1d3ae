"""Chip-side arithmetic: the table of peaks, the super-GMM's operations and
bytes from the configuration's shapes, and the least time they need.  A
model's own FLOPs and routed rows come from its reference module
(`chipbench/references/<name>.py`).

Padding never counts: not the rows that pad a prompt to its bucket, and not
the empty slots of a capacity buffer.  A kernel that stops computing
padding therefore reads closer to its roofline, never further from it.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")
_DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"chipbench/peaks.json ({sorted(table)})")
    return table[device_kind]


def expert_pair_flops(m: Dict[str, Any]) -> float:
    """FLOPs of one routed (token, expert) pair in the gated expert FFN:
    gate, up and down projections."""
    return 6.0 * m["d_model"] * m["expert_d_ff"]


def super_gmm_work(m: Dict[str, Any], real_pairs: int, launches: int,
                   experts_per_launch: int) -> Tuple[float, float]:
    """(FLOPs, bytes) that `launches` super-GMM launches need to serve
    `real_pairs` real (token, expert) rows: the three projections of each
    row; the weights of the experts a launch holds (every held expert is
    touched at these sizes, and the program does not report routing per
    launch), plus each real row read in and written out once."""
    wbytes = _DTYPE_BYTES[m["dtype"]]
    d, f = m["d_model"], m["expert_d_ff"]
    flops = real_pairs * expert_pair_flops(m)
    weights = launches * experts_per_launch * 3 * d * f * wbytes
    rows = real_pairs * 2 * d * wbytes
    return float(flops), float(weights + rows)


def least_time(flops: float, nbytes: float,
               peak: Dict[str, Any]) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
