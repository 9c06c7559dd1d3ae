"""Jitted wrappers + model integration for the MoE Super Kernel.

`make_super_kernel_gmm(stacked_experts, cfg)` returns a drop-in `gmm` for
`repro.models.lm.lm_forward(..., gmm=...)`: inside the layer scan it receives
the per-layer expert weights (ignored) and the runtime `layer_id`, and runs the
three expert projections through the layer-oblivious kernel against the FULL
stacked weights — the weights become scan constants (resident in HBM), the
layer id is scan data, and XLA emits ONE kernel for all layers.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.super_gmm import tuning
from repro.kernels.super_gmm.ref import super_moe_ffn_ref
from repro.kernels.super_gmm.super_gmm import super_gmm
from repro.models.common import ModelConfig, act_fn


def _pick_blocks(C: int, N: int, K: int):
    def pick(d, pref=128):
        for b in (pref, 64, 32, 16, 8, 4, 2, 1):
            if d % b == 0:
                return b
        return 1
    return pick(C), pick(N), pick(K)


def super_moe_ffn(layer_id: jax.Array, experts: dict, xb: jax.Array,
                  cfg: ModelConfig, expert_ids: Optional[jax.Array] = None,
                  interpret: Optional[bool] = None,
                  kernel: str = "pallas") -> jax.Array:
    """Gated expert FFN on capacity buffers via three super-GMM calls.

    xb: [E, C, d] -> [E, C, d] (fp32); buffer e holds the rows of expert
    `expert_ids[e]` of the stacked [L, n, ...] `experts` (default: expert
    e).  kernel="ref" routes through the layer-indexed einsum oracle instead
    of the Pallas grid — same layer-oblivious semantics (layer id stays
    runtime data); a test/oracle option, never the chip path.  `interpret`
    resolves through `repro.kernels.interpret`."""
    act = act_fn(cfg.act)
    if kernel == "ref":
        return super_moe_ffn_ref(jnp.reshape(layer_id, ()), experts, xb, act,
                                 expert_ids)
    E, C, d = xb.shape
    f = experts["w_gate"].shape[-1]
    # autotuned grid blocking when a table entry covers this geometry ×
    # capacity bucket (ISSUE 10); the lookup key is a function of the jit
    # cache key only, so tuned launches stay zero-retrace in steady state
    tuned = tuning.lookup_blocks(E, d, f, xb.dtype, C)
    if tuned is not None:
        (bc, bn, bk), (bc2, bn2, bk2) = tuned
    else:
        bc, bn, bk = _pick_blocks(C, f, d)
        bc2, bn2, bk2 = _pick_blocks(C, d, f)
    g = super_gmm(layer_id, experts["w_gate"], xb, expert_ids, block_c=bc,
                  block_n=bn, block_k=bk, interpret=interpret)
    u = super_gmm(layer_id, experts["w_up"], xb, expert_ids, block_c=bc,
                  block_n=bn, block_k=bk, interpret=interpret)
    h = (act(g) * u).astype(xb.dtype)
    return super_gmm(layer_id, experts["w_down"], h, expert_ids, block_c=bc2,
                     block_n=bn2, block_k=bk2, interpret=interpret)


def make_super_kernel_gmm(stacked_experts: dict, cfg: ModelConfig,
                          interpret: Optional[bool] = None) -> Callable:
    """Adapter for lm_forward(gmm=...): signature (xb, experts_layer, cfg,
    layer_id) -> yb. `experts_layer` (the scan-sliced per-layer weights) is
    intentionally unused — global weight access is the point."""

    def gmm(xb, experts_layer, cfg_inner, layer_id):
        del experts_layer
        lid = jnp.asarray(layer_id, jnp.int32).reshape(1)
        out = super_moe_ffn(lid, stacked_experts, xb, cfg_inner,
                            interpret=interpret)
        return out.astype(xb.dtype)

    return gmm


# ---------------------------------------------------------------------------
# Capacity-buffer packing (host side, for the threaded executor's hot path)
# ---------------------------------------------------------------------------


def round_capacity(n: int, minimum: int = 8) -> int:
    """Round a per-expert row count up to the next power of two (>= minimum).

    Bucketing the capacity keeps the jit cache keyed on O(log N) distinct
    [n_experts, C, d] shapes, so steady-state regions hit an existing trace
    instead of recompiling for every token count."""
    return max(minimum, 1 << max(int(n) - 1, 0).bit_length())


def pack_capacity(tokens: np.ndarray, eids: np.ndarray, n_experts: int,
                  capacity: Optional[int] = None
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Scatter N token rows into dropless [n_experts, C, d] capacity buffers.

    One vectorized segment-sort (stable argsort by expert + exclusive-prefix
    offsets) replaces the per-expert boolean-mask loop: every row lands at
    slot ``expert * C + position_within_expert``.  C defaults to the bucketed
    max per-expert count so nothing is dropped (the executor's numerical
    contract) and the buffer shape stays jit-cache friendly.

    Returns (xb [n_experts, C, d], order, slots, C) where `order`/`slots`
    invert the packing in `unpack_capacity`.
    """
    n, d = tokens.shape
    counts = np.bincount(eids, minlength=n_experts)
    cmax = int(counts.max()) if n else 1
    C = capacity if capacity is not None else round_capacity(cmax)
    assert C >= cmax, f"capacity {C} drops rows (max count {cmax})"
    order = np.argsort(eids, kind="stable")
    offsets = np.cumsum(counts) - counts  # exclusive prefix sum
    pos = np.arange(n) - offsets[eids[order]]
    slots = eids[order] * C + pos
    xb = np.zeros((n_experts * C, d), tokens.dtype)
    xb[slots] = tokens[order]
    return xb.reshape(n_experts, C, d), order, slots, C


def unpack_capacity(yb: np.ndarray, order: np.ndarray, slots: np.ndarray,
                    n: int) -> np.ndarray:
    """Gather expert outputs back to the original row order (inverse of
    `pack_capacity`). yb: [n_experts, C, d] -> [n, d]."""
    d = yb.shape[-1]
    out = np.empty((n, d), yb.dtype)
    out[order] = yb.reshape(-1, d)[slots]
    return out


def pack_capacity_multi(token_list: Sequence[np.ndarray],
                        eid_list: Sequence[np.ndarray], n_experts: int,
                        capacity: Optional[int] = None
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int,
                                   np.ndarray]:
    """Pack SEVERAL regions' rows into ONE shared capacity buffer (ISSUE 10).

    The continuous batcher's merge step: regions drained from different DP
    groups (same layer) are concatenated row-major and packed with ONE
    `pack_capacity` call, so one `super_moe_ffn` launch serves them all.  Row
    provenance is preserved via `bounds` — the cumulative row count per
    region — which `unpack_capacity_multi` uses to scatter each region's
    outputs back to its OWN combine path, exactly once.

    Bit-equality with the per-region path holds because every capacity-buffer
    row is an independent dot-product chain: merging regions (or growing C to
    the merged bucket) changes WHERE a row sits, never the reduction order
    over d_model/d_ff — pinned by tests/test_kernels.py.

    Returns (xb [n_experts, C, d], order, slots, C, bounds) where
    (order, slots) invert the merged packing and bounds[r] is the first row
    index AFTER region r in the concatenated order.
    """
    assert len(token_list) == len(eid_list) and token_list, "no regions"
    bounds = np.cumsum([len(t) for t in token_list])
    tokens = token_list[0] if len(token_list) == 1 \
        else np.concatenate(token_list, axis=0)
    eids = eid_list[0] if len(eid_list) == 1 \
        else np.concatenate(eid_list, axis=0)
    xb, order, slots, C = pack_capacity(tokens, eids, n_experts, capacity)
    return xb, order, slots, C, bounds


def unpack_capacity_multi(yb: np.ndarray, order: np.ndarray,
                          slots: np.ndarray, bounds: np.ndarray
                          ) -> list[np.ndarray]:
    """Split merged expert outputs back into per-region row blocks (inverse
    of `pack_capacity_multi`).  yb: [n_experts, C, d] -> one [n_r, d] array
    per region, in the region order the packer was given."""
    out = unpack_capacity(yb, order, slots, int(bounds[-1]))
    return np.split(out, bounds[:-1])
