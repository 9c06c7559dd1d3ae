"""Jitted wrappers integrating the dispatch/combine kernels with the MoE layer.

`kernel_moe_dispatch` / `kernel_moe_combine` mirror models/moe.py::
moe_dispatch / moe_combine bit-for-bit (tested), with the payload movement
done by the Pallas indirection kernels instead of jnp scatter/gather.

Production-shape note: a row-per-pair grid issues N tiny DMAs; the production
variant sorts slots so consecutive rows share destination blocks and copies
8·128-aligned tiles (same index_map machinery, coarser grid). Kept simple here
because the kernels are exercised only by the interpret-mode tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.dispatch_combine.dispatch_combine import (combine_gather,
                                                             dispatch_scatter)
from repro.models.common import ModelConfig
from repro.models.moe import expert_capacity


def kernel_moe_dispatch(x: jax.Array, idx: jax.Array, cfg: ModelConfig,
                        capacity=None, interpret: Optional[bool] = None):
    """x: [T, d]; idx: [T, K] -> ([E, C, d], info) — same contract as
    models.moe.moe_dispatch."""
    T, d = x.shape
    K, E = cfg.top_k, cfg.num_experts
    C = capacity or expert_capacity(T, cfg)
    flat_e = idx.reshape(T * K)
    perm = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[perm]
    group_sizes = jnp.bincount(flat_e, length=E)
    group_offset = jnp.cumsum(group_sizes) - group_sizes
    pos_in_group = jnp.arange(T * K) - group_offset[sorted_e]
    valid = pos_in_group < C
    slot = jnp.where(valid, sorted_e * C + pos_in_group, E * C)
    token_of = (perm // K).astype(jnp.int32)
    xb = dispatch_scatter(token_of, slot.astype(jnp.int32), x,
                          rows_out=E * C + 1, interpret=interpret)
    xb = xb[:E * C].reshape(E, C, d)
    info = dict(perm=perm, slot=slot, valid=valid, group_sizes=group_sizes,
                capacity=C)
    return xb, info


def kernel_moe_combine(yb: jax.Array, info, weights: jax.Array, T: int,
                       interpret: Optional[bool] = None) -> jax.Array:
    E, C, d = yb.shape
    K = weights.shape[1]
    flat = jnp.concatenate([yb.reshape(E * C, d),
                            jnp.zeros((1, d), yb.dtype)], 0)
    gathered = combine_gather(info["slot"].astype(jnp.int32), flat,
                              interpret=interpret)
    out_sorted = jnp.zeros((T * K, d), flat.dtype).at[info["perm"]].set(gathered)
    out = out_sorted.reshape(T, K, d)
    return jnp.einsum("tkd,tk->td", out, weights.astype(out.dtype))
