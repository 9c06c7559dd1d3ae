"""Pure-jnp oracle for the MoE Super Kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def super_gmm_ref(layer_id: jax.Array, w: jax.Array, x: jax.Array,
                  expert_ids=None) -> jax.Array:
    """out[e, c, n] = x[e, c, :] @ w[layer_id, expert_ids[e], :, :] (fp32
    accumulate; `expert_ids=None` means buffer e is expert e)."""
    wl = jax.lax.dynamic_index_in_dim(w, layer_id.reshape(()), axis=0,
                                      keepdims=False)
    if expert_ids is not None:
        wl = wl[expert_ids]
    return jnp.einsum("eck,ekn->ecn", x, wl,
                      preferred_element_type=jnp.float32).astype(jnp.float32)


def super_moe_ffn_ref(layer_id, experts, xb, act, expert_ids=None) -> jax.Array:
    """Full gated expert FFN through the layer-indexed weights."""
    g = super_gmm_ref(layer_id, experts["w_gate"], xb, expert_ids)
    u = super_gmm_ref(layer_id, experts["w_up"], xb, expert_ids)
    h = (act(g) * u).astype(xb.dtype)
    return super_gmm_ref(layer_id, experts["w_down"], h, expert_ids)
