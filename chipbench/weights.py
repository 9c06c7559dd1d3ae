"""Weights drawn from the seed on the device, in one jitted call.

The benchmark makes the weights, not the program: the reference then uses
nothing that the program made.  The tree has the program's parameter layout
(`system.check_layout` compares it with the program's own initializer by
shape and dtype), and its values are the benchmark's:

  matrices      normal, std 1/sqrt(fan_in), in the served dtype
  embedding     normal, std 1
  norm scales   1 + 0.1 * normal, so that a norm whose scale is dropped or
                misapplied moves the logits
  router        float32, as the program keeps it

The leaves that decide where each row is routed (the embedding, the
attention and its norms, the FFN norm and the router) are one fixed draw,
the same for every seed; the experts, the final norm and the head are drawn
from the seed.  The executor pads a prompt to its power-of-two bucket and
routes the pad rows as well, all alike, so the capacity of each super-GMM
launch, and with it the work of a run, follows the routing weights: drawn
from the seed, they put a run's capacity slots 26% apart from one seed to
the next.  So every seed serves the same work, on other prompts and other
expert weights.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def prng_key(seed: int) -> jax.Array:
    """A key for any integer seed: its low and high 32 bits, folded."""
    s = seed % (1 << 64)
    key = jax.random.PRNGKey(jnp.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(key, jnp.uint32(s >> 32))


ROUTING_SEED = 20240611  # the one draw of the leaves that decide routing


def _tree(m: Dict[str, Any], key: jax.Array,
          routing_key: jax.Array) -> Dict[str, Any]:
    L, d, V = m["num_layers"], m["d_model"], m["vocab_size"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    E, f = m["num_experts"], m["expert_d_ff"]
    dt = jnp.dtype(m["dtype"])
    seeded = iter(jax.random.split(key, 16))
    fixed = iter(jax.random.split(routing_key, 16))

    # drawn in the stored dtype, so no float32 copy of a leaf is made
    def normal(keys, shape, std, dtype=dt):
        return jax.random.normal(next(keys), shape, dtype) \
            * jnp.asarray(std, dtype)

    def scale(keys, shape):
        return 1 + jax.random.normal(next(keys), shape, dt) \
            * jnp.asarray(0.1, dt)

    attn = {"wq": normal(fixed, (L, d, H * hd), d ** -0.5),
            "wk": normal(fixed, (L, d, KV * hd), d ** -0.5),
            "wv": normal(fixed, (L, d, KV * hd), d ** -0.5),
            "wo": normal(fixed, (L, H * hd, d), (H * hd) ** -0.5)}
    if m["qk_norm"]:
        attn["q_norm"] = scale(fixed, (L, hd))
        attn["k_norm"] = scale(fixed, (L, hd))
    experts = {"w_gate": normal(seeded, (L, E, d, f), d ** -0.5),
               "w_up": normal(seeded, (L, E, d, f), d ** -0.5),
               "w_down": normal(seeded, (L, E, f, d), f ** -0.5)}
    stage = {
        "ln_attn": scale(fixed, (L, d)),
        "attn": attn,
        "ln_ffn": scale(fixed, (L, d)),
        "ffn": {"router": normal(fixed, (L, d, E), d ** -0.5, jnp.float32),
                "experts": experts},
    }
    return {"embed": normal(fixed, (V, d), 1.0), "stages": [stage],
            "final_norm": scale(seeded, (d,)),
            "lm_head": normal(seeded, (d, V), d ** -0.5)}


def make(m: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weights of the configuration's `model` block: the routing
    leaves from ROUTING_SEED, the rest from `seed`."""
    return jax.jit(lambda k, r: _tree(m, k, r))(prng_key(seed),
                                                prng_key(ROUTING_SEED))
