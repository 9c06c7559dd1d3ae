"""The default reference: a decoder-only MoE transformer in float32.

The module a configuration file runs with when it names no `reference`
(see `manifest.py` for what a reference module provides).  Written from the
published description of Qwen3-MoE, in straightforward `jax.numpy` at
`highest` matmul precision, with no kernel, cache, capacity buffer or
batching of the program's.  It imports nothing from the program.  Per
layer:

    x = rms_norm(h) * ln_attn
    q, k, v = x Wq, x Wk, x Wv            (heads of head_dim; kv heads shared
                                            by num_heads / num_kv_heads query
                                            heads each)
    q, k = rms_norm(q) * q_norm, rms_norm(k) * k_norm   (qk_norm only)
    q, k = rope(q), rope(k)               (rotate-half, theta = rope_theta)
    h = h + causal_softmax(q k^T / sqrt(head_dim)) v Wo
    x = rms_norm(h) * ln_ffn
    p = softmax(x Wr); top_k of p, renormalised to sum 1
    h = h + sum_k p_k * (silu(x Wg_e) * (x Wu_e)) Wd_e
  logits = (rms_norm(h) * final_norm) W_lm_head

Weights come in the served dtype and are widened to float32 one layer (and,
for the experts, one expert) at a time, so the reference fits beside the
weights on one chip.  This chip holds every expert of every layer.  A
`model` block that states a key this module does not read, or a departure
from the plain decoder (a shared expert, a softcap, a window, ...) at any
but its plain value, is refused by every function here.

`control=True` computes the same model with every weight matmul's operands
rounded to float8 e4m3 (per-row scales for activations, per-output-column
scales for weights, float32 accumulation): the precision below the
configuration's bfloat16, which `correct` has to refuse when its final
hidden states and first tokens are put in place of the served ones.

The weights (`weights`) are drawn from the seed on the device, in one
jitted call.  The benchmark makes them, not the program: the reference then
uses nothing that the program made.  The tree has the program's parameter
layout (`system.check_layout` compares it with the program's own
initializer by shape and dtype), and its values are the benchmark's:

  matrices      normal, std 1/sqrt(fan_in), in the served dtype
  embedding     normal, std 1
  norm scales   1 + 0.1 * normal, so that a norm whose scale is dropped or
                misapplied moves the logits
  router        float32, as the program keeps it

The leaves that decide where each row is routed (the embedding, the
attention and its norms, the FFN norm and the router) are one fixed draw,
the same for every seed; the experts, the final norm and the head are drawn
from the seed.  Pad rows once went through the MoE, all routed alike, so
routing weights drawn from the seed put a run's capacity slots 26% apart
from one seed to the next; the draw is kept so that every seed serves the
same work, on other prompts and other expert weights.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with an absmax scale along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(_F32) * s


def _mm(x: jax.Array, w: jax.Array, control: bool) -> jax.Array:
    """x [..., k] @ w [k, n] in float32; in the control, both operands
    rounded to e4m3 first."""
    x = x.astype(_F32)
    w = w.astype(_F32)
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=_HI)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(_F32)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [..., S, heads, hd], pos [..., S]: rotate-half rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=_F32) / hd)
    ang = pos.astype(_F32)[..., None] * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_inputs(m, lp, h, control):
    """Normed input, q, k, v [S, heads, hd] of one layer for all rows."""
    S = h.shape[0]
    x = _rms(h, lp["ln_attn"], m["norm_eps"])
    a = lp["attn"]
    q = _mm(x, a["wq"], control).reshape(S, m["num_heads"], m["head_dim"])
    k = _mm(x, a["wk"], control).reshape(S, m["num_kv_heads"], m["head_dim"])
    v = _mm(x, a["wv"], control).reshape(S, m["num_kv_heads"], m["head_dim"])
    if m["qk_norm"]:
        q = _rms(q, a["q_norm"], m["norm_eps"])
        k = _rms(k, a["k_norm"], m["norm_eps"])
    pos = jnp.arange(S)
    return _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"]), v


def _attend(m, q, k, v, q_pos, n):
    """q [Q, H, hd] at positions q_pos [Q] over keys 0..n-1 (causal)."""
    rep = m["num_heads"] // m["num_kv_heads"]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) * m["head_dim"] ** -0.5
    kpos = jnp.arange(k.shape[0])
    ok = (kpos[None, :] <= q_pos[:, None]) & (kpos[None, :] < n)
    s = jnp.where(ok[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=_HI)


def _route(m, lp, x, control):
    """Top-k expert ids and renormalised weights of rows x [T, d]."""
    p = jax.nn.softmax(_mm(x, lp["ffn"]["router"], control), axis=-1)
    w, idx = jax.lax.top_k(p, m["top_k"])
    if m["router_renorm"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, idx


def _expert(x, wg, wu, wd, control):
    return _mm(jax.nn.silu(_mm(x, wg, control)) * _mm(x, wu, control), wd,
               control)


def _moe_all_rows(m, lp, x, control):
    """MoE output of every row, one expert at a time (dense over experts,
    each row weighted by its routing weight, 0 where not routed)."""
    w, idx = _route(m, lp, x, control)
    comb = jnp.zeros((x.shape[0], m["num_experts"]), _F32).at[
        jnp.arange(x.shape[0])[:, None], idx].add(w)
    ex = lp["ffn"]["experts"]

    def one(acc, e):
        y = _expert(x, ex["w_gate"][e], ex["w_up"][e], ex["w_down"][e],
                    control)
        return acc + comb[:, e][:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros(x.shape, _F32),
                          jnp.arange(m["num_experts"]))
    return acc


def _layer(m, w, l):
    st = w["stages"][0]
    return jax.tree.map(lambda a: a[l], st)


def _full_layer(m, lp, h, n, control):
    """One layer over every row of h [S, d] (rows >= n are padding)."""
    q, k, v = _attention_inputs(m, lp, h, control)
    S = h.shape[0]
    o = _attend(m, q, k, v, jnp.arange(S), n)
    h = h + _mm(o.reshape(S, -1), lp["attn"]["wo"], control)
    x = _rms(h, lp["ln_ffn"], m["norm_eps"])
    return h + _moe_all_rows(m, lp, x, control)


def _embed(w, tokens):
    return jnp.take(w["embed"], tokens, axis=0).astype(_F32)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _hidden_states(mkey: Tuple, w, tokens, n, control: bool):
    m = dict(mkey)
    h = _embed(w, tokens)
    for l in range(m["num_layers"]):
        h = _full_layer(m, _layer(m, w, l), h, n, control)
    return _rms(h, w["final_norm"], m["norm_eps"])


@functools.partial(jax.jit, static_argnums=(2,))
def _head(lm_head, h, control: bool):
    return _mm(h, lm_head, control)


# the `model` keys this module computes, and the departures from a plain
# decoder MoE that it computes only at their plain values
_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
         "num_experts", "top_k", "expert_d_ff", "vocab_size", "rope_theta",
         "norm_eps", "qk_norm", "router_renorm")
_PLAIN = {"num_shared_experts": 0, "tie_embeddings": False,
          "scale_embeddings": False, "act": "silu",
          "nonparametric_norm": False, "qkv_bias": False,
          "logit_softcap": None, "window_size": None}


def _check(m: Dict[str, Any]) -> None:
    """Refuse a `model` block that states what this module does not
    compute: a key it does not read, or a departure at another value."""
    bad = {k: v for k, v in m.items() if k not in _KEYS + ("dtype",)
           and (k not in _PLAIN or v != _PLAIN[k])}
    if bad:
        raise ValueError(f"the decoder_moe reference does not compute the "
                         f"model block's {bad}")


def model_key(m: Dict[str, Any]) -> Tuple:
    _check(m)
    return tuple((k, m[k]) for k in _KEYS)


def pad_to_bucket(n: int) -> int:
    """Rows a prompt of n tokens is computed over (a power of two >= 8):
    padding rows follow the prompt, so causal attention leaves it exact."""
    return 1 << (max(int(n), 8) - 1).bit_length()


def hidden_states(m: Dict[str, Any], w, tokens: np.ndarray,
                  control: bool = False) -> np.ndarray:
    """float32 reference hidden states [n, d] at every position of one
    prompt, after the final norm: what the head reads; with `control`, the
    float8 control's."""
    n = len(tokens)
    padded = np.zeros(pad_to_bucket(n), np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        out = _hidden_states(model_key(m), w, jnp.asarray(padded),
                             jnp.asarray(n, jnp.int32), control)
    return np.asarray(out[:n], np.float32)


def head_logits(m: Dict[str, Any], w, h: np.ndarray,
                control: bool = False) -> np.ndarray:
    """float32 logits [V] of a final hidden state [d] through the head;
    with `control`, in float8."""
    _check(m)
    with jax.default_matmul_precision("highest"):
        out = _head(w["lm_head"], jnp.asarray(h, jnp.float32), control)
    return np.asarray(out, np.float32)


def prompt_flops(m: Dict[str, Any], n: int) -> float:
    """Model FLOPs of prefilling one prompt of n real tokens: per layer the
    q/k/v/o projections, causal scores and values over n(n+1)/2 pairs, the
    router, 6 d f for each routed (token, expert) pair; then the head at
    the last position only."""
    _check(m)
    d, H, KV, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    proj = 2 * n * d * (H * hd + 2 * KV * hd) + 2 * n * H * hd * d
    attn = 2 * (n * (n + 1) // 2) * H * hd * 2
    router = 2 * n * d * m["num_experts"]
    experts = n * m["top_k"] * 6 * d * m["expert_d_ff"]
    return float(m["num_layers"] * (proj + attn + router + experts)
                 + 2 * d * m["vocab_size"])


def routed_rows(m: Dict[str, Any], w, tokens: np.ndarray) -> int:
    """Real (token, expert) rows the experts held here serve for one
    prompt.  This chip holds every expert, so each real token's top_k rows
    in every layer are served here, wherever its routing sends them."""
    _check(m)
    return len(tokens) * m["top_k"] * m["num_layers"]


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


def weights(m: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weights of the configuration's `model` block: the routing
    leaves from ROUTING_SEED, the rest from `seed`."""
    _check(m)
    return jax.jit(lambda k, r: _tree(m, k, r))(prng_key(seed),
                                                prng_key(ROUTING_SEED))
