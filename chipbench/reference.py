"""The plain reference: a decoder-only MoE transformer in float32.

Written from the published descriptions of Qwen3-MoE and DBRX (as the
configuration file runs them, with its `departures`), in straightforward
`jax.numpy` at `highest` matmul precision, with no kernel, cache, capacity
buffer or batching of the program's.  It imports nothing from the program.
Per layer:

    x = rms_norm(h) * ln_attn
    q, k, v = x Wq, x Wk, x Wv            (heads of head_dim; kv heads shared
                                            by num_heads / num_kv_heads query
                                            heads each; clamped to
                                            [-clip_qkv, clip_qkv] where the
                                            configuration sets clip_qkv)
    q, k = rms_norm(q) * q_norm, rms_norm(k) * k_norm   (qk_norm only)
    q, k = rope(q), rope(k)               (rotate-half, theta = rope_theta)
    h = h + causal_softmax(q k^T / sqrt(head_dim)) v Wo
    x = rms_norm(h) * ln_ffn
    p = softmax(x Wr); top_k of p, renormalised to sum 1
    h = h + sum_k p_k * (silu(x Wg_e) * (x Wu_e)) Wd_e
  logits = (rms_norm(h) * final_norm) W_lm_head

Weights come in the served dtype and are widened to float32 one layer (and,
for the experts, one expert) at a time, so the reference fits beside the
weights on one chip.

`control=True` computes the same model with every weight matmul's operands
rounded to float8 e4m3 (per-row scales for activations, per-output-column
scales for weights, float32 accumulation): the precision below the
configuration's bfloat16, which `correct` has to refuse when its final
hidden states and first tokens are put in place of the served ones.
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
    if m["clip_qkv"] is not None:
        c = m["clip_qkv"]
        q, k, v = (jnp.clip(t, -c, c) for t in (q, k, v))
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


def _layer(m, weights, l):
    st = weights["stages"][0]
    return jax.tree.map(lambda a: a[l], st)


def _full_layer(m, lp, h, n, control):
    """One layer over every row of h [S, d] (rows >= n are padding)."""
    q, k, v = _attention_inputs(m, lp, h, control)
    S = h.shape[0]
    o = _attend(m, q, k, v, jnp.arange(S), n)
    h = h + _mm(o.reshape(S, -1), lp["attn"]["wo"], control)
    x = _rms(h, lp["ln_ffn"], m["norm_eps"])
    return h + _moe_all_rows(m, lp, x, control)


def _embed(weights, tokens):
    return jnp.take(weights["embed"], tokens, axis=0).astype(_F32)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _hidden_states(mkey: Tuple, weights, tokens, n, control: bool):
    m = dict(mkey)
    h = _embed(weights, tokens)
    for l in range(m["num_layers"]):
        h = _full_layer(m, _layer(m, weights, l), h, n, control)
    return _rms(h, weights["final_norm"], m["norm_eps"])


@functools.partial(jax.jit, static_argnums=(2,))
def _head(lm_head, h, control: bool):
    return _mm(h, lm_head, control)


def model_key(m: Dict[str, Any]) -> Tuple:
    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
            "num_experts", "top_k", "expert_d_ff", "vocab_size", "rope_theta",
            "norm_eps", "qk_norm", "router_renorm")
    return tuple((k, m[k]) for k in keys) + (("clip_qkv",
                                              m.get("clip_qkv")),)


def pad_to_bucket(n: int) -> int:
    """Rows a prompt of n tokens is computed over (a power of two >= 8):
    padding rows follow the prompt, so causal attention leaves it exact."""
    return 1 << (max(int(n), 8) - 1).bit_length()


def hidden_states(m: Dict[str, Any], weights, tokens: np.ndarray,
                  control: bool = False) -> np.ndarray:
    """float32 reference hidden states [n, d] at every position of one
    prompt, after the final norm: what the head reads; with `control`, the
    float8 control's."""
    n = len(tokens)
    padded = np.zeros(pad_to_bucket(n), np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        out = _hidden_states(model_key(m), weights, jnp.asarray(padded),
                             jnp.asarray(n, jnp.int32), control)
    return np.asarray(out[:n], np.float32)


def head_logits(weights, h: np.ndarray, control: bool = False) -> np.ndarray:
    """float32 logits [V] of a final hidden state [d] through the head;
    with `control`, in float8."""
    with jax.default_matmul_precision("highest"):
        out = _head(weights["lm_head"], jnp.asarray(h, jnp.float32), control)
    return np.asarray(out, np.float32)
