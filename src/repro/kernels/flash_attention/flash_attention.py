"""Blocked causal flash attention (prefill hot spot) — Pallas TPU kernel.

Grid (B·H, S/bq, S/bk) with the key-block dimension innermost ("arbitrary"
semantics) so the online-softmax state (m, l, acc) lives in VMEM scratch across
key blocks. Causal + optional sliding-window masking; key blocks fully outside
the causal/window frontier are skipped with pl.when (no MXU work issued).

This is the kernel-level counterpart of models/attention.py::
chunked_causal_attention (the jnp oracle used on CPU and in the dry-run).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocking import floor_to_divisor
from repro.kernels.interpret import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale: float, block_q: int, block_k: int, nk: int,
            causal: bool, window: Optional[int], softcap: Optional[float]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # Frontier tests are on block extremes -> static-shape pl.when guards.
    in_causal = (not causal) or (k_start <= q_start + block_q - 1)
    if window is not None:
        in_window = k_start + block_k - 1 > q_start - window
    else:
        in_window = True

    @pl.when(jnp.logical_and(in_causal, in_window))
    def _work():
        q = q_ref[0]  # [bq, dh]
        k = k_ref[0]  # [bk, dh]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] \
            + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q, k, v: [BH, S, dh] (kv already head-expanded). Returns [BH, S, dh]."""
    BH, S, dh = q.shape
    # round DOWN to a divisor (never min-clamp): S=192 with block 128 must
    # pick 96, not a non-dividing 128 that misindexes the (nq, nk) grid
    bq = floor_to_divisor(S, block_q, what="flash_attention S/bq")
    bk = floor_to_divisor(S, block_k, what="flash_attention S/bk")
    nq, nk = S // bq, S // bk
    sm_scale = 1.0 / math.sqrt(dh)
    kern = functools.partial(_kernel, sm_scale=sm_scale, block_q=bq,
                             block_k=bk, nk=nk, causal=causal, window=window,
                             softcap=softcap)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
