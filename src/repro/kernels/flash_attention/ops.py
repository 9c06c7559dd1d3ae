"""Jitted wrapper: model-layout adapter for the flash attention kernel."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.models.attention import _expand_kv


def mha_flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None, block_q: int = 128,
              block_k: int = 128,
              interpret: Optional[bool] = None) -> jax.Array:
    """Model layout [B, S, H, dh] (kv may have fewer heads — GQA-expanded)."""
    B, S, H, dh = q.shape
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, dh)

    o = flash_attention(to_bh(q), to_bh(k), to_bh(v), causal=causal,
                        window=window, softcap=softcap, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return o.reshape(B, H, S, dh).transpose(0, 2, 1, 3)
