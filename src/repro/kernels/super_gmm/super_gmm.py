"""MoE Super Kernel — layer-oblivious grouped (batched-expert) matmul.

The paper's §3.4.2 kernel, adapted to TPU idiom:

  * Global weight access    -> the kernel binds the FULL [L, n, d_in, d_out]
    stacked expert weights resident in HBM.
  * Pre-calculated indexing -> the BlockSpec `index_map` is the address array:
    it converts (layer, expert, tile) to a constant-time HBM block offset.
  * Dynamic resolution      -> `layer_id` and the per-buffer `expert_ids` are
    SCALAR-PREFETCH operands (SMEM), i.e. device-side runtime values, never
    Python/compile-time constants.

Because the layer id is data, XLA traces ONE kernel for all L layers; a
`lax.scan` over layers dispatches it ahead of time with zero per-layer host
work — the TPU equivalent of eliminating the 220 µs/layer CPU dispatch bubble
(Fig 10/18).  Because the expert ids are data, an MoE device serves its
subset of experts straight out of the one shared weight stack: no per-device
copy of its experts, and a re-placement swaps a small id vector.

Grid: (E, C/bc, N/bn, K/bk) with the contraction tile innermost so the fp32
output tile accumulates in VMEM across `bk` steps (sequential minor grid on
TPU). Block shapes default to MXU-aligned 128 multiples.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocking import floor_to_divisor
from repro.kernels.interpret import resolve_interpret


def _kernel(layer_ref, ids_ref, x_ref, w_ref, o_ref):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jnp.dot(x_ref[0], w_ref[0, 0], preferred_element_type=jnp.float32)
    o_ref[0] += acc


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_n", "block_k",
                                    "interpret"))
def super_gmm(layer_id: jax.Array, w: jax.Array, x: jax.Array,
              expert_ids: Optional[jax.Array] = None, *,
              block_c: int = 128, block_n: int = 128, block_k: int = 128,
              interpret: Optional[bool] = None) -> jax.Array:
    """out[e, c, n] = x[e, c, :] @ w[layer_id, expert_ids[e], :, :].

    layer_id:   [1] int32 (device-side scalar)
    w:          [L, n, K, N] stacked all-layer expert weights
    x:          [E, C, K] capacity buffers, one per expert served
    expert_ids: [E] int32 rows of `w` the buffers belong to (default:
                buffer e is expert e, which needs n == E)
    returns     [E, C, N] float32
    """
    L, n, K, N = w.shape
    E, C, Kx = x.shape
    assert Kx == K, (x.shape, w.shape)
    if expert_ids is None:
        assert n == E, (x.shape, w.shape)
        expert_ids = jnp.arange(E, dtype=jnp.int32)
    assert expert_ids.shape == (E,), (expert_ids.shape, x.shape)
    # round DOWN to a divisor (never min-clamp): a clamped block that does
    # not divide the dim silently misindexes the (C//bc, N//bn, K//bk) grid
    # for non-power-of-two dims
    bc = floor_to_divisor(C, block_c, what="super_gmm C")
    bn = floor_to_divisor(N, block_n, what="super_gmm N")
    bk = floor_to_divisor(K, block_k, what="super_gmm K")
    grid = (E, C // bc, N // bn, K // bk)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bc, bk),
                             lambda e, ci, ni, ki, layer, ids: (e, ci, ki)),
                pl.BlockSpec((1, 1, bk, bn),
                             lambda e, ci, ni, ki, layer, ids:
                             (layer[0], ids[e], ki, ni)),
            ],
            out_specs=pl.BlockSpec((1, bc, bn),
                                   lambda e, ci, ni, ki, layer, ids:
                                   (e, ci, ni)),
        ),
        out_shape=jax.ShapeDtypeStruct((E, C, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(layer_id, expert_ids.astype(jnp.int32), x, w)
