"""Token dispatch/combine kernels — TPU-native construction of the paper's
shared-buffer payloads (§3.2, Table 2 ② "tokens (hidden states)").

`dispatch_scatter` builds the [E·C(+1), d] expert capacity buffer from token
hidden states: grid is one row per routed (token, k) pair; scalar-prefetched
index vectors drive BOTH BlockSpec index_maps (source row = token id, dest row
= expert-buffer slot). This is the paper's "pre-calculated address indexing"
applied to payload placement: all offsets are computed ahead of the kernel,
the copy itself is indirection-only. Dropped pairs target the trash row E·C.
On real hardware the destination block of each row-write is the remote
device's shared buffer (Pallas `make_async_remote_copy`); in this repo the
buffer is local HBM and the remote hop is modeled in core/cost_model.py.

`combine_gather` is the inverse indirection (expert outputs back to
(token, k) order); the top-K weighted reduction happens in ops.py.

Row-granular grids are correct but DMA-latency-bound on real TPUs; ops.py
notes the production-shape alternative (block-sorted slots). Correctness is
what tests pin down here.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret


def _scatter_kernel(token_of_ref, slot_ref, x_ref, init_ref, o_ref):
    del token_of_ref, slot_ref, init_ref
    o_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("rows_out", "interpret"))
def dispatch_scatter(token_of: jax.Array, slot: jax.Array, x: jax.Array, *,
                     rows_out: int,
                     interpret: Optional[bool] = None) -> jax.Array:
    """out[slot[i]] = x[token_of[i]] for i in range(N); out has rows_out rows
    (last row is the drop target and must be ignored by the caller).

    token_of, slot: [N] int32; x: [T, d]."""
    N = token_of.shape[0]
    d = x.shape[1]
    init = jnp.zeros((rows_out, d), x.dtype)
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N,),
            in_specs=[
                pl.BlockSpec((1, d), lambda i, token_of, slot: (token_of[i], 0)),
                pl.BlockSpec((1, d), lambda i, token_of, slot: (slot[i], 0)),
            ],
            out_specs=pl.BlockSpec((1, d), lambda i, token_of, slot: (slot[i], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows_out, d), x.dtype),
        input_output_aliases={3: 0},  # zero-init buffer donated to output
        interpret=resolve_interpret(interpret),
    )(token_of, slot, x, init)


def _gather_kernel(slot_ref, y_ref, o_ref):
    del slot_ref
    o_ref[...] = y_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_gather(slot: jax.Array, yb: jax.Array, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """out[i] = yb[slot[i]]. slot: [N]; yb: [R, d] (row R-1 must be zeros —
    the drop target)."""
    N = slot.shape[0]
    d = yb.shape[1]
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N,),
            in_specs=[pl.BlockSpec((1, d), lambda i, slot: (slot[i], 0))],
            out_specs=pl.BlockSpec((1, d), lambda i, slot: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, d), yb.dtype),
        interpret=resolve_interpret(interpret),
    )(slot, yb)
