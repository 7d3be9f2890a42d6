"""Pallas TPU kernel: segment-sum as one-hot matmul (grouped aggregation).

The TPU-native replacement for hash aggregation: instead of scattering rows
into buckets (no efficient random scatter in VMEM), each row-block builds a
(B, K) one-hot of its segment ids and hits the MXU with
``one_hotᵀ @ data  →  (K, d)`` partials accumulated across the grid.
Arithmetic intensity scales with d, and the scatter becomes a systolic
matmul — the hardware-adaptation point of DESIGN.md §2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(k_total: int, x_ref, seg_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]              # (B, d)
    seg = seg_ref[...]          # (B, 1) int32
    b = x.shape[0]
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (b, k_total), 1) == seg
    ).astype(x.dtype)           # (B, K)
    out_ref[...] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("num_segments", "block_rows", "interpret"))
def segsum_p(data: jax.Array, seg_ids: jax.Array, *, num_segments: int,
             block_rows: int = 512, interpret: bool) -> jax.Array:
    """data: (n, d) f32; seg_ids: (n,) i32 in [0, num_segments). → (K, d)."""
    n, d = data.shape
    assert n % block_rows == 0, (n, block_rows)
    nblocks = n // block_rows

    out = pl.pallas_call(
        functools.partial(_kernel, num_segments),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_segments, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments, d), jnp.float32),
        interpret=interpret,
    )(data, seg_ids.reshape(n, 1))
    return out
