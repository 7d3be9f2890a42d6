"""Pallas TPU kernel: fused k-means assignment + accumulation step.

One grid pass over the point blocks computes, entirely in VMEM:
  d²(x, c) = ‖x‖² − 2·x@cᵀ + ‖c‖²  (MXU),
  labels   = argmin rows,
  sums    += one_hot(labels)ᵀ @ X    (MXU again),
  counts  += Σ one_hot(labels).

This is the fused "run-based aggregation" plan the paper credits for
matching scikit-learn's hand-written C++ k-means — adapted to the MXU:
both the distance matrix and the scatter-accumulate become matmuls.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, c_ref, sums_ref, counts_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...]                      # (B, d)
    c = c_ref[...]                      # (k, d)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)          # (B, 1)
    c2 = jnp.sum(c * c, axis=1, keepdims=True).T        # (1, k)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (B, k)
    d2 = x2 - 2.0 * xc + c2
    k = c.shape[0]
    lab = jnp.argmin(d2, axis=1)                        # (B,)
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], k), 1) == lab[:, None]
    ).astype(jnp.float32)
    sums_ref[...] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    counts_ref[...] += jnp.sum(onehot, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def kmeans_step_p(x: jax.Array, c: jax.Array, *, block_rows: int = 1024,
                  interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """x: (n, d) f32, c: (k, d) f32 → (sums (k, d), counts (k,))."""
    n, d = x.shape
    k = c.shape[0]
    assert n % block_rows == 0, (n, block_rows)
    nblocks = n // block_rows

    sums, counts = pl.pallas_call(
        _kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=interpret,
    )(x, c)
    return sums, counts[0]
