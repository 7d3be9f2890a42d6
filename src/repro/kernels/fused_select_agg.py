"""Pallas TPU kernel: fused select + project + aggregate (TPC-H Q6 shape).

The single-pass pipeline JITQ compiles selective scan-aggregate queries
into.  Expressions (predicate + aggregated projections) are *compiled into
the kernel body* — the CVM lowering passes them as closure constants, so
each query gets its own specialized kernel, exactly like JITQ's per-pipeline
machine code.

Layout: each column is reshaped to (R, 128) lanes; the grid walks row-blocks
of ``block_rows`` sublanes; partial aggregates accumulate into a single
(8, 128)-padded VMEM output block (grid iterations on TPU are sequential, so
read-modify-write accumulation is safe).
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.expr import AggSpec, Expr, evaluate

LANES = 128
_NEG = -3.0e38
_POS = 3.0e38


def _kernel(pred: Expr, aggs: Tuple[AggSpec, ...], names: Tuple[str, ...], *refs):
    col_refs, valid_ref, out_ref = refs[:-2], refs[-2], refs[-1]
    i = pl.program_id(0)

    # one accumulator row per aggregate, updated through static row slices
    # of the output ref (Mosaic has no scatter, so no ``.at[j]`` updates)
    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        for j, a in enumerate(aggs):
            if a.fn in ("min", "max"):
                out_ref[j:j + 1, :] = jnp.full((1, LANES),
                                               _POS if a.fn == "min" else _NEG,
                                               jnp.float32)

    cols = {n: r[...] for n, r in zip(names, col_refs)}
    keep = valid_ref[...] & evaluate(pred, cols, jnp)

    for j, a in enumerate(aggs):
        row = out_ref[j:j + 1, :]
        if a.fn == "count":
            out_ref[j:j + 1, :] = row + jnp.sum(keep.astype(jnp.float32), axis=0,
                                                keepdims=True)
            continue
        arr = evaluate(a.expr, cols, jnp).astype(jnp.float32)
        if a.fn == "sum":
            part = jnp.sum(jnp.where(keep, arr, 0.0), axis=0, keepdims=True)
            out_ref[j:j + 1, :] = row + part
        elif a.fn == "min":
            part = jnp.min(jnp.where(keep, arr, _POS), axis=0, keepdims=True)
            out_ref[j:j + 1, :] = jnp.minimum(row, part)
        elif a.fn == "max":
            part = jnp.max(jnp.where(keep, arr, _NEG), axis=0, keepdims=True)
            out_ref[j:j + 1, :] = jnp.maximum(row, part)
        else:
            raise ValueError(a.fn)


@functools.partial(jax.jit, static_argnames=("pred", "aggs", "names", "block_rows", "interpret"))
def fused_select_agg_p(cols: Tuple[jax.Array, ...], valid: jax.Array, *,
                       pred: Expr, aggs: Tuple[AggSpec, ...], names: Tuple[str, ...],
                       block_rows: int = 512, interpret: bool) -> jax.Array:
    """cols: tuple of (R, 128) arrays; valid: (R, 128) bool. Returns (n_aggs,)."""
    rows = valid.shape[0]
    assert rows % block_rows == 0, (rows, block_rows)
    nblocks = rows // block_rows
    n_aggs = len(aggs)
    out_rows = max(8, n_aggs)

    in_specs = [
        pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
        for _ in range(len(cols) + 1)
    ]
    out_spec = pl.BlockSpec((out_rows, LANES), lambda i: (0, 0))

    lane_acc = pl.pallas_call(
        functools.partial(_kernel, pred, aggs, names),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.float32),
        interpret=interpret,
    )(*cols, valid)

    # final cross-lane reduction (tiny) outside the kernel
    outs = []
    for j, a in enumerate(aggs):
        lane = lane_acc[j]
        if a.fn in ("sum", "count"):
            outs.append(jnp.sum(lane))
        elif a.fn == "min":
            outs.append(jnp.min(lane))
        else:
            outs.append(jnp.max(lane))
    return jnp.stack(outs)
