"""Pallas TPU kernel: fused select + dense-bucket grouped aggregation.

The grouped sibling of ``fused_select_agg`` (TPC-H Q1 shape): one blockwise
pass evaluates the fused predicate, derives each row's dense bucket id from
its key columns (static catalog-bounded domains), and accumulates every
aggregate into per-bucket per-lane VMEM accumulators — no sort, no gather,
no scatter.  Bucket membership is materialized as a one-hot over the
(static) bucket axis and reduced with masked sums/mins/maxes per block —
the same scatter-free idiom as the ``segsum`` one-hot matmul, extended to
min/max and a fused predicate.

Layout matches ``fused_select_agg``: each column reshaped to (R, 128)
lanes; the grid walks row-blocks; outputs are (NB_pad, 128) lane
accumulators (count first, then one per agg), cross-lane-reduced outside
the kernel.  Grid iterations on TPU are sequential, so read-modify-write
accumulation is safe.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.expr import AggSpec, Expr, evaluate

LANES = 128
_NEG = -3.0e38
_POS = 3.0e38

BLOCK_ROWS = 256
#: Mosaic's default scoped-VMEM limit on a TPU v5e; the kernel asks for no more
VMEM_LIMIT_BYTES = 16 * 1024 * 1024
#: (buckets, block_rows, lanes) f32 buffers the compiler may hold at once:
#: the one-hot membership and the masked operands reduced through it.  Fitted
#: to what Mosaic compiles for a v5e: the worst configuration measured needed
#: about 2.1 such buffers per bucket (one sum at 60 buckets); 3 leaves margin.
ONE_HOT_COPIES = 3


def pad_buckets(num_buckets: int) -> int:
    """Bucket rows of the accumulators, padded to whole (8, 128) tiles."""
    return max(8, -(-num_buckets // 8) * 8)


def vmem_bytes(num_buckets: int, n_aggs: int, n_cols: int,
               block_rows: int = BLOCK_ROWS) -> int:
    """Estimated VMEM of one grid step: the one-hot block over the buckets,
    plus the (count + aggregates) accumulators and the input blocks, both
    double-buffered."""
    nb = pad_buckets(num_buckets)
    return 4 * LANES * (ONE_HOT_COPIES * nb * block_rows
                        + 2 * (n_aggs + 1) * nb
                        + 2 * (n_cols + 1) * block_rows)


def _kernel(pred: Optional[Expr], aggs: Tuple[AggSpec, ...], names: Tuple[str, ...],
            key_specs: Tuple[Tuple[str, int, int], ...], nb: int, *refs):
    col_refs, valid_ref = refs[:len(names)], refs[len(names)]
    cnt_ref, agg_refs = refs[len(names) + 1], refs[len(names) + 2:]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        for j, a in enumerate(aggs):
            init = jnp.zeros_like(agg_refs[j])
            if a.fn == "min":
                init = jnp.full_like(agg_refs[j], _POS)
            elif a.fn == "max":
                init = jnp.full_like(agg_refs[j], _NEG)
            agg_refs[j][...] = init

    cols = {n: r[...] for n, r in zip(names, col_refs)}
    keep = valid_ref[...]
    if pred is not None:
        keep = keep & evaluate(pred, cols, jnp)

    # dense bucket id per element: lexicographic rank in the key domain
    bid = jnp.zeros_like(keep, jnp.int32)
    for name, lo, size in key_specs:
        v = jnp.clip(cols[name].astype(jnp.int32) - lo, 0, size - 1)
        bid = bid * size + v
    # one-hot over the (static, padded) bucket axis: (NB_pad, B, L)
    nb_pad = cnt_ref.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (nb_pad, 1, 1), 0)
    member = (bid[None, :, :] == iota) & keep[None, :, :]

    cnt_ref[...] += jnp.sum(member.astype(jnp.float32), axis=1)
    for j, a in enumerate(aggs):
        if a.fn == "count":
            agg_refs[j][...] += jnp.sum(member.astype(jnp.float32), axis=1)
            continue
        arr = evaluate(a.expr, cols, jnp).astype(jnp.float32)[None, :, :]
        if a.fn == "sum":
            agg_refs[j][...] += jnp.sum(jnp.where(member, arr, 0.0), axis=1)
        elif a.fn == "min":
            agg_refs[j][...] = jnp.minimum(
                agg_refs[j][...], jnp.min(jnp.where(member, arr, _POS), axis=1))
        elif a.fn == "max":
            agg_refs[j][...] = jnp.maximum(
                agg_refs[j][...], jnp.max(jnp.where(member, arr, _NEG), axis=1))
        else:
            raise ValueError(a.fn)


@functools.partial(jax.jit, static_argnames=(
    "pred", "aggs", "names", "key_specs", "num_buckets", "block_rows", "interpret"))
def grouped_select_agg_p(cols: Tuple[jax.Array, ...], valid: jax.Array, *,
                         pred: Optional[Expr], aggs: Tuple[AggSpec, ...],
                         names: Tuple[str, ...],
                         key_specs: Tuple[Tuple[str, int, int], ...],
                         num_buckets: int, block_rows: int = BLOCK_ROWS,
                         interpret: bool) -> Tuple[jax.Array, ...]:
    """cols: tuple of (R, 128) arrays; valid: (R, 128) bool.

    Returns lane accumulators ``(count, agg_0, ..., agg_k)`` each of shape
    (num_buckets_padded, 128) f32; callers cross-lane-reduce and slice to
    ``num_buckets``."""
    rows = valid.shape[0]
    assert rows % block_rows == 0, (rows, block_rows)
    nblocks = rows // block_rows
    nb_pad = pad_buckets(num_buckets)

    in_specs = [
        pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
        for _ in range(len(cols) + 1)
    ]
    out_spec = pl.BlockSpec((nb_pad, LANES), lambda i: (0, 0))
    out_shape = jax.ShapeDtypeStruct((nb_pad, LANES), jnp.float32)

    return pl.pallas_call(
        functools.partial(_kernel, pred, aggs, names, key_specs, num_buckets),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=[out_spec] * (len(aggs) + 1),
        out_shape=[out_shape] * (len(aggs) + 1),
        interpret=interpret,
    )(*cols, valid)
