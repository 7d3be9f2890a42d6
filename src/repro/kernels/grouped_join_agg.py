"""Pallas TPU kernel: fused select + direct-table join + grouped aggregation.

The whole-pipeline operator (TPC-H Q3/Q12 shape): one blockwise pass over
the probe side evaluates the fused predicate, derives each row's JOIN
bucket id from its key columns (checked against the static joint key
domain), "gathers" the build-side payload through a one-hot reduction
against small dense per-bucket tables (scatter- and gather-free — the same
one-hot idiom ``grouped_select_agg`` uses for accumulation, run in reverse
for the lookup), then derives the GROUP bucket id over the joined columns
and accumulates every aggregate into per-bucket per-lane VMEM accumulators.
The join result is never materialized.

The build side is preprocessed OUTSIDE the kernel into dense tables over
the join-bucket axis (one f32 value per bucket per needed column, plus a
0/1 presence table); duplicate build keys resolve to the lowest row index,
matching the unfused tiers.  Build-side values ride through the one-hot
reduction in f32 — integral columns are exact up to 2^24, far beyond the
gated bucket budgets.

Layout matches ``grouped_select_agg``: probe columns reshaped to (R, 128)
lanes, the grid walks row-blocks, outputs are (NBG_pad, 128) lane
accumulators (count first, then one per agg).  The per-bucket build tables
are (NBJ_pad, 1) blocks — scalar-per-bucket side inputs, which Mosaic
pads to the lane width in VMEM.  Grid iterations on TPU are sequential, so
read-modify-write accumulation is safe.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.expr import AggSpec, Expr, evaluate
from .grouped_select_agg import (BLOCK_ROWS, LANES, ONE_HOT_COPIES,
                                 pad_buckets)

_NEG = -3.0e38
_POS = 3.0e38


def vmem_bytes(num_join_buckets: int, num_buckets: int, n_aggs: int,
               n_probe_cols: int, n_build_cols: int,
               block_rows: int = BLOCK_ROWS) -> int:
    """Estimated VMEM of one grid step: the one-hot blocks over the join and
    the group buckets, plus the accumulators, the probe blocks and the
    per-join-bucket build tables (each padded to the lane width), all
    double-buffered."""
    nbj, nbg = pad_buckets(num_join_buckets), pad_buckets(num_buckets)
    return 4 * LANES * (ONE_HOT_COPIES * (nbj + nbg) * block_rows
                        + 2 * (n_aggs + 1) * nbg
                        + 2 * (n_probe_cols + 1) * block_rows
                        + 2 * (n_build_cols + 1) * nbj)


def _kernel(pred: Optional[Expr], aggs: Tuple[AggSpec, ...],
            lnames: Tuple[str, ...], rnames: Tuple[str, ...],
            jkey_specs: Tuple[Tuple[str, int, int], ...],
            gkey_specs: Tuple[Tuple[str, int, int], ...], *refs):
    nl, nr = len(lnames), len(rnames)
    col_refs = refs[:nl]
    valid_ref = refs[nl]
    present_ref = refs[nl + 1]
    rtab_refs = refs[nl + 2:nl + 2 + nr]
    cnt_ref = refs[nl + 2 + nr]
    agg_refs = refs[nl + 3 + nr:]
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

    cols = {n: r[...] for n, r in zip(lnames, col_refs)}
    keep = valid_ref[...]
    if pred is not None:
        keep = keep & evaluate(pred, cols, jnp)

    # join bucket id per element, checked against the declared domain: an
    # out-of-domain probe key must NOT alias the clipped boundary bucket
    jbid = jnp.zeros_like(keep, jnp.int32)
    for name, lo, size in jkey_specs:
        v = cols[name].astype(jnp.int32) - lo
        keep = keep & (v >= 0) & (v < size)
        jbid = jbid * size + jnp.clip(v, 0, size - 1)

    # one-hot over the (static, padded) join-bucket axis: (NBJ_pad, B, L)
    nbj_pad = present_ref.shape[0]
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (nbj_pad, 1, 1), 0)
    memj = (jbid[None, :, :] == iota_j) & keep[None, :, :]

    # probe: present-bucket membership is the match mask, and each needed
    # build column is "gathered" by reducing its dense table through the
    # same one-hot (exactly one bucket contributes per element)
    present = present_ref[...][:, :, None]  # (NBJ_pad, 1, 1)
    keep = keep & (jnp.sum(jnp.where(memj, present, 0.0), axis=0) > 0.0)
    for n, r in zip(rnames, rtab_refs):
        tbl = r[...][:, :, None]  # (NBJ_pad, 1, 1)
        cols[n] = jnp.sum(jnp.where(memj, tbl, 0.0), axis=0)

    # group bucket id over the joined columns (post-join domain is exact by
    # construction, so the clip is the same as grouped_select_agg's)
    gbid = jnp.zeros_like(keep, jnp.int32)
    for name, lo, size in gkey_specs:
        v = jnp.clip(cols[name].astype(jnp.int32) - lo, 0, size - 1)
        gbid = gbid * size + v
    nbg_pad = cnt_ref.shape[0]
    iota_g = jax.lax.broadcasted_iota(jnp.int32, (nbg_pad, 1, 1), 0)
    member = (gbid[None, :, :] == iota_g) & keep[None, :, :]

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
    "pred", "aggs", "lnames", "rnames", "jkey_specs", "gkey_specs",
    "num_join_buckets", "num_buckets", "block_rows", "interpret"))
def grouped_join_agg_p(cols: Tuple[jax.Array, ...], valid: jax.Array,
                       present: jax.Array, rtabs: Tuple[jax.Array, ...], *,
                       pred: Optional[Expr], aggs: Tuple[AggSpec, ...],
                       lnames: Tuple[str, ...], rnames: Tuple[str, ...],
                       jkey_specs: Tuple[Tuple[str, int, int], ...],
                       gkey_specs: Tuple[Tuple[str, int, int], ...],
                       num_join_buckets: int, num_buckets: int,
                       block_rows: int = BLOCK_ROWS,
                       interpret: bool) -> Tuple[jax.Array, ...]:
    """cols: tuple of (R, 128) probe arrays; valid: (R, 128) bool;
    present/rtabs: (NBJ_pad, 1) f32 dense build tables.

    Returns lane accumulators ``(count, agg_0, ..., agg_k)`` each of shape
    (num_buckets_padded, 128) f32; callers cross-lane-reduce and slice to
    ``num_buckets``."""
    rows = valid.shape[0]
    assert rows % block_rows == 0, (rows, block_rows)
    nblocks = rows // block_rows
    nbj_pad = present.shape[0]
    nbg_pad = pad_buckets(num_buckets)

    in_specs = [
        pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
        for _ in range(len(cols) + 1)
    ] + [
        pl.BlockSpec((nbj_pad, 1), lambda i: (0, 0))
        for _ in range(len(rtabs) + 1)
    ]
    out_spec = pl.BlockSpec((nbg_pad, LANES), lambda i: (0, 0))
    out_shape = jax.ShapeDtypeStruct((nbg_pad, LANES), jnp.float32)

    return pl.pallas_call(
        functools.partial(_kernel, pred, aggs, lnames, rnames,
                          jkey_specs, gkey_specs),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=[out_spec] * (len(aggs) + 1),
        out_shape=[out_shape] * (len(aggs) + 1),
        interpret=interpret,
    )(*cols, valid, present, *rtabs)
