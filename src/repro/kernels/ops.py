"""Jit'd public wrappers around the Pallas kernels (+ jnp fallbacks).

Dispatch policy:
  * ``mode="pallas"``    — the Pallas kernel (``interpret=True`` on CPU only);
  * ``mode="chunked"``   — memory-efficient pure-jnp flash (lax.scan over kv
    blocks + remat): what train/serve steps use so the *compiled* HLO has
    O(S·d) attention footprint — this is the shape the dry-run measures;
  * ``mode="ref"``       — materialized oracle (small tests only).

The relational entry points (``fused_select_agg``, ``segsum_table``) adapt
VecTable blocks to kernel layout (pad → reshape to lanes).
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.expr import AggSpec, Expr
from . import grouped_join_agg as gja
from . import grouped_select_agg as gsa
from . import ref
from .flash_attention import flash_attention_p
from .fused_select_agg import LANES, fused_select_agg_p
from .kmeans_step import kmeans_step_p
from .segsum import segsum_p


# ---------------------------------------------------------------------------
# relational kernels
# ---------------------------------------------------------------------------


def _pad_rows(a: jax.Array, rows: int) -> jax.Array:
    pad = rows - a.shape[0]
    if pad == 0:
        return a
    return jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])


def fused_select_agg(table, pred: Expr, aggs: Sequence[AggSpec], *,
                     block_rows: int = 512, interpret: bool) -> Dict[str, jax.Array]:
    """VecTable → Single⟨aggs⟩ via the fused Pallas kernel."""
    names = tuple(sorted(set(pred.fields()) | {f for a in aggs for f in a.expr.fields()}))
    cap = table.capacity
    rows = -(-cap // LANES)  # ceil
    rows = -(-rows // block_rows) * block_rows
    total = rows * LANES

    def to_lanes(arr):
        return _pad_rows(arr, total).reshape(rows, LANES)

    cols = tuple(to_lanes(table.cols[n].astype(jnp.float32)
                          if jnp.issubdtype(table.cols[n].dtype, jnp.floating)
                          else table.cols[n]) for n in names)
    valid = to_lanes(table.valid)
    out = fused_select_agg_p(cols, valid, pred=pred, aggs=tuple(aggs), names=names,
                             block_rows=block_rows, interpret=interpret)
    # empty-selection min/max: map the kernel's finite sentinels back to ±inf
    out = jnp.where(out >= 3.0e38, jnp.inf, jnp.where(out <= -3.0e38, -jnp.inf, out))
    return {a.name: out[i] for i, a in enumerate(aggs)}


def _select_names(pred: Optional[Expr], keys: Sequence[str],
                  aggs: Sequence[AggSpec]) -> Tuple[str, ...]:
    """Columns the grouped select kernel reads, in kernel argument order."""
    agg_fields = {f for a in aggs for f in a.expr.fields()}
    pred_fields = set(pred.fields()) if pred is not None else set()
    return tuple(sorted(pred_fields | agg_fields | set(keys)))


def grouped_select_agg_fits(pred: Optional[Expr], keys: Sequence[str],
                            aggs: Sequence[AggSpec], num_buckets: int) -> bool:
    """Whether Mosaic compiles the grouped select kernel for this plan at the
    default block size: its estimated VMEM is within the scoped limit."""
    n_cols = len(_select_names(pred, keys, aggs))
    return (gsa.vmem_bytes(num_buckets, len(aggs), n_cols)
            <= gsa.VMEM_LIMIT_BYTES)


def grouped_select_agg(table, pred: Optional[Expr], keys: Sequence[str],
                       aggs: Sequence[AggSpec],
                       max_groups: int,
                       key_domains: Sequence[Tuple[int, int]],
                       num_buckets: int, *,
                       block_rows: int = gsa.BLOCK_ROWS, interpret: bool):
    """VecTable → Vec⟨keys+aggs⟩ via the fused Pallas kernel.

    One blockwise pass: fused predicate + dense-bucket accumulation
    (``vec.GroupAggDirect`` under ``use_kernels``).  The tiny per-bucket
    epilogue (cross-lane reduce, key decode, compaction to ``max_groups``)
    runs outside the kernel.
    """
    from ..relational import runtime as rt

    names = _select_names(pred, keys, aggs)
    cap = table.capacity
    rows = -(-cap // LANES)  # ceil
    rows = -(-rows // block_rows) * block_rows
    total = rows * LANES

    def to_lanes(arr):
        return _pad_rows(arr, total).reshape(rows, LANES)

    cols = tuple(to_lanes(table.cols[n].astype(jnp.float32)
                          if jnp.issubdtype(table.cols[n].dtype, jnp.floating)
                          else table.cols[n]) for n in names)
    valid = to_lanes(table.valid)
    key_specs = tuple((k, int(lo), int(hi) - int(lo) + 1)
                      for k, (lo, hi) in zip(keys, key_domains))
    lane_accs = gsa.grouped_select_agg_p(
        cols, valid, pred=pred, aggs=tuple(aggs), names=names,
        key_specs=key_specs, num_buckets=num_buckets,
        block_rows=block_rows, interpret=interpret)

    counts = jnp.sum(lane_accs[0], axis=1)[:num_buckets]
    out_cols = rt.decode_bucket_keys(keys, key_domains,
                                     [table.cols[k].dtype for k in keys],
                                     num_buckets)
    for j, a in enumerate(aggs):
        lane = lane_accs[j + 1]
        if a.fn in ("sum", "count"):
            red = jnp.sum(lane, axis=1)
        elif a.fn == "min":
            red = jnp.min(lane, axis=1)
        else:
            red = jnp.max(lane, axis=1)
        red = red[:num_buckets]
        if a.fn == "count":
            red = red.astype(jnp.int32)
        else:
            # empty-bucket min/max: finite kernel sentinels back to ±inf
            red = jnp.where(red >= 3.0e38, jnp.inf,
                            jnp.where(red <= -3.0e38, -jnp.inf, red))
        out_cols[a.name] = red
    buckets = rt.VecTable(out_cols, counts > 0)
    return rt.compact(buckets, max_groups)


def _join_names(left, right, left_on: Sequence[str], right_on: Sequence[str],
                keys: Sequence[str], aggs: Sequence[AggSpec],
                pred: Optional[Expr]) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """(probe, build) columns the join kernel reads, in argument order."""
    agg_fields = {f for a in aggs for f in a.expr.fields() if a.fn != "count"}
    pred_fields = set(pred.fields()) if pred is not None else set()
    rnames = tuple(sorted((set(keys) | agg_fields)
                          & (set(right.cols) - set(right_on))))
    lnames = tuple(sorted((pred_fields | set(left_on)
                           | ((set(keys) | agg_fields) & set(left.cols)))))
    return lnames, rnames


def grouped_join_agg_fits(left, right, *, left_on: Sequence[str],
                          right_on: Sequence[str], join_num_buckets: int,
                          keys: Sequence[str], aggs: Sequence[AggSpec],
                          num_buckets: int, pred: Optional[Expr]) -> bool:
    """Whether Mosaic compiles the join kernel for this plan at the default
    block size: its estimated VMEM is within the scoped limit."""
    lnames, rnames = _join_names(left, right, left_on, right_on, keys, aggs,
                                 pred)
    return (gja.vmem_bytes(join_num_buckets, num_buckets, len(aggs),
                           len(lnames), len(rnames))
            <= gsa.VMEM_LIMIT_BYTES)


def grouped_join_agg(left, right, *, left_on: Sequence[str],
                     right_on: Sequence[str],
                     join_key_domains: Sequence[Tuple[int, int]],
                     join_num_buckets: int, keys: Sequence[str],
                     aggs: Sequence[AggSpec], max_groups: int,
                     key_domains: Sequence[Tuple[int, int]],
                     num_buckets: int, pred: Optional[Expr] = None,
                     block_rows: int = gsa.BLOCK_ROWS, interpret: bool):
    """(probe VecTable, build VecTable) → Vec⟨keys+aggs⟩, one fused kernel.

    The whole select→join→group pipeline (``vec.FusedJoinGroupAgg`` under
    ``use_kernels``): the build side is condensed OUTSIDE the kernel into
    dense per-join-bucket tables (presence + one f32 value per needed
    column, duplicate keys → lowest row index, matching the unfused tiers);
    the kernel then runs predicate, probe, group-bucket derivation and all
    accumulators blockwise in a single pass — the join result is never
    materialized.  The tiny epilogue (cross-lane reduce, key decode,
    compaction to ``max_groups``) runs outside the kernel.
    """
    from ..relational import runtime as rt

    keys = tuple(keys)
    aggs = tuple(aggs)
    lnames, rnames = _join_names(left, right, left_on, right_on, keys, aggs,
                                 pred)

    cap = left.capacity
    rows = -(-cap // LANES)  # ceil
    rows = -(-rows // block_rows) * block_rows
    total = rows * LANES

    def to_lanes(arr):
        return _pad_rows(arr, total).reshape(rows, LANES)

    cols = tuple(to_lanes(left.cols[n].astype(jnp.float32)
                          if jnp.issubdtype(left.cols[n].dtype, jnp.floating)
                          else left.cols[n]) for n in lnames)
    valid = to_lanes(left.valid)

    # dense build tables over the join-bucket axis (first occurrence wins)
    nbj = int(join_num_buckets)
    nbj_pad = gsa.pad_buckets(nbj)
    cap_r = right.capacity
    rbid, rok = rt._bucket_ids_checked(right, right_on, join_key_domains)
    slot = jnp.where(rok & right.valid, rbid, nbj)
    ridx = jnp.full((nbj + 1,), cap_r, jnp.int32)
    ridx = ridx.at[slot].min(jnp.arange(cap_r, dtype=jnp.int32),
                             mode="drop")[:nbj]
    present_b = ridx < cap_r
    ridx_c = jnp.minimum(ridx, cap_r - 1)

    def to_table(arr):
        vals = jnp.where(present_b, arr[ridx_c].astype(jnp.float32), 0.0)
        return jnp.pad(vals, (0, nbj_pad - nbj))[:, None]

    present = to_table(present_b)
    rtabs = tuple(to_table(right.cols[n]) for n in rnames)

    jkey_specs = tuple((k, int(lo), int(hi) - int(lo) + 1)
                       for k, (lo, hi) in zip(left_on, join_key_domains))
    gkey_specs = tuple((k, int(lo), int(hi) - int(lo) + 1)
                       for k, (lo, hi) in zip(keys, key_domains))
    lane_accs = gja.grouped_join_agg_p(
        cols, valid, present, rtabs, pred=pred, aggs=aggs,
        lnames=lnames, rnames=rnames, jkey_specs=jkey_specs,
        gkey_specs=gkey_specs, num_join_buckets=nbj, num_buckets=num_buckets,
        block_rows=block_rows, interpret=interpret)

    counts = jnp.sum(lane_accs[0], axis=1)[:num_buckets]
    key_dtypes = [left.cols[k].dtype if k in left.cols else right.cols[k].dtype
                  for k in keys]
    out_cols = rt.decode_bucket_keys(keys, key_domains, key_dtypes, num_buckets)
    for j, a in enumerate(aggs):
        lane = lane_accs[j + 1]
        if a.fn in ("sum", "count"):
            red = jnp.sum(lane, axis=1)
        elif a.fn == "min":
            red = jnp.min(lane, axis=1)
        else:
            red = jnp.max(lane, axis=1)
        red = red[:num_buckets]
        if a.fn == "count":
            red = red.astype(jnp.int32)
        else:
            # empty-bucket min/max: finite kernel sentinels back to ±inf
            red = jnp.where(red >= 3.0e38, jnp.inf,
                            jnp.where(red <= -3.0e38, -jnp.inf, red))
        out_cols[a.name] = red
    buckets = rt.VecTable(out_cols, counts > 0)
    return rt.compact(buckets, max_groups)


def segsum(data: jax.Array, seg_ids: jax.Array, num_segments: int, *,
           block_rows: int = 512, interpret: bool) -> jax.Array:
    n, d = data.shape
    rows = -(-n // block_rows) * block_rows
    data_p = _pad_rows(data.astype(jnp.float32), rows)
    seg_p = jnp.concatenate([
        seg_ids.astype(jnp.int32),
        jnp.full((rows - n,), num_segments, jnp.int32),  # padded rows → dumped
    ]) if rows != n else seg_ids.astype(jnp.int32)
    out = segsum_p(data_p, seg_p, num_segments=num_segments + 1,
                   block_rows=block_rows, interpret=interpret)
    return out[:num_segments]


def kmeans_step(x: jax.Array, c: jax.Array, *, block_rows: int = 1024,
                interpret: bool) -> Tuple[jax.Array, jax.Array]:
    n, d = x.shape
    rows = -(-n // block_rows) * block_rows
    if rows != n:
        # pad with copies of the first centroid → corrected afterwards
        pad = rows - n
        x_p = jnp.concatenate([x, jnp.broadcast_to(c[0], (pad, d))])
        sums, counts = kmeans_step_p(x_p, c, block_rows=block_rows, interpret=interpret)
        sums = sums.at[0].add(-pad * c[0])
        counts = counts.at[0].add(-float(pad))
        return sums, counts
    return kmeans_step_p(x, c, block_rows=block_rows, interpret=interpret)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "sm_scale", "block_k", "policy", "unroll"),
)
def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      sm_scale: Optional[float] = None, block_k: int = 512,
                      policy: str = "remat", unroll: bool = False) -> jax.Array:
    """Memory-efficient GQA flash attention in pure jnp (scan over kv blocks).

    Differentiable; with remat the backward recomputes per-block logits so
    peak memory is O(S·d) instead of O(S²) — this is the attention the
    train/serve pipelines compile (and what the dry-run memory analysis
    sees).  Semantics identical to ``ref.flash_attention``.
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    bk = min(block_k, s)
    assert s % bk == 0
    nk = s // bk

    # matmuls run in the input dtype (bf16 on the MXU) with f32 accumulation;
    # the online-softmax state (m, l, acc) stays f32.  REPRO_ATTN_F32=1
    # restores the baseline all-f32 math (perf-iteration A/B attribution).
    out_dtype = q.dtype
    if os.environ.get("REPRO_ATTN_F32") == "1":
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(b, hkv, group, s, d)
    kf = k
    vf = v

    qpos = jnp.arange(s)

    def block(carry, ki):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(kf, ki * bk, bk, axis=2)   # (b,hkv,bk,d)
        vs = jax.lax.dynamic_slice_in_dim(vf, ki * bk, bk, axis=2)
        s_blk = jnp.einsum("bhgqd,bhkd->bhgqk", qf, ks,
                           preferred_element_type=jnp.float32)       # (b,hkv,g,s,bk)
        kpos = ki * bk + jnp.arange(bk)
        mask = jnp.ones((s, bk), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s_blk = jnp.where(mask, s_blk, -1.0e30)
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s_blk - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vs.dtype), vs,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    if policy == "remat":
        block = jax.checkpoint(block)

    init = (
        jnp.full((b, hkv, group, s), -1.0e30, jnp.float32),
        jnp.zeros((b, hkv, group, s), jnp.float32),
        jnp.zeros((b, hkv, group, s, d), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(block, init, jnp.arange(nk),
                                  unroll=nk if unroll else 1)
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).reshape(b, hq, s, d)
    return out.astype(out_dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              sm_scale: Optional[float] = None, mode: str = "chunked",
              interpret: Optional[bool] = None,
              unroll: bool = False) -> jax.Array:
    if mode == "pallas":
        if interpret is None:  # where the caller runs: the default backend
            from . import interpret_on

            interpret = interpret_on(jax.default_backend())
        return flash_attention_p(q, k, v, causal=causal, window=window,
                                 sm_scale=sm_scale, interpret=interpret)
    if mode == "chunked":
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 sm_scale=sm_scale, unroll=unroll)
    return ref.flash_attention(q, k, v, causal=causal, window=window, sm_scale=sm_scale)


def decode_attention(q, k_cache, v_cache, cache_len, *, sm_scale=None):
    return ref.decode_attention(q, k_cache, v_cache, cache_len, sm_scale=sm_scale)
