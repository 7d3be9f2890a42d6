"""VecTable: the physical ``Vec⟨tuple⟩`` collection on JAX.

A VecTable is a struct-of-arrays block with a static capacity and a
validity mask.  All relational operators are pure functions VecTable →
VecTable with static output shapes (XLA requirement); cardinality lives in
the mask.  This file is the executable meaning of the ``vec.*`` IR flavor.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.expr import AggSpec, Expr, evaluate

_I64_MAX = np.iinfo(np.int64).max
_F32_INF = np.float32(np.inf)


@jax.tree_util.register_pytree_node_class
@dataclass
class VecTable:
    cols: Dict[str, jax.Array]
    valid: jax.Array  # bool (cap,)

    # -- pytree ------------------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.cols))
        return tuple(self.cols[n] for n in names) + (self.valid,), names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(cols=dict(zip(names, children[:-1])), valid=children[-1])

    # -- basics ------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    def count(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32))

    @staticmethod
    def from_numpy(data: Mapping[str, np.ndarray], capacity: Optional[int] = None) -> "VecTable":
        """Pad host columns to ``capacity`` and put them on the default device."""
        return jax.device_put(VecTable.padded_host(data, capacity))

    @staticmethod
    def padded_host(data: Mapping[str, np.ndarray],
                    capacity: Optional[int] = None) -> "VecTable":
        """Host columns padded to ``capacity``, still numpy: a backend that
        places its inputs itself puts them on its devices from here."""
        n = len(next(iter(data.values())))
        cap = capacity or n
        if cap < n:
            raise ValueError(f"capacity {cap} < rows {n}")
        cols = {}
        for k, v in data.items():
            v = np.asarray(v)
            pad = np.zeros((cap - n,) + v.shape[1:], dtype=v.dtype)
            cols[k] = np.concatenate([v, pad])
        return VecTable(cols, np.arange(cap) < n)

    def to_numpy(self) -> Dict[str, np.ndarray]:
        mask = np.asarray(self.valid)
        return {k: np.asarray(v)[mask] for k, v in self.cols.items()}

    def astuple_cols(self, names: Sequence[str]) -> List[jax.Array]:
        return [self.cols[n] for n in names]


# ---------------------------------------------------------------------------
# operators (pure functions — the vec.* flavor semantics)
# ---------------------------------------------------------------------------


def mask_select(t: VecTable, pred: Expr) -> VecTable:
    """Predicated (late-materialized) selection: narrow the mask only."""
    p = evaluate(pred, t.cols, jnp)
    return VecTable(t.cols, t.valid & p)


def proj(t: VecTable, names: Sequence[str]) -> VecTable:
    return VecTable({n: t.cols[n] for n in names}, t.valid)


def exproj(t: VecTable, exprs: Sequence[Tuple[str, Expr]]) -> VecTable:
    cap = t.capacity
    out = {}
    for name, e in exprs:
        v = evaluate(e, t.cols, jnp)
        if jnp.ndim(v) == 0:
            v = jnp.full((cap,), v)
        out[name] = v
    return VecTable(out, t.valid)


def _masked(fn: str, arr: jax.Array, valid: jax.Array) -> jax.Array:
    if fn == "count":
        return jnp.sum(valid.astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32))
    if jnp.issubdtype(arr.dtype, jnp.integer) or jnp.issubdtype(arr.dtype, jnp.bool_):
        arr = arr.astype(jnp.float32)
    if fn == "sum":
        return jnp.sum(jnp.where(valid, arr, 0))
    if fn == "min":
        return jnp.min(jnp.where(valid, arr, _F32_INF))
    if fn == "max":
        return jnp.max(jnp.where(valid, arr, -_F32_INF))
    raise ValueError(fn)


def aggr(t: VecTable, aggs: Sequence[AggSpec]) -> Dict[str, jax.Array]:
    """Masked scalar aggregation → Single⟨aggs⟩ (dict of scalars)."""
    out = {}
    for a in aggs:
        arr = evaluate(a.expr, t.cols, jnp) if a.fn != "count" else t.valid
        if jnp.ndim(arr) == 0:
            arr = jnp.full((t.capacity,), arr)
        out[a.name] = _masked(a.fn, arr, t.valid)
    return out


def combine_partials(partials: Sequence[Dict[str, jax.Array]], aggs: Sequence[AggSpec]) -> Dict[str, jax.Array]:
    out = {}
    for a in aggs:
        vals = jnp.stack([p[a.name] for p in partials])
        fn = a.combine_fn
        out[a.name] = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[fn](vals)
    return out


def _sort_perm(t: VecTable, keys: Sequence[str], ascending: Sequence[bool]) -> jax.Array:
    """Permutation: valid rows first, ordered by keys (stable)."""
    arrays = []
    for k, asc in zip(reversed(list(keys)), reversed(list(ascending))):
        arr = t.cols[k]
        if not asc:
            if jnp.issubdtype(arr.dtype, jnp.bool_):
                arr = ~arr
            else:
                arr = -arr.astype(jnp.float32) if not jnp.issubdtype(arr.dtype, jnp.integer) else -arr
        arrays.append(arr)
    arrays.append(~t.valid)  # primary: valid first
    return jnp.lexsort(tuple(arrays), axis=0)


def sort_by_key(t: VecTable, keys: Sequence[str], ascending: Optional[Sequence[bool]] = None) -> VecTable:
    asc = list(ascending or [True] * len(keys))
    perm = _sort_perm(t, keys, asc)
    return VecTable({k: v[perm] for k, v in t.cols.items()}, t.valid[perm])


def compact(t: VecTable, max_count: Optional[int] = None) -> VecTable:
    """Densify valid rows to the front — O(n) prefix-sum scatter.

    Position of each valid row is its prefix count of valid rows; rows
    beyond ``max_count`` (and all invalid rows) scatter out of bounds and
    are dropped.  Replaces the old argsort(~valid) shuffle (O(n log n)).
    """
    out_cap = int(max_count) if max_count is not None else t.capacity
    valid_i = t.valid.astype(jnp.int32)
    pos = jnp.cumsum(valid_i) - 1
    idx = jnp.where(t.valid, pos, out_cap)  # invalid rows → out of bounds
    n = jnp.minimum(jnp.sum(valid_i), out_cap)

    def scatter(col: jax.Array) -> jax.Array:
        out = jnp.zeros((out_cap,) + col.shape[1:], col.dtype)
        return out.at[idx].set(col, mode="drop")

    cols = {k: scatter(v) for k, v in t.cols.items()}
    valid = jnp.arange(out_cap) < n
    return VecTable(cols, valid)


def dict_encode(t: VecTable, cols: Sequence[str], modes: Sequence[str],
                tables: Sequence, lows: Sequence[int],
                cards: Sequence[int]) -> VecTable:
    """Per-column value→rank encoding against static sorted dictionaries.

    ``mode == "remap"``: one gather through a span-sized rank table whose
    out-of-dictionary slots already hold the sentinel.  Otherwise a
    searchsorted rank lookup.  Out-of-dictionary values (possible on join
    probe sides) get the sentinel rank ``card`` — one past every declared
    rank domain, so downstream direct tables treat them as out-of-domain
    rather than aliasing a real bucket.
    """
    out = dict(t.cols)
    for c, mode, table, lo, card in zip(cols, modes, tables, lows, cards):
        arr = t.cols[c]
        tab = jnp.asarray(table)
        if mode == "remap":
            span = tab.shape[0]
            idx = arr.astype(jnp.int32) - jnp.int32(lo)
            ok = (idx >= 0) & (idx < span)
            ranks = tab[jnp.clip(idx, 0, span - 1)]
            out[c] = jnp.where(ok, ranks, jnp.int32(card)).astype(jnp.int32)
        else:
            tab = tab.astype(arr.dtype)
            i = jnp.searchsorted(tab, arr)
            ic = jnp.clip(i, 0, card - 1)
            out[c] = jnp.where(tab[ic] == arr, ic,
                               jnp.int32(card)).astype(jnp.int32)
    return VecTable(out, t.valid)


def dict_decode(t: VecTable, cols: Sequence[str], tables: Sequence) -> VecTable:
    """Gather ranks back to raw values through the sorted value tables.

    Sentinel/invalid ranks clip to the last dictionary entry — such rows
    are already masked out by validity."""
    out = dict(t.cols)
    for c, table in zip(cols, tables):
        tab = jnp.asarray(table)
        ranks = jnp.clip(t.cols[c].astype(jnp.int32), 0, tab.shape[0] - 1)
        out[c] = tab[ranks]
    return VecTable(out, t.valid)


#: composite-key packings with more buckets than this raise instead of
#: silently colliding in the 32-bit accumulator
_PACK_LIMIT = 1 << 31


def _composite_key(t: VecTable, keys: Sequence[str],
                   key_domains: Optional[Sequence[Tuple[int, int]]] = None,
                   lows: Optional[Sequence[jax.Array]] = None,
                   sizes: Optional[Sequence[jax.Array]] = None) -> jax.Array:
    """Pack key columns into one i32, preserving lexicographic order.

    Packing needs per-column value bounds.  Three sources, in order:
    static ``key_domains`` from the catalog (checked against the 32-bit
    budget — overpacking raises instead of colliding); dynamic
    ``lows``/``sizes`` traced from the data (collision-free whenever the
    actual domain product fits 32 bits); neither → single column only.
    """
    if key_domains is not None:
        n_buckets = 1
        for lo, hi in key_domains:
            n_buckets *= int(hi) - int(lo) + 1
        if n_buckets > _PACK_LIMIT:
            raise ValueError(
                f"composite key domain for {tuple(keys)} has {n_buckets} "
                f"buckets and cannot be packed into a 32-bit accumulator; "
                "reduce the key domain or use a single integer key column")
        acc = jnp.zeros((t.capacity,), jnp.int32)
        for k, (lo, hi) in zip(keys, key_domains):
            size = int(hi) - int(lo) + 1
            arr = _int_key(t.cols[k])
            arr = jnp.clip(arr - jnp.int32(lo), 0, size - 1)
            acc = acc * jnp.int32(size) + arr
        return acc
    if lows is not None and sizes is not None:
        acc = jnp.zeros((t.capacity,), jnp.int32)
        for k, lo, size in zip(keys, lows, sizes):
            arr = _int_key(t.cols[k])
            acc = acc * size.astype(jnp.int32) + (arr - lo.astype(jnp.int32))
        return acc
    if len(keys) == 1:
        return _int_key(t.cols[keys[0]])
    raise ValueError(
        f"cannot pack composite key {tuple(keys)} without per-column domain "
        "bounds; provide catalog key domains (see Catalog.stats) or derive "
        "dynamic bounds from the data")


def _int_key(arr: jax.Array) -> jax.Array:
    if jnp.issubdtype(arr.dtype, jnp.floating):
        arr = arr.view(jnp.int32) if arr.dtype == jnp.float32 else arr.astype(jnp.int32)
    return arr.astype(jnp.int32)


def _key_change(t: VecTable, keys: Sequence[str]) -> jax.Array:
    """Per-row "starts a new group" flags for a key-sorted block.

    Per-column comparison against the previous row — collision-free for any
    key dtype, domain, and column count (unlike composite-key packing)."""
    change = jnp.zeros((t.capacity,), bool).at[0].set(True)
    for k in keys:
        col = t.cols[k]
        change = change | (col != jnp.concatenate([col[:1], col[:-1]]))
    return change & t.valid


def group_agg_sorted(t: VecTable, keys: Sequence[str], aggs: Sequence[AggSpec],
                     max_groups: int) -> VecTable:
    """Grouped aggregation over a key-sorted block via segment reduction.

    The TPU-native replacement of hash aggregation: valid rows are sorted by
    key (invalid at the end), segment ids are the prefix count of key
    changes, and each agg is a masked ``jax.ops.segment_*``.
    """
    change = _key_change(t, keys)
    seg = jnp.cumsum(change.astype(jnp.int32)) - 1  # -1 before first valid group
    seg = jnp.where(t.valid, seg, max_groups)  # dump invalid rows
    seg = jnp.clip(seg, 0, max_groups)

    out_cols: Dict[str, jax.Array] = {}
    for k in keys:
        out_cols[k] = jax.ops.segment_max(
            jnp.where(t.valid, t.cols[k], jnp.zeros((), t.cols[k].dtype)),
            seg, num_segments=max_groups + 1)[:max_groups]
    for a in aggs:
        red = _segment_agg(a, t.cols, t.valid, seg, max_groups + 1)[:max_groups]
        out_cols[a.name] = red
    n_groups = jnp.sum(change.astype(jnp.int32))
    group_valid = jnp.arange(max_groups) < n_groups
    return VecTable(out_cols, group_valid)


#: rows whose values one float accumulator adds up in :func:`_segment_sum`
_SUM_CHUNK_ROWS = 4096
#: bound on the (chunks × segments) table of partial sums
_SUM_PARTIALS_MAX = 1 << 22


def _segment_sum(x: jax.Array, seg: jax.Array, num_segments: int) -> jax.Array:
    """``segment_sum`` with short float accumulation chains.

    A scatter-add adds each segment's rows one after another, so a float32
    sum of millions of rows into a few segments loses its low digits (TPC-H
    Q1 at ~1M rows per group misses rtol 2e-4).  Rows are cut into chunks,
    each chunk is scatter-added into its own row of a (chunks, segments)
    table, and the partials are then summed across chunks."""
    n = x.shape[0]
    chunks = min(n // _SUM_CHUNK_ROWS, _SUM_PARTIALS_MAX // num_segments)
    if chunks <= 1 or not jnp.issubdtype(x.dtype, jnp.floating):
        return jax.ops.segment_sum(x, seg, num_segments=num_segments)
    rows = -(-n // chunks)
    ids = jnp.arange(n, dtype=jnp.int32) // rows * num_segments + seg
    part = jax.ops.segment_sum(x, ids, num_segments=chunks * num_segments)
    return part.reshape(chunks, num_segments).sum(axis=0)


def _segment_agg(a: AggSpec, cols: Mapping[str, jax.Array], valid: jax.Array,
                 seg: jax.Array, num_segments: int) -> jax.Array:
    """One masked segment reduction (shared by the sorted and direct tiers)."""
    if a.fn == "count":
        return jax.ops.segment_sum(valid.astype(jnp.int32), seg,
                                   num_segments=num_segments)
    arr = evaluate(a.expr, cols, jnp)
    if jnp.issubdtype(arr.dtype, jnp.integer) or jnp.issubdtype(arr.dtype, jnp.bool_):
        arr = arr.astype(jnp.float32)
    if a.fn == "sum":
        return _segment_sum(jnp.where(valid, arr, 0), seg, num_segments)
    if a.fn == "min":
        return jax.ops.segment_min(jnp.where(valid, arr, _F32_INF), seg,
                                   num_segments=num_segments)
    if a.fn == "max":
        return jax.ops.segment_max(jnp.where(valid, arr, -_F32_INF), seg,
                                   num_segments=num_segments)
    raise ValueError(a.fn)


def bucket_ids(t: VecTable, keys: Sequence[str],
               key_domains: Sequence[Tuple[int, int]]) -> jax.Array:
    """Dense bucket id per row: lexicographic rank in the static key domain."""
    acc = jnp.zeros((t.capacity,), jnp.int32)
    for k, (lo, hi) in zip(keys, key_domains):
        size = int(hi) - int(lo) + 1
        arr = jnp.clip(_int_key(t.cols[k]) - jnp.int32(lo), 0, size - 1)
        acc = acc * jnp.int32(size) + arr
    return acc


def decode_bucket_keys(keys: Sequence[str], key_domains: Sequence[Tuple[int, int]],
                       dtypes: Sequence[Any], num_buckets: int) -> Dict[str, jax.Array]:
    """Key column values for each dense bucket id (inverse of bucket_ids)."""
    b = jnp.arange(num_buckets, dtype=jnp.int32)
    sizes = [int(hi) - int(lo) + 1 for lo, hi in key_domains]
    out: Dict[str, jax.Array] = {}
    stride = num_buckets
    for k, (lo, _), size, dt in zip(keys, key_domains, sizes, dtypes):
        stride //= size
        vals = (b // stride) % size + jnp.int32(lo)
        out[k] = vals.astype(dt)
    return out


def group_agg_direct(t: VecTable, keys: Sequence[str], aggs: Sequence[AggSpec],
                     max_groups: int, key_domains: Sequence[Tuple[int, int]],
                     num_buckets: int, pred: Optional[Expr] = None) -> VecTable:
    """Grouped aggregation WITHOUT sorting: dense-bucket segment reduction.

    When the catalog bounds the composite key domain, every row's group is a
    static function of its key values — segment-reduce straight into
    ``num_buckets`` dense buckets (O(n), no lexsort, no per-column gather),
    then prefix-sum-compact the non-empty buckets to ``max_groups``.  Bucket
    order is lexicographic key order, so the output matches
    ``sort_by_key + group_agg_sorted`` row for row.  An optional fused
    predicate narrows validity in the same pass (MaskSelect fusion).
    """
    valid = t.valid
    if pred is not None:
        valid = valid & evaluate(pred, t.cols, jnp)
    bid = bucket_ids(t, keys, key_domains)
    seg = jnp.where(valid, bid, num_buckets)  # dump invalid rows

    counts = jax.ops.segment_sum(valid.astype(jnp.int32), seg,
                                 num_segments=num_buckets + 1)[:num_buckets]
    out_cols = decode_bucket_keys(keys, key_domains,
                                  [t.cols[k].dtype for k in keys], num_buckets)
    for a in aggs:
        out_cols[a.name] = _segment_agg(a, t.cols, valid, seg,
                                        num_buckets + 1)[:num_buckets]
    buckets = VecTable(out_cols, counts > 0)
    return compact(buckets, max_groups)


def merge_join_sorted(left: VecTable, right: VecTable, left_on: Sequence[str],
                      right_on: Sequence[str], max_count: int,
                      key_domains: Optional[Sequence[Tuple[int, int]]] = None,
                      ) -> VecTable:
    """PK-FK inner equi-join: ``right`` must be key-sorted with unique keys.

    searchsorted + gather — the TPU-native rewrite of Build/ProbeHTable.
    Multi-column keys are packed with catalog ``key_domains`` when the
    lowering provides them (static overflow check — overpacking raises),
    otherwise with bounds traced jointly from both sides (collision-free
    whenever the actual domain product fits the 32-bit accumulator).
    """
    if len(left_on) != 1 or len(right_on) != 1:
        if key_domains is not None:
            lk = _composite_key(left, left_on, key_domains=key_domains)
            rk = _composite_key(right, right_on, key_domains=key_domains)
        else:
            lows, sizes = _joint_key_bounds(left, right, left_on, right_on)
            lk = _composite_key(left, left_on, lows=lows, sizes=sizes)
            rk = _composite_key(right, right_on, lows=lows, sizes=sizes)
    else:
        lk = left.cols[left_on[0]].astype(jnp.int32)
        rk = right.cols[right_on[0]].astype(jnp.int32)
    sentinel = jnp.iinfo(jnp.int32).max
    rk = jnp.where(right.valid, rk, sentinel)
    idx = jnp.searchsorted(rk, lk)
    idx_c = jnp.clip(idx, 0, right.capacity - 1)
    match = (rk[idx_c] == lk) & left.valid

    out = dict(left.cols)
    lnames = set(left.cols)
    for k, v in right.cols.items():
        if k in right_on:
            continue
        name = k if k not in lnames else k + "_r"
        out[name] = v[idx_c]
    joined = VecTable(out, match)
    if max_count != left.capacity:
        joined = compact(joined, max_count)
    return joined


def _bucket_ids_checked(t: VecTable, keys: Sequence[str],
                        key_domains: Sequence[Tuple[int, int]],
                        ) -> Tuple[jax.Array, jax.Array]:
    """Dense bucket id per row + an in-domain mask.

    Unlike :func:`bucket_ids` (which clips — fine for grouping, where the
    catalog domains are exact by construction), joins must KNOW whether a
    key was inside the declared domain: a clipped out-of-domain probe key
    would silently alias the boundary bucket and fabricate a match.
    """
    acc = jnp.zeros((t.capacity,), jnp.int32)
    ok = jnp.ones((t.capacity,), bool)
    for k, (lo, hi) in zip(keys, key_domains):
        size = int(hi) - int(lo) + 1
        arr = _int_key(t.cols[k]) - jnp.int32(lo)
        ok = ok & (arr >= 0) & (arr < size)
        acc = acc * jnp.int32(size) + jnp.clip(arr, 0, size - 1)
    return acc, ok


def _direct_probe(left: VecTable, right: VecTable, right_on: Sequence[str],
                  num_buckets: int, lbid: jax.Array, lok: jax.Array,
                  rbid: jax.Array, rok: jax.Array,
                  columns: Optional[Sequence[str]] = None) -> VecTable:
    """Dense direct-table probe shared by the hash-join tiers.

    Build: scatter each valid right row's index into its key bucket with a
    ``min`` combiner — deterministic under duplicate build keys (the lowest
    row index wins, matching searchsorted's first occurrence).  Probe: one
    O(1) gather per left row.  Bucket ids are collision-free within the
    domain (bijective packing), so no key re-verification is needed; rows
    outside the domain are masked via ``lok``/``rok``.  Output rows stay at
    ``left.capacity`` (caller compacts).  ``columns`` optionally restricts
    which right columns are gathered (fusion gathers only what the
    downstream aggregation reads).
    """
    cap_r = right.capacity
    slot = jnp.where(rok & right.valid, rbid, num_buckets)
    table = jnp.full((num_buckets + 1,), cap_r, jnp.int32)
    table = table.at[slot].min(jnp.arange(cap_r, dtype=jnp.int32), mode="drop")
    idx = table[jnp.clip(lbid, 0, num_buckets - 1)]
    match = left.valid & lok & (idx < cap_r)
    idx_c = jnp.minimum(idx, cap_r - 1)
    out = dict(left.cols)
    lnames = set(left.cols)
    for k, v in right.cols.items():
        if k in right_on or (columns is not None and k not in columns):
            continue
        name = k if k not in lnames else k + "_r"
        out[name] = v[idx_c]
    return VecTable(out, match)


def hash_join_direct(left: VecTable, right: VecTable, left_on: Sequence[str],
                     right_on: Sequence[str], max_count: int,
                     key_domains: Optional[Sequence[Tuple[int, int]]] = None,
                     num_buckets: Optional[int] = None) -> VecTable:
    """Sort-free PK-FK inner equi-join via a dense direct table.

    The O(n) sibling of :func:`merge_join_sorted` — no sort of the build
    side, no searchsorted: when the composite key domain is bounded, the
    build side scatters into a dense table indexed by bucket id and every
    probe is a single gather (the dense-bucket analogue of BuildHTable /
    ProbeHTable, exactly as GroupAggDirect is to hash aggregation).

    Two variants:

    * static ``key_domains`` (catalog-derived): bucket ids are checked
      against the declared domain, out-of-domain rows never match;
    * dynamic (``key_domains=None``): per-column bounds are traced jointly
      from both sides; when the traced domain product exceeds the static
      ``num_buckets`` budget the instruction falls back to the sorted merge
      join *inside* the trace (``lax.cond``), so the plan stays valid for
      any data.
    """
    if key_domains is not None:
        nb = 1
        for lo, hi in key_domains:
            nb *= int(hi) - int(lo) + 1
        lbid, lok = _bucket_ids_checked(left, left_on, key_domains)
        rbid, rok = _bucket_ids_checked(right, right_on, key_domains)
        joined = _direct_probe(left, right, right_on, nb, lbid, lok, rbid, rok)
        if max_count != left.capacity:
            joined = compact(joined, max_count)
        return joined

    if num_buckets is None:
        raise ValueError("hash_join_direct without key_domains needs a "
                         "static num_buckets budget")
    nb = int(num_buckets)
    lows, sizes = _joint_key_bounds(left, right, left_on, right_on)
    prod = jnp.ones((), jnp.float32)
    for s in sizes:
        prod = prod * s.astype(jnp.float32)  # f32: no i32 overflow on product
    fits = prod <= jnp.float32(nb)

    def _dyn_bid(t: VecTable, keys: Sequence[str]) -> jax.Array:
        acc = jnp.zeros((t.capacity,), jnp.int32)
        for k, lo, size in zip(keys, lows, sizes):
            arr = _int_key(t.cols[k]) - lo.astype(jnp.int32)
            acc = acc * size.astype(jnp.int32) \
                + jnp.clip(arr, 0, size.astype(jnp.int32) - 1)
        return acc

    def _direct(args):
        l, r = args
        # joint bounds cover every valid row of both sides by construction
        lbid = _dyn_bid(l, left_on)
        rbid = _dyn_bid(r, right_on)
        lok = jnp.ones((l.capacity,), bool)
        rok = jnp.ones((r.capacity,), bool)
        return _direct_probe(l, r, right_on, nb, lbid, lok, rbid, rok)

    def _sorted(args):
        l, r = args
        rs = sort_by_key(r, right_on)
        return merge_join_sorted(l, rs, left_on, right_on, l.capacity)

    joined = jax.lax.cond(fits, _direct, _sorted, (left, right))
    if max_count != left.capacity:
        joined = compact(joined, max_count)
    return joined


def fused_join_group_agg(left: VecTable, right: VecTable,
                         left_on: Sequence[str], right_on: Sequence[str],
                         join_key_domains: Sequence[Tuple[int, int]],
                         join_num_buckets: int, keys: Sequence[str],
                         aggs: Sequence[AggSpec], max_groups: int,
                         key_domains: Sequence[Tuple[int, int]],
                         num_buckets: int, pred: Optional[Expr] = None,
                         ) -> VecTable:
    """Whole-pipeline select→join→group in one pass, join never materialized.

    Predicate, direct-table probe, bucket id and all accumulators are
    computed per input row; only the right columns the grouping actually
    reads are gathered, and the joined rows go straight into the dense
    grouped reduction without an intermediate compact.
    """
    valid = left.valid
    if pred is not None:
        valid = valid & evaluate(pred, left.cols, jnp)
    lbid, lok = _bucket_ids_checked(left, left_on, join_key_domains)
    rbid, rok = _bucket_ids_checked(right, right_on, join_key_domains)
    needed = set(keys)
    for a in aggs:
        if a.fn != "count":
            needed.update(a.expr.fields())
    joined = _direct_probe(VecTable(left.cols, valid), right, right_on,
                           join_num_buckets, lbid, lok, rbid, rok,
                           columns=sorted(needed))
    return group_agg_direct(joined, keys, aggs, max_groups, key_domains,
                            num_buckets)


def _joint_key_bounds(left: VecTable, right: VecTable, left_on: Sequence[str],
                      right_on: Sequence[str]) -> Tuple[List[jax.Array], List[jax.Array]]:
    """Shared per-column (lo, size) over the valid rows of BOTH join sides —
    packing must agree across sides or equal keys stop matching."""
    big = jnp.iinfo(jnp.int32).max
    lows, sizes = [], []
    for lk, rk in zip(left_on, right_on):
        la, ra = _int_key(left.cols[lk]), _int_key(right.cols[rk])
        lo = jnp.minimum(jnp.min(jnp.where(left.valid, la, big)),
                         jnp.min(jnp.where(right.valid, ra, big)))
        hi = jnp.maximum(jnp.max(jnp.where(left.valid, la, -big)),
                         jnp.max(jnp.where(right.valid, ra, -big)))
        lows.append(lo)
        sizes.append(jnp.maximum(hi - lo + 1, 1))
    return lows, sizes


def topk(t: VecTable, keys: Sequence[str], ascending: Sequence[bool], k: int) -> VecTable:
    if len(keys) == 1 and not jnp.issubdtype(t.cols[keys[0]].dtype, jnp.bool_):
        # single numeric key: jax.lax.top_k over a validity-masked score
        # instead of a full lexsort + gather.  top_k breaks ties by lowest
        # index, matching the stable sort.  Ascending ints flip via bitwise
        # NOT (~x = -x-1): strictly decreasing over the FULL int32 range,
        # unlike negation which overflows at INT32_MIN.  (A valid key whose
        # score equals the sentinel can still lose its slot to an earlier
        # invalid row; the sort path remains the general-purpose tier.)
        arr = t.cols[keys[0]]
        k_eff = min(int(k), t.capacity)
        if jnp.issubdtype(arr.dtype, jnp.integer):
            sentinel = jnp.iinfo(jnp.int32).min
            score = jnp.invert(arr.astype(jnp.int32)) if ascending[0] else arr.astype(jnp.int32)
        else:
            sentinel = -_F32_INF
            score = jnp.negative(arr) if ascending[0] else arr
        score = jnp.where(t.valid, score, sentinel)
        _, idx = jax.lax.top_k(score, k_eff)
        return VecTable({kk: v[idx] for kk, v in t.cols.items()}, t.valid[idx])
    s = sort_by_key(t, keys, ascending)
    return VecTable({kk: v[:k] for kk, v in s.cols.items()}, s.valid[:k])


def concat(tables: Sequence[VecTable]) -> VecTable:
    cols = {k: jnp.concatenate([t.cols[k] for t in tables]) for k in tables[0].cols}
    valid = jnp.concatenate([t.valid for t in tables])
    return VecTable(cols, valid)


def split(t: VecTable, n: int) -> List[VecTable]:
    cap = t.capacity
    if cap % n != 0:
        raise ValueError(f"capacity {cap} not divisible by {n}")
    c = cap // n
    return [
        VecTable({k: v[i * c:(i + 1) * c] for k, v in t.cols.items()},
                 t.valid[i * c:(i + 1) * c])
        for i in range(n)
    ]


def limit(t: VecTable, k: int) -> VecTable:
    c = compact(t)
    keep = jnp.arange(t.capacity) < k
    return VecTable(c.cols, c.valid & keep)


# ---------------------------------------------------------------------------
# incremental (streaming) state: init / merge across micro-batches
# ---------------------------------------------------------------------------
#
# The streaming target (core/passes/lower_stream.py) splits a lowered plan
# at its terminal aggregation: each micro-batch produces a *partial*
# aggregate (the batch segment reuses the ordinary grouped/scalar operators
# above), and the running state is folded forward with the functions below.
# Every AggSpec is self-decomposable (count combines with sum), so
# merge-of-partials is itself a grouped aggregation over the concatenated
# (state, delta) block — the GroupAggDirect dense-bucket accumulators carry
# straight across micro-batches instead of being recomputed.


def _merge_aggs(aggs: Sequence[AggSpec]) -> List[AggSpec]:
    """The partial-combining AggSpecs: ``fn=combine_fn`` over the partial
    column itself (sum-of-sums, min-of-mins, sum-of-counts)."""
    from ..core.expr import Col

    return [AggSpec(a.combine_fn, Col(a.name), a.name) for a in aggs]


def empty_grouped_state(template: VecTable) -> VecTable:
    """The identity element for grouped merge: same schema/capacity as a
    partial-aggregate block, zero valid rows."""
    return VecTable({k: jnp.zeros_like(v) for k, v in template.cols.items()},
                    jnp.zeros_like(template.valid))


def merge_grouped_partials(state: VecTable, delta: VecTable,
                           keys: Sequence[str], aggs: Sequence[AggSpec],
                           max_groups: int,
                           key_domains: Optional[Sequence[Tuple[int, int]]] = None,
                           num_buckets: Optional[int] = None) -> VecTable:
    """Fold one micro-batch's grouped partial aggregate into the running
    state (both capacity ``max_groups``) — the streaming step/merge op.

    With catalog ``key_domains`` the merge is the sort-free dense-bucket
    tier (O(state+delta), the carried GroupAggDirect accumulator); without
    them it falls back to sort + segment reduction.  Aggregate columns are
    cast back to the delta's dtypes so integer counts stay integers across
    arbitrarily many merges.
    """
    both = concat([state, delta])
    merge_aggs = _merge_aggs(aggs)
    if key_domains is not None and num_buckets is not None:
        merged = group_agg_direct(both, keys, merge_aggs, max_groups,
                                  key_domains, int(num_buckets))
    else:
        merged = group_agg_sorted(sort_by_key(both, keys), keys, merge_aggs,
                                  max_groups)
    cols = {k: merged.cols[k].astype(delta.cols[k].dtype)
            for k in merged.cols}
    return VecTable(cols, merged.valid)


def merge_scalar_partials(state: Dict[str, jax.Array],
                          delta: Dict[str, jax.Array],
                          aggs: Sequence[AggSpec]) -> Dict[str, jax.Array]:
    """Fold one micro-batch's scalar partial aggregate (Single) into the
    running state, dtype-preserving (counts stay integral)."""
    out: Dict[str, jax.Array] = {}
    for a in aggs:
        fn = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[a.combine_fn]
        out[a.name] = fn(state[a.name], delta[a.name])
    return out
