"""JAX emitters: executable meaning of each opcode during lowering.

The final stage of compilation (paper §3.5): every instruction of the final
IR corresponds to an executable building block.  Here the building blocks
are pure JAX functions; tracing the whole program under ``jax.jit`` is the
JIT-compile-the-pipeline step (XLA plays the role of LLVM in JITQ).

Value model (mirrors ``backends.interp`` but on device):
  Vec⟨tuple⟩ → VecTable, Single⟨tuple⟩ → dict[str, scalar], Tensor → Array,
  split Seq[n]⟨X⟩ → list of n values.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

from ..core.expr import AggSpec, evaluate
from ..core.program import Instruction, Program
from ..relational import runtime as rt
from ..relational.runtime import VecTable

_EMIT: Dict[str, Callable[..., List[Any]]] = {}


def emitter(opcode: str):
    def deco(fn):
        _EMIT[opcode] = fn
        return fn
    return deco


@dataclass(kw_only=True)
class EvalCtx:
    """Carries sources and backend knobs through evaluation."""

    #: Pallas interpret mode, from ``repro.kernels.interpret_on`` for the
    #: platform the backend runs on — no default, so no path can reach a
    #: kernel with a stale one
    interpret: bool
    sources: Dict[str, Any] = field(default_factory=dict)
    use_kernels: bool = False
    mesh: Any = None            # set by the SPMD backend
    axis: Optional[str] = None  # mesh axis inside shard_map bodies
    #: traced executions install a dict here; tapped ops accumulate
    #: ``key → [occurrences, rows_in, rows_out]`` (rows are traced scalars
    #: under jit — returned from the compiled body, never host callbacks)
    taps: Optional[Dict[str, List[Any]]] = None


def tap_rows(v: Any) -> Any:
    """Cardinality of one runtime value: valid rows for a VecTable (a traced
    scalar under jit), leading dim for arrays and column dicts, summed
    chunks for split sequences, 1 for singles."""
    if isinstance(v, VecTable):
        return v.count()
    if isinstance(v, dict):
        if not v:
            return 0
        first = next(iter(v.values()))
        return first.shape[0] if getattr(first, "ndim", 0) >= 1 else 1
    if isinstance(v, (list, tuple)):
        return sum(tap_rows(c) for c in v)
    shape = getattr(v, "shape", None)
    if shape:
        return shape[0]
    return 1


def record_tap(ctx: EvalCtx, program: Program, index: int, ins: Instruction,
               args: Sequence[Any], outs: Sequence[Any]) -> None:
    """Accumulate one instruction's measured cardinality into ``ctx.taps``.

    Repeated hits of the same instruction (unrolled ConcurrentExecute
    bodies) sum their row counts — the summed-chunk global cardinality the
    profile joins against the per-chunk estimate × occurrences."""
    from ..obs.feedback import TAPPED_OPS, tap_key

    if ins.opcode not in TAPPED_OPS or not ins.outputs:
        return
    key = tap_key(program.name, index, ins.opcode, ins.outputs[0].name)
    rows_in = tap_rows(args[0]) if args else None
    rows_out = tap_rows(outs[0])
    entry = ctx.taps.get(key)
    if entry is None:
        ctx.taps[key] = [1, rows_in, rows_out]
    else:
        entry[0] += 1
        entry[1] = (None if entry[1] is None or rows_in is None
                    else entry[1] + rows_in)
        entry[2] = entry[2] + rows_out


def evaluate_program(ctx: EvalCtx, program: Program, *args: Any) -> List[Any]:
    """Trace a CVM program into JAX ops (call under jit)."""
    if len(args) != len(program.inputs):
        raise ValueError(f"{program.name}: expected {len(program.inputs)} args")
    env: Dict[str, Any] = {r.name: v for r, v in zip(program.inputs, args)}
    for i, ins in enumerate(program.body):
        fn = _EMIT.get(ins.opcode)
        if fn is None:
            raise NotImplementedError(f"no JAX emitter for {ins.opcode}")
        ins_args = [env[r.name] for r in ins.inputs]
        outs = fn(ctx, ins, ins_args)
        if ctx.taps is not None:
            record_tap(ctx, program, i, ins, ins_args, outs)
        for r, v in zip(ins.outputs, outs):
            env[r.name] = v
    return [env[r.name] for r in program.results]


# ---------------------------------------------------------------------------
# vec flavor
# ---------------------------------------------------------------------------


@emitter("vec.ScanVec")
def _scanvec(ctx, ins, args):
    return [ctx.sources[ins.param("table")]]


@emitter("vec.MaskSelect")
def _maskselect(ctx, ins, args):
    return [rt.mask_select(args[0], ins.param("pred"))]


@emitter("vec.ProjVec")
def _projvec(ctx, ins, args):
    return [rt.proj(args[0], ins.param("names"))]


@emitter("vec.ExProjVec")
def _exprojvec(ctx, ins, args):
    return [rt.exproj(args[0], ins.param("exprs"))]


@emitter("vec.AggrVec")
def _aggrvec(ctx, ins, args):
    return [rt.aggr(args[0], ins.param("aggs"))]


@emitter("vec.FusedSelectAgg")
def _fused_select_agg(ctx, ins, args):
    (t,) = args
    pred, aggs = ins.param("pred"), ins.param("aggs")
    if ctx.use_kernels:
        from ..kernels import ops as kops
        return [kops.fused_select_agg(t, pred, aggs, interpret=ctx.interpret)]
    return [rt.aggr(rt.mask_select(t, pred), aggs)]


@emitter("vec.FinalizeSingle")
def _finalize_single(ctx, ins, args):
    (single,) = args
    return [{n: evaluate(e, single, jnp) for n, e in ins.param("exprs")}]


@emitter("vec.SortByKey")
def _sortbykey(ctx, ins, args):
    keys = ins.param("keys")
    asc = ins.param("ascending") or [True] * len(keys)
    return [rt.sort_by_key(args[0], keys, asc)]


@emitter("vec.GroupAggSorted")
def _groupagg(ctx, ins, args):
    return [rt.group_agg_sorted(args[0], ins.param("keys"), ins.param("aggs"),
                                int(ins.param("max_groups")))]


@emitter("vec.GroupAggDirect")
def _groupagg_direct(ctx, ins, args):
    (t,) = args
    keys = tuple(ins.param("keys"))
    aggs = tuple(ins.param("aggs"))
    mg = int(ins.param("max_groups"))
    domains = tuple(ins.param("key_domains"))
    nb = int(ins.param("num_buckets"))
    pred = ins.param("pred")
    # the kernel's one-hot block grows with the buckets: a plan whose VMEM
    # estimate is over the limit takes the XLA segment reduction instead
    from ..kernels import ops as kops
    if ctx.use_kernels and kops.grouped_select_agg_fits(pred, keys, aggs, nb):
        return [kops.grouped_select_agg(t, pred, keys, aggs, mg, domains, nb,
                                        interpret=ctx.interpret)]
    return [rt.group_agg_direct(t, keys, aggs, mg, domains, nb, pred=pred)]


@emitter("vec.DictEncode")
def _dictencode(ctx, ins, args):
    return [rt.dict_encode(args[0], ins.param("cols"), ins.param("modes"),
                           ins.param("tables"), ins.param("lows"),
                           ins.param("cards"))]


@emitter("vec.DictDecode")
def _dictdecode(ctx, ins, args):
    return [rt.dict_decode(args[0], ins.param("cols"), ins.param("tables"))]


@emitter("vec.MergeJoinSorted")
def _mergejoin(ctx, ins, args):
    return [rt.merge_join_sorted(args[0], args[1], ins.param("left_on"),
                                 ins.param("right_on"), int(ins.param("max_count")),
                                 key_domains=ins.param("key_domains"))]


@emitter("vec.HashJoinDirect")
def _hashjoin_direct(ctx, ins, args):
    nb = ins.param("num_buckets")
    return [rt.hash_join_direct(args[0], args[1], ins.param("left_on"),
                                ins.param("right_on"),
                                int(ins.param("max_count")),
                                key_domains=ins.param("key_domains"),
                                num_buckets=int(nb) if nb is not None else None)]


@emitter("vec.FusedJoinGroupAgg")
def _fused_join_group_agg(ctx, ins, args):
    left, right = args
    kw = dict(
        left_on=tuple(ins.param("left_on")),
        right_on=tuple(ins.param("right_on")),
        join_key_domains=tuple(ins.param("join_key_domains")),
        join_num_buckets=int(ins.param("join_num_buckets")),
        keys=tuple(ins.param("keys")),
        aggs=tuple(ins.param("aggs")),
        max_groups=int(ins.param("max_groups")),
        key_domains=tuple(ins.param("key_domains")),
        num_buckets=int(ins.param("num_buckets")),
        pred=ins.param("pred"),
    )
    from ..kernels import ops as kops
    if ctx.use_kernels and kops.grouped_join_agg_fits(
            left, right, left_on=kw["left_on"], right_on=kw["right_on"],
            join_num_buckets=kw["join_num_buckets"], keys=kw["keys"],
            aggs=kw["aggs"], num_buckets=kw["num_buckets"], pred=kw["pred"]):
        return [kops.grouped_join_agg(left, right, interpret=ctx.interpret,
                                      **kw)]
    return [rt.fused_join_group_agg(left, right, **kw)]


@emitter("vec.MergeGroupedState")
def _merge_grouped_state(ctx, ins, args):
    kd = ins.param("key_domains")
    nb = ins.param("num_buckets")
    return [rt.merge_grouped_partials(
        args[0], args[1], tuple(ins.param("keys")), tuple(ins.param("aggs")),
        int(ins.param("max_groups")),
        key_domains=tuple(kd) if kd is not None else None,
        num_buckets=int(nb) if nb is not None else None)]


@emitter("vec.MergeScalarState")
def _merge_scalar_state(ctx, ins, args):
    return [rt.merge_scalar_partials(args[0], args[1],
                                     tuple(ins.param("aggs")))]


@emitter("vec.Compact")
def _compact(ctx, ins, args):
    return [rt.compact(args[0], ins.param("max_count"))]


@emitter("vec.TopKVec")
def _topkvec(ctx, ins, args):
    keys = ins.param("keys")
    asc = ins.param("ascending") or [True] * len(keys)
    return [rt.topk(args[0], keys, asc, int(ins.param("k")))]


@emitter("vec.LimitVec")
def _limitvec(ctx, ins, args):
    return [rt.limit(args[0], int(ins.param("k")))]


@emitter("vec.SplitVec")
def _splitvec(ctx, ins, args):
    return [rt.split(args[0], int(ins.param("n")))]


@emitter("vec.ConcatVec")
def _concatvec(ctx, ins, args):
    return [rt.concat(args[0])]


@emitter("rel.CombinePartials")
def _combinepartials(ctx, ins, args):
    return [rt.combine_partials(args[0], ins.param("aggs"))]


# ---------------------------------------------------------------------------
# control flow
# ---------------------------------------------------------------------------


def _split_value(v: Any, n: int) -> List[Any]:
    if isinstance(v, VecTable):
        return rt.split(v, n)
    arrs = jnp.split(v, n, axis=0)
    return list(arrs)


def _merge_value(chunks: List[Any]) -> Any:
    if isinstance(chunks[0], VecTable):
        return rt.concat(chunks)
    return jnp.concatenate(chunks, axis=0)


@emitter("cf.Split")
def _cf_split(ctx, ins, args):
    return [_split_value(args[0], int(ins.param("n")))]


@emitter("cf.Broadcast")
def _cf_broadcast(ctx, ins, args):
    return [[args[0]] * int(ins.param("n"))]


@emitter("cf.Merge")
def _cf_merge(ctx, ins, args):
    return [_merge_value(args[0])]


@emitter("cf.ConcurrentExecute")
def _cf_ce(ctx, ins, args):
    """Local lowering of ConcurrentExecute: unrolled per-chunk traces.

    On a single device the concurrency comes from XLA's own parallelism
    (JITQ analogue: thread-level parallelism inside one fused module).  The
    SPMD backend overrides this with a shard_map lowering.
    """
    p: Program = ins.param("P")
    n = len(args[0])
    per_worker = [[a[w] for a in args] for w in range(n)]
    results: List[List[Any]] = [[] for _ in p.results]
    for w in range(n):
        outs = evaluate_program(ctx, p, *per_worker[w])
        for i, o in enumerate(outs):
            results[i].append(o)
    return results


@emitter("cf.CombineChunks")
def _cf_combine(ctx, ins, args):
    (chunks,) = args
    op = ins.param("op")
    fn = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[op]
    acc = chunks[0]
    for c in chunks[1:]:
        acc = jax.tree_util.tree_map(fn, acc, c)
    return [acc]


@emitter("cf.TakeChunk")
def _cf_take(ctx, ins, args):
    return [args[0][int(ins.param("i", 0))]]


@emitter("cf.Loop")
def _cf_loop(ctx, ins, args):
    p: Program = ins.param("P")
    n = int(ins.param("n"))
    state = list(args)
    if n <= 4:  # unroll small loops (lets XLA fuse across iterations)
        for _ in range(n):
            state = evaluate_program(ctx, p, *state)
        return state

    def body(carry, _):
        outs = evaluate_program(ctx, p, *carry)
        return tuple(outs), None

    final, _ = jax.lax.scan(body, tuple(state), None, length=n)
    return list(final)


@emitter("cf.While")
def _cf_while(ctx, ins, args):
    p: Program = ins.param("P")

    def cond(carry):
        outs = evaluate_program(ctx, p, *carry)
        return outs[0]

    def body(carry):
        outs = evaluate_program(ctx, p, *carry)
        return tuple(outs[1:])

    final = jax.lax.while_loop(cond, body, tuple(args))
    return list(final)


@emitter("cf.Cond")
def _cf_cond(ctx, ins, args):
    pred, rest = args[0], args[1:]
    pt, pe = ins.param("Pthen"), ins.param("Pelse")
    return list(jax.lax.cond(
        pred,
        lambda xs: tuple(evaluate_program(ctx, pt, *xs)),
        lambda xs: tuple(evaluate_program(ctx, pe, *xs)),
        tuple(rest),
    ))


@emitter("cf.Call")
def _cf_call(ctx, ins, args):
    return evaluate_program(ctx, ins.param("P"), *args)


# ---------------------------------------------------------------------------
# dataflow + linear algebra
# ---------------------------------------------------------------------------


@emitter("df.Source")
def _df_source(ctx, ins, args):
    return [ctx.sources[ins.param("name")]]


@emitter("df.Collect")
def _df_collect(ctx, ins, args):
    return [args[0]]


@emitter("la.Literal")
def _la_literal(ctx, ins, args):
    name = ins.param("name")
    if name is not None and name in ctx.sources:
        return [ctx.sources[name]]
    return [jnp.asarray(ins.param("value"))]


@emitter("la.MMMult")
def _la_mmmult(ctx, ins, args):
    return [args[0] @ args[1]]


@emitter("la.Transpose")
def _la_transpose(ctx, ins, args):
    return [args[0].T]


@emitter("la.Ewise")
def _la_ewise(ctx, ins, args):
    op = ins.param("op")
    if len(args) == 1:
        a = args[0]
        return [{"neg": lambda: -a, "abs": lambda: jnp.abs(a), "add": lambda: a,
                 "sqrt": lambda: jnp.sqrt(a), "square": lambda: a * a}[op]()]
    a, b = args
    return [{"add": lambda: a + b, "sub": lambda: a - b,
             "mul": lambda: a * b, "div": lambda: a / b}[op]()]


@emitter("la.ReduceSum")
def _la_reducesum(ctx, ins, args):
    return [jnp.sum(args[0], axis=int(ins.param("axis")))]


@emitter("la.CDist2")
def _la_cdist2(ctx, ins, args):
    x, c = args
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1, keepdims=True).T
    return [x2 - 2.0 * (x @ c.T) + c2]


@emitter("la.ArgMinRow")
def _la_argminrow(ctx, ins, args):
    return [jnp.argmin(args[0], axis=1).astype(jnp.int32)]


@emitter("la.SegSum")
def _la_segsum(ctx, ins, args):
    x, lab = args
    k = int(ins.param("k"))
    return [jax.ops.segment_sum(x, lab, num_segments=k)]


@emitter("la.SegCount")
def _la_segcount(ctx, ins, args):
    lab = args[0]
    k = int(ins.param("k"))
    return [jax.ops.segment_sum(jnp.ones_like(lab, dtype=jnp.float32), lab, num_segments=k)]


@emitter("la.KMeansStep")
def _la_kmeans_step(ctx, ins, args):
    x, c = args
    if ctx.use_kernels:
        from ..kernels import ops as kops
        sums, counts = kops.kmeans_step(x, c, interpret=ctx.interpret)
        return [sums, counts]
    d = _la_cdist2(ctx, ins, args)[0]
    lab = jnp.argmin(d, axis=1).astype(jnp.int32)
    k = c.shape[0]
    sums = jax.ops.segment_sum(x, lab, num_segments=k)
    counts = jax.ops.segment_sum(jnp.ones_like(lab, dtype=jnp.float32), lab, num_segments=k)
    return [sums, counts]
