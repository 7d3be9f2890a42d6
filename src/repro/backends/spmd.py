"""SPMD mesh backend — the Modularis analogue on TPU.

Backend-specific rewrite + lowering:

  * ``cf.ConcurrentExecute`` → ``mesh.MeshExecute(axis)``: the chunk axis
    becomes a named mesh axis; the nested program body runs under
    ``jax.shard_map`` (per-device slice), so XLA compiles ONE program for
    all workers (SPMD) — the TPU equivalent of Modularis' MPIExecutor.
  * value model: a split ``Seq[n]⟨X⟩`` is a *stacked* global array (leading
    worker dim) sharded along that dim; ``Broadcast`` replicates.
  * combines after a MeshExecute can be pulled inside as collectives
    (``PushCombineIntoMesh``): CombineChunks(sum) → ``lax.psum`` over the
    mesh axis inside the body — the paper's pre-aggregation becoming an
    all-reduce instead of a gather+reduce.  Exchange-by-key lowers to
    histogram partitioning + ``lax.all_to_all`` (MPIHistogram+MPIExchange).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.program import Program
# The backend-specific rewritings (LowerToMesh, PushCombineIntoMesh) are
# registered pipeline stages now — re-exported here for compatibility.
from ..core.passes.mesh_lower import LowerToMesh, PushCombineIntoMesh  # noqa: F401
from ..relational.runtime import VecTable
from ..robust.inject import maybe_inject
from . import emit as base_emit
from .emit import EvalCtx, evaluate_program


# ---------------------------------------------------------------------------
# SPMD emitters
# ---------------------------------------------------------------------------

_SPMD_EMIT: Dict[str, Callable[..., List[Any]]] = {}


def spmd_emitter(opcode: str):
    def deco(fn):
        _SPMD_EMIT[opcode] = fn
        return fn
    return deco


def _stack_split(v: Any, n: int) -> Any:
    """Split a value into a stacked leading worker dim (global view)."""
    if isinstance(v, VecTable):
        cap = v.capacity
        assert cap % n == 0
        return VecTable(
            {k: a.reshape(n, cap // n) for k, a in v.cols.items()},
            v.valid.reshape(n, cap // n),
        )
    return v.reshape((n, v.shape[0] // n) + v.shape[1:])


def _unstack_merge(v: Any) -> Any:
    if isinstance(v, VecTable):
        n, c = v.valid.shape[0], v.valid.shape[1]
        return VecTable(
            {k: a.reshape((n * c,) + a.shape[2:]) for k, a in v.cols.items()},
            v.valid.reshape(n * c),
        )
    return v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:])


@spmd_emitter("cf.Split")
def _split(ctx, ins, args):
    return [_stack_split(args[0], int(ins.param("n")))]


@spmd_emitter("cf.Merge")
def _merge(ctx, ins, args):
    return [_unstack_merge(args[0])]


@spmd_emitter("cf.Broadcast")
def _broadcast(ctx, ins, args):
    return [("bcast", args[0])]


@spmd_emitter("cf.TakeChunk")
def _take(ctx, ins, args):
    v = args[0]
    i = int(ins.param("i", 0))
    if isinstance(v, VecTable):
        return [VecTable({k: a[i] for k, a in v.cols.items()}, v.valid[i])]
    return [jax.tree_util.tree_map(lambda a: a[i], v)]


@spmd_emitter("cf.CombineChunks")
def _combine(ctx, ins, args):
    op = ins.param("op")
    fn = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op]
    return [jax.tree_util.tree_map(lambda a: fn(a, axis=0), args[0])]


@spmd_emitter("rel.CombinePartials")
def _combine_partials(ctx, ins, args):
    (stacked,) = args  # dict of (n,) arrays
    out = {}
    for a in ins.param("aggs"):
        vals = stacked[a.name]
        out[a.name] = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[a.combine_fn](vals)
    return [out]


@spmd_emitter("mesh.MeshExecute")
def _mesh_execute(ctx, ins, args):
    """Run the nested program as one SPMD body under shard_map."""
    p: Program = ins.param("P")
    axis = ins.param("axis", "workers")
    mesh: Mesh = ctx.mesh

    bcast_flags = [isinstance(a, tuple) and len(a) == 2 and a[0] == "bcast" for a in args]
    values = [a[1] if f else a for a, f in zip(args, bcast_flags)]

    def spec_for(v, bcast):
        def leaf_spec(x):
            return P() if bcast else P(axis)
        return jax.tree_util.tree_map(leaf_spec, v)

    in_specs = tuple(spec_for(v, f) for v, f in zip(values, bcast_flags))
    out_specs = P(axis)

    def body(*worker_args):
        local = []
        for a, f in zip(worker_args, bcast_flags):
            if f:
                local.append(a)
            else:
                local.append(jax.tree_util.tree_map(lambda x: x[0], a))
        inner_ctx = EvalCtx(sources=ctx.sources, use_kernels=ctx.use_kernels,
                            mesh=mesh, axis=axis, interpret=ctx.interpret,
                            platform=ctx.platform)
        outs = evaluate_spmd_program(inner_ctx, p, *local)
        return tuple(jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], o)
                     for o in outs)

    shard_fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=tuple(out_specs for _ in p.results),
                             check_vma=False)
    outs = shard_fn(*values)
    return list(outs)


@spmd_emitter("mesh.AllReduce")
def _allreduce(ctx, ins, args):
    axis = ins.param("axis")
    op = ins.param("op", "sum")
    (x,) = args
    if op == "combine_aggs":
        out = {}
        for a in ins.param("aggs"):
            fn = {"sum": jax.lax.psum, "min": jax.lax.pmin, "max": jax.lax.pmax}[a.combine_fn]
            out[a.name] = fn(x[a.name], axis)
        return [out]
    fn = {"sum": jax.lax.psum, "min": jax.lax.pmin, "max": jax.lax.pmax}[op]
    return [jax.tree_util.tree_map(lambda v: fn(v, axis), x)]


@spmd_emitter("mesh.AllGatherVec")
def _allgather(ctx, ins, args):
    (v,) = args
    axis = ins.param("axis")
    if isinstance(v, VecTable):
        cols = {k: jax.lax.all_gather(a, axis, tiled=True) for k, a in v.cols.items()}
        return [VecTable(cols, jax.lax.all_gather(v.valid, axis, tiled=True))]
    return [jax.lax.all_gather(v, axis, tiled=True)]


@spmd_emitter("mesh.ExchangeByKey")
def _exchange(ctx, ins, args):
    """Histogram partition + all_to_all: rows with equal keys land on the
    same device (MPIHistogram + MPIExchange)."""
    (v,) = args
    axis = ins.param("axis")
    n = int(ins.param("n"))
    key = ins.param("key")
    skew = float(ins.param("skew", 2.0))
    cap = v.capacity
    per = int(cap * skew) // n * n // n  # per-destination slots

    dest = (v.cols[key].astype(jnp.uint32) % jnp.uint32(n)).astype(jnp.int32)
    dest = jnp.where(v.valid, dest, n)  # invalid → dropped bucket

    # slot position within destination bucket
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    start = jnp.searchsorted(sorted_dest, jnp.arange(n + 1))
    pos_sorted = jnp.arange(cap) - start[sorted_dest]
    keep = (pos_sorted < per) & (sorted_dest < n)
    slot_sorted = jnp.where(keep, sorted_dest * per + pos_sorted, n * per)

    def scatter(col):
        buf = jnp.zeros((n * per + 1,), col.dtype)
        return buf.at[slot_sorted].set(col[order])[:-1].reshape(n, per)

    cols = {k: scatter(a) for k, a in v.cols.items()}
    valid = jnp.zeros((n * per + 1,), jnp.bool_).at[slot_sorted].set(
        keep)[:-1].reshape(n, per)
    # exchange: concat over source workers of bucket for me
    cols = {k: jax.lax.all_to_all(a, axis, split_axis=0, concat_axis=0)
            for k, a in cols.items()}
    valid = jax.lax.all_to_all(valid, axis, split_axis=0, concat_axis=0)
    return [VecTable({k: a.reshape(-1) for k, a in cols.items()}, valid.reshape(-1))]


def evaluate_spmd_program(ctx: EvalCtx, program: Program, *args: Any) -> List[Any]:
    maybe_inject("spmd.shard", program=program.name)
    env: Dict[str, Any] = {r.name: v for r, v in zip(program.inputs, args)}
    for i, ins in enumerate(program.body):
        fn = _SPMD_EMIT.get(ins.opcode) or base_emit._EMIT.get(ins.opcode)
        if fn is None:
            raise NotImplementedError(f"spmd backend: no emitter for {ins.opcode}")
        ins_args = [env[r.name] for r in ins.inputs]
        with base_emit.op_scope(i, ins):
            outs = fn(ctx, ins, ins_args)
        if ctx.taps is not None:
            # top-level only: MeshExecute bodies run under shard_map with a
            # fresh tap-free ctx, so a stacked MeshExecute output is tapped
            # here once — its count() sums valid rows across all shards
            base_emit.record_tap(ctx, program, i, ins, ins_args, outs)
        for r, v in zip(ins.outputs, outs):
            env[r.name] = v
    return [env[r.name] for r in program.results]


# ---------------------------------------------------------------------------
# backend facade
# ---------------------------------------------------------------------------


@dataclass
class SpmdCompiled:
    program: Program
    fn: Callable[..., List[Any]]
    traced_fn: Optional[Callable[..., Any]] = None
    mesh: Optional[Mesh] = None
    axis: str = "workers"

    def place(self, sources: Any) -> Dict[str, Any]:
        """Put the sources where the mesh program reads them: every leaf
        whose leading dim splits evenly over the worker axis is sharded
        along it (each device holds its slice of the rows), the rest is
        replicated.  Host (numpy) leaves go straight to their devices;
        leaves already placed so are returned as they are."""
        n = self.mesh.shape[self.axis]
        split = NamedSharding(self.mesh, P(self.axis))
        whole = NamedSharding(self.mesh, P())

        def put(x):
            shape = jnp.shape(x)
            return jax.device_put(
                x, split if shape and shape[0] % n == 0 else whole)

        return jax.tree_util.tree_map(put, dict(sources or {}))

    def __call__(self, sources=None, *args):
        return self.fn(self.place(sources), *args)

    def run_traced(self, sources=None, *args):
        """Execute and measure: ``(results, {tap key → TapRecord}, {})``."""
        from ..obs.feedback import TapRecord

        outs, taps = self.traced_fn(self.place(sources), *args)
        cards = {
            k: TapRecord(int(occ), None if ri is None else int(ri), int(ro))
            for k, (occ, ri, ro) in taps.items()
        }
        return outs, cards, {}


class SpmdBackend:
    """Compile a parallelized CVM program for a device mesh."""

    name = "spmd"

    def __init__(self, mesh: Mesh, axis: str = "workers", use_kernels: bool = False,
                 collectives: bool = True, jit: bool = True,
                 rewrite: bool = True) -> None:
        from ..kernels import interpret_on

        self.mesh = mesh
        self.axis = axis
        self.use_kernels = use_kernels
        # kernels run on the mesh's devices, whatever the default backend
        self.platform = mesh.devices.flat[0].platform
        self.interpret = interpret_on(self.platform)
        self.collectives = collectives
        self.jit = jit
        # standalone use still rewrites here; the compilation driver runs the
        # same rules as pipeline stages and passes rewrite=False
        self.rewrite = rewrite

    def compile(self, program: Program) -> SpmdCompiled:
        if self.rewrite:
            program = LowerToMesh(self.axis).apply(program)
            if self.collectives:
                program = PushCombineIntoMesh().apply(program)

        def run(sources: Dict[str, Any], *args: Any) -> List[Any]:
            ctx = EvalCtx(sources=sources, use_kernels=self.use_kernels,
                          mesh=self.mesh, interpret=self.interpret,
                          platform=self.platform)
            return evaluate_spmd_program(ctx, program, *args)

        def run_traced(sources: Dict[str, Any], *args: Any):
            ctx = EvalCtx(sources=sources, use_kernels=self.use_kernels,
                          mesh=self.mesh, interpret=self.interpret,
                          platform=self.platform, taps={})
            outs = evaluate_spmd_program(ctx, program, *args)
            return outs, ctx.taps

        fn = jax.jit(run) if self.jit else run
        tfn = jax.jit(run_traced) if self.jit else run_traced
        return SpmdCompiled(program, fn, tfn, self.mesh, self.axis)
