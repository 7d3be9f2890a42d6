"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing runs on a chip: each test lowers and compiles for one chip of a
described ``v5e:2x2`` topology with the TPU compiler that ships with jaxlib,
at TPC-H SF10 row counts (``tpch.generate(sf=100)``: ~60M lineitem rows) and
the kernels' default block sizes, and asserts that the compiled HLO holds a
Mosaic kernel (``tpu_custom_call``).  The grouped kernels are compiled at the
largest bucket count their VMEM gate admits, so a gate that admits more than
Mosaic can compile fails here.

The topology is described inside a module fixture, never at import or
collection: only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends.local import LocalBackend
from repro.core.expr import AggSpec, col
from repro.kernels import ops as kops
from repro.relational import tpch
from repro.relational.runtime import VecTable

#: lineitem rows of ``tpch.generate(sf=100)`` (1..7 lines per 15M orders),
#: rounded up to the 256-row capacity padding of ``tpch.make_context``
LINEITEM_ROWS = 60_000_000
ORDERS_ROWS = 15_000_064
PART_ROWS = 20_000_000

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # executables for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _table(sharding, rows, cols):
    """A VecTable of shapes: ``cols`` maps column name → dtype."""
    return VecTable(
        {n: jax.ShapeDtypeStruct((rows,), dt, sharding=sharding)
         for n, dt in cols.items()},
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=sharding))


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _max_admitted(fits):
    """Largest bucket count the gate ``fits(nb)`` admits."""
    nb = 1
    while fits(nb + 1):
        nb += 1
    assert nb > 1, "the gate admits no grouped kernel at all"
    return nb


Q6_PRED = ((col("l_shipdate") >= 8766) & (col("l_shipdate") < 9131)
           & (col("l_discount") >= 0.05) & (col("l_discount") <= 0.07)
           & (col("l_quantity") < 24.0))
LI_COLS = {"l_shipdate": jnp.int32, "l_discount": jnp.float32,
           "l_quantity": jnp.float32, "l_extendedprice": jnp.float32,
           "l_tax": jnp.float32, "l_returnflag": jnp.int32,
           "l_linestatus": jnp.int32, "l_orderkey": jnp.int32}
PRICE = col("l_extendedprice")
#: aggregate sets: Q1's seven, one sum, and one of each kind
AGG_SETS = {
    "q1": (AggSpec("sum", col("l_quantity"), "sum_qty"),
           AggSpec("sum", PRICE, "sum_base_price"),
           AggSpec("sum", PRICE * (1.0 - col("l_discount")), "sum_disc_price"),
           AggSpec("sum", PRICE * (1.0 - col("l_discount"))
                   * (1.0 + col("l_tax")), "sum_charge"),
           AggSpec("sum", col("l_discount"), "sum_disc"),
           AggSpec("count", col("l_quantity"), "n"),
           AggSpec("count", col("l_quantity"), "count_order")),
    "one_sum": (AggSpec("sum", PRICE, "rev"),),
    "mixed": (AggSpec("min", PRICE, "lo"), AggSpec("max", PRICE, "hi"),
              AggSpec("count", PRICE, "n"), AggSpec("sum", PRICE, "rev")),
}


def test_fused_select_agg_compiles(one_chip):
    t = _table(one_chip, LINEITEM_ROWS, LI_COLS)
    aggs = (AggSpec("sum", PRICE * col("l_discount"), "revenue"),
            AggSpec("min", PRICE, "lo"), AggSpec("count", PRICE, "n"))
    hlo = _hlo(lambda t: kops.fused_select_agg(t, Q6_PRED, aggs,
                                               interpret=False), t)
    assert KERNEL in hlo


@pytest.mark.parametrize("agg_set", sorted(AGG_SETS))
def test_grouped_select_agg_compiles_at_gate(one_chip, agg_set):
    aggs = AGG_SETS[agg_set]
    pred = col("l_shipdate") <= 10471
    keys = ("l_returnflag",)
    nb = _max_admitted(
        lambda n: kops.grouped_select_agg_fits(pred, keys, aggs, n))
    t = _table(one_chip, LINEITEM_ROWS, LI_COLS)
    hlo = _hlo(lambda t: kops.grouped_select_agg(
        t, pred, keys, aggs, nb, [(0, nb - 1)], nb, interpret=False), t)
    assert KERNEL in hlo


def test_grouped_join_agg_compiles_at_gate(one_chip):
    """Q12's shape (probe lineitem, build orders, group by shipmode) at the
    largest join-bucket count the gate admits."""
    aggs = (AggSpec("sum", col("o_orderpriority") <= 1, "high"),
            AggSpec("sum", col("o_orderpriority") > 1, "low"))
    pred = col("l_commitdate") < col("l_receiptdate")
    kw = dict(left_on=("l_orderkey",), right_on=("o_orderkey",),
              keys=("l_shipmode",), aggs=aggs, num_buckets=7, pred=pred)
    left = _table(one_chip, LINEITEM_ROWS,
                  {"l_orderkey": jnp.int32, "l_shipmode": jnp.int32,
                   "l_commitdate": jnp.int32, "l_receiptdate": jnp.int32})
    right = _table(one_chip, ORDERS_ROWS,
                   {"o_orderkey": jnp.int32, "o_orderpriority": jnp.int32})
    nbj = _max_admitted(lambda n: kops.grouped_join_agg_fits(
        left, right, join_num_buckets=n, **kw))
    hlo = _hlo(lambda l, r: kops.grouped_join_agg(
        l, r, join_key_domains=[(1, nbj)], join_num_buckets=nbj,
        max_groups=8, key_domains=[(0, 6)], interpret=False, **kw),
        left, right)
    assert KERNEL in hlo


def test_kmeans_step_compiles(one_chip):
    # an (n, 8) f32 array is tiled to 128 lanes in HBM (16x its size), so
    # 4M points fill 2 GiB where the 60M of lineitem would need 30 GiB
    x = jax.ShapeDtypeStruct((1 << 22, 8), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((16, 8), jnp.float32, sharding=one_chip)
    hlo = _hlo(lambda x, c: kops.kmeans_step(x, c, interpret=False), x, c)
    assert KERNEL in hlo


def test_q1_local_program_with_kernels_compiles(one_chip):
    """The whole local-target Q1 program of ``use_kernels=True`` with
    ``groupby=direct``, lowered from a small session and compiled for the
    SF10 table shapes."""
    tables = tpch.generate(sf=0.002, seed=0)
    ctx = tpch.make_context(tables)
    res = ctx.compile(tpch.q1(ctx), use_kernels=True,
                      strategy={"groupby": "direct"}, guard=False)
    backend = LocalBackend(use_kernels=True)
    backend.interpret = False  # compile for the described chip, not the host
    fn = backend.compile(res.program).fn
    rows = {"lineitem": LINEITEM_ROWS, "orders": ORDERS_ROWS,
            "part": PART_ROWS}
    shapes = {
        name: _table(one_chip, rows[name],
                     {k: a.dtype for k, a in vt.cols.items()})
        for name, vt in ctx.sources().items()}
    assert KERNEL in fn.lower(shapes).compile().as_text()


def test_join_probes_compile_small(one_chip):
    """Both join probes compile at Q12's SF10 shapes (60M lineitem rows
    into 15M orders keys).  A TPU executable's code sits in HBM beside the
    tables, and a sort or a scatter compiles to megabytes of it, so the
    descent holds neither and stays within 1 MiB of the binary search."""
    from repro.relational import runtime as rt

    rk = jax.ShapeDtypeStruct((ORDERS_ROWS,), jnp.int32, sharding=one_chip)
    rvalid = jax.ShapeDtypeStruct((ORDERS_ROWS,), jnp.bool_, sharding=one_chip)
    lk = jax.ShapeDtypeStruct((LINEITEM_ROWS,), jnp.int32, sharding=one_chip)
    code = {}
    for probe in (rt.probe_search, rt.probe_descent):
        compiled = jax.jit(probe).lower(rk, rvalid, lk).compile()
        code[probe.__name__] = compiled.memory_analysis().generated_code_size_in_bytes
        if probe is rt.probe_descent:
            hlo = compiled.as_text()
            assert " sort(" not in hlo and " scatter(" not in hlo
    assert code["probe_descent"] - code["probe_search"] < 1 << 20, code
