"""The six TPC-H queries as the benchmark sends them, through the public
dataflow API (``Context.table`` and ``Frame``), with the validation
substitution values of the spec's query definitions (clause 2.4).

``COLUMNS`` names the columns each query reads: the least bytes a query
must move, which the roofline metric divides by the peak bandwidth.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

from repro.core.expr import col, const
from repro.frontends.dataflow import Context, Frame, avg_, count_, sum_

from .datagen import BRANDS, SHIPINSTRUCT, SHIPMODES, code, day
from .params import PROMO_TYPES, Q1_CUTOFF, Q19_ARMS, containers


def q1(ctx: Context) -> Frame:
    li = ctx.table("lineitem")
    return (
        li.filter(col("l_shipdate") <= Q1_CUTOFF)
        .with_columns(
            disc_price=col("l_extendedprice") * (1.0 - col("l_discount")),
            charge=col("l_extendedprice") * (1.0 - col("l_discount")) * (1.0 + col("l_tax")),
        )
        .group_by("l_returnflag", "l_linestatus", max_groups=8)
        .agg(
            sum_("l_quantity").as_("sum_qty"),
            sum_("l_extendedprice").as_("sum_base_price"),
            sum_("disc_price").as_("sum_disc_price"),
            sum_("charge").as_("sum_charge"),
            avg_("l_quantity").as_("avg_qty"),
            avg_("l_extendedprice").as_("avg_price"),
            avg_("l_discount").as_("avg_disc"),
            count_().as_("count_order"),
        )
        .order_by("l_returnflag", "l_linestatus")
    )


def q4(ctx: Context) -> Frame:
    late = (
        ctx.table("lineitem").filter(col("l_commitdate") < col("l_receiptdate"))
        .group_by("l_orderkey", max_groups=ctx.capacity("orders"))
        .agg(count_().as_("n_late"))
    )
    return (
        ctx.table("orders").filter(
            (col("o_orderdate") >= day(1993, 7, 1)) & (col("o_orderdate") < day(1993, 10, 1))
        )
        .join(late, left_on="o_orderkey", right_on="l_orderkey")
        .group_by("o_orderpriority", max_groups=8)
        .agg(count_().as_("order_count"))
        .order_by("o_orderpriority")
    )


def q6(ctx: Context) -> Frame:
    return ctx.table("lineitem").filter(
        (col("l_shipdate") >= day(1994, 1, 1))
        & (col("l_shipdate") < day(1995, 1, 1))
        & col("l_discount").between(0.05, 0.07)
        & (col("l_quantity") < 24.0)
    ).agg(sum_(col("l_extendedprice") * col("l_discount")).as_("revenue"))


def q12(ctx: Context) -> Frame:
    mail, ship = code(SHIPMODES, "MAIL"), code(SHIPMODES, "SHIP")
    filtered = ctx.table("lineitem").filter(
        (col("l_shipmode").isin((mail, ship)))
        & (col("l_commitdate") < col("l_receiptdate"))
        & (col("l_shipdate") < col("l_commitdate"))
        & (col("l_receiptdate") >= day(1994, 1, 1))
        & (col("l_receiptdate") < day(1995, 1, 1))
    )
    joined = filtered.join(ctx.table("orders"), left_on="l_orderkey",
                           right_on="o_orderkey")
    high = col("o_orderpriority") <= 1  # 1-URGENT or 2-HIGH
    return (
        joined.group_by("l_shipmode", max_groups=8)
        .agg(sum_(high).as_("high_line_count"), sum_(~high).as_("low_line_count"))
        .order_by("l_shipmode")
    )


def q14(ctx: Context) -> Frame:
    rev = col("l_extendedprice") * (1.0 - col("l_discount"))
    joined = (
        ctx.table("lineitem").filter(
            (col("l_shipdate") >= day(1995, 9, 1)) & (col("l_shipdate") < day(1995, 10, 1))
        )
        .join(ctx.table("part"), left_on="l_partkey", right_on="p_partkey")
        .with_columns(rev=rev, promo=col("p_type").between(*PROMO_TYPES) * rev)
    )
    return joined.agg(
        sum_("promo").as_("promo_rev"), sum_("rev").as_("total_rev")
    ).project(promo_revenue=const(100.0) * col("promo_rev") / col("total_rev"))


def q19(ctx: Context) -> Frame:
    # 'AIR REG' is no ship mode of the spec's list, so it matches no line
    air = tuple(code(SHIPMODES, m) for m in ("AIR", "AIR REG") if m in SHIPMODES)
    dip = code(SHIPINSTRUCT, "DELIVER IN PERSON")
    joined = ctx.table("lineitem").join(ctx.table("part"), left_on="l_partkey",
                                        right_on="p_partkey")
    arms = None
    for brand, group, (qlo, qhi), size in Q19_ARMS:
        arm = (col("p_brand").eq(code(BRANDS, brand))
               & col("p_container").isin(containers(group))
               & col("l_quantity").between(qlo, qhi) & col("p_size").between(1, size))
        arms = arm if arms is None else arms | arm
    common = col("l_shipmode").isin(air) & col("l_shipinstruct").eq(dip)
    return joined.filter(common & arms).agg(
        sum_(col("l_extendedprice") * (1.0 - col("l_discount"))).as_("revenue"))


BUILDERS: Dict[str, Callable[[Context], Frame]] = {
    "q1": q1, "q4": q4, "q6": q6, "q12": q12, "q14": q14, "q19": q19,
}

#: the columns each query reads, by table
COLUMNS: Dict[str, Dict[str, Tuple[str, ...]]] = {
    "q1": {"lineitem": ("l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
                        "l_extendedprice", "l_discount", "l_tax")},
    "q4": {"lineitem": ("l_orderkey", "l_commitdate", "l_receiptdate"),
           "orders": ("o_orderkey", "o_orderdate", "o_orderpriority")},
    "q6": {"lineitem": ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")},
    "q12": {"lineitem": ("l_orderkey", "l_shipmode", "l_commitdate", "l_receiptdate",
                         "l_shipdate"),
            "orders": ("o_orderkey", "o_orderpriority")},
    "q14": {"lineitem": ("l_partkey", "l_shipdate", "l_extendedprice", "l_discount"),
            "part": ("p_partkey", "p_type")},
    "q19": {"lineitem": ("l_partkey", "l_quantity", "l_extendedprice", "l_discount",
                         "l_shipmode", "l_shipinstruct"),
            "part": ("p_partkey", "p_brand", "p_container", "p_size")},
}

#: group keys of the grouped queries: result rows are compared in key order
GROUP_KEYS: Dict[str, Tuple[str, ...]] = {
    "q1": ("l_returnflag", "l_linestatus"),
    "q4": ("o_orderpriority",),
    "q12": ("l_shipmode",),
}


def least_bytes(query: str, tables) -> int:
    """Bytes of the live rows of every column ``query`` reads: what any plan
    must read from device memory at least once."""
    return sum(tables[t][c].nbytes for t, cols in COLUMNS[query].items()
               for c in cols)
