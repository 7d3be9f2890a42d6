"""TPC-H tables from a seed, at the spec's cardinalities and key layout.

TPC Benchmark H Standard Specification rev. 3.0.1, clause 4.2: every table
(LINEITEM, ORDERS, PARTSUPP, PART, CUSTOMER, SUPPLIER, NATION, REGION) and
every column but the free-text comments (``*_comment``: the program holds
strings as codes of one dictionary that the host builds over every distinct
value, and comment text is distinct in nearly every row).  Strings are i32 codes in the order of the
spec's word lists (clause 4.2.2.13); strings that are nearly unique per row
(names, addresses, phone numbers) are the rank of the row's string among
the table's, drawn as a permutation.  Dates are days since 1970-01-01;
prices, quantities and rates are float32.

Keys and values follow clause 4.2.3: sparse order keys (the first 8 of
every 32), ``l_suppkey`` and ``ps_suppkey`` by the spec's formula, customer
keys of orders never divisible by 3, ``o_totalprice`` and ``o_orderstatus``
from the order's lines.  One departure, listed under ``assumed`` in the
configuration files: lines per order take each of 1..7 equally often (the
orders left over once whole sevens are dealt get 4) in an order drawn from
the seed, so LINEITEM has exactly 4 x ORDERS rows and the shapes do not
depend on the seed: a new seed compiles nothing.

Each column draws from its own stream (``SeedSequence(seed).spawn``), so
columns are made in parallel threads and still depend on the seed alone.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from datetime import date
from typing import Callable, Dict

import numpy as np

RETURNFLAGS = ["A", "N", "R"]
LINESTATUS = ["O", "F"]
ORDERSTATUS = ["F", "O", "P"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
BRANDS = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]
TYPES = [f"{a} {b} {c}"
         for a in ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
         for b in ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
         for c in ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]]
CONTAINERS = [f"{a} {b}" for a in ["SM", "LG", "MED", "JUMBO", "WRAP"]
              for b in ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]]
#: n_regionkey of each nation, by n_nationkey (clause 4.2.3)
NATION_REGIONS = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3,
                  4, 2, 3, 3, 1]

THREADS = 8


def day(y: int, m: int, d: int) -> int:
    return date(y, m, d).toordinal() - date(1970, 1, 1).toordinal()


def code(vocab, name) -> int:
    return vocab.index(name)


START = day(1992, 1, 1)
#: ENDDATE (1998-12-31) less 151 days: the last order date (clause 4.2.3)
LAST_ORDER = day(1998, 8, 2)
#: CURRENTDATE, which fixes the return flag and the line status
CURRENT = day(1995, 6, 17)


def sizes(sf: float) -> Dict[str, int]:
    """Row counts of the tables: a function of the scale alone."""
    n_orders = max(64, int(1_500_000 * sf))
    n_part = max(32, int(200_000 * sf))
    return {"lineitem": 4 * n_orders, "orders": n_orders, "partsupp": 4 * n_part,
            "part": n_part, "customer": max(32, int(150_000 * sf)),
            "supplier": max(8, int(10_000 * sf)), "nation": 25, "region": 5}


def order_keys(n_orders: int) -> np.ndarray:
    """Sparse order keys: the first 8 of every 32 (clause 4.2.3)."""
    i = np.arange(n_orders, dtype=np.int64)
    return (32 * (i // 8) + i % 8 + 1).astype(np.int32)


def supp_keys(partkey: np.ndarray, i, n_supp: int) -> np.ndarray:
    """The ``i``-th supplier of each part, 0 <= i < 4 (clause 4.2.3)."""
    pk = partkey.astype(np.int64)
    return ((pk + i * (n_supp // 4 + (pk - 1) // n_supp)) % n_supp + 1).astype(np.int32)


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in dollars (clause 4.2.3)."""
    pk = partkey.astype(np.int64)
    return ((90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100).astype(np.float32)


def lines_per_order(n_orders: int, rng: np.random.Generator) -> np.ndarray:
    """1..7 lines per order, each count equally often, summing to 4 x orders."""
    whole = (n_orders // 7) * 7
    counts = np.concatenate([np.arange(whole) % 7 + 1,
                             np.full(n_orders - whole, 4)]).astype(np.int32)
    rng.shuffle(counts)
    return counts


def generate(sf: float, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    n = sizes(sf)
    n_o, n_l, n_p = n["orders"], n["lineitem"], n["part"]
    n_c, n_s = n["customer"], n["supplier"]
    streams = iter(np.random.SeedSequence(seed % 2**64).spawn(64))

    def draw(fn: Callable[[np.random.Generator], np.ndarray]):
        rng = np.random.default_rng(next(streams))
        return lambda: fn(rng)

    def ints(lo, hi, size):  # uniform on [lo, hi]
        return draw(lambda r: r.integers(lo, hi + 1, size, dtype=np.int32))

    def cents(lo, hi, size):  # uniform on [lo, hi] dollars, whole cents
        return draw(lambda r: (r.integers(round(lo * 100), round(hi * 100) + 1, size)
                               / 100).astype(np.float32))

    def ranks(size):  # codes of strings nearly unique per row
        return draw(lambda r: r.permutation(size).astype(np.int32))

    with ThreadPoolExecutor(THREADS) as pool:
        def run(jobs):
            futs = {k: pool.submit(f) for k, f in jobs.items()}
            return {k: f.result() for k, f in futs.items()}

        d = run({
            "lines": draw(lambda r: lines_per_order(n_o, r)),
            "o_orderdate": ints(START, LAST_ORDER, n_o),
            "o_orderpriority": ints(0, len(PRIORITIES) - 1, n_o),
            # customer keys not divisible by 3: the n-th such key
            "o_custkey": draw(lambda r: (lambda i: 3 * (i // 2) + i % 2 + 1)(
                r.integers(0, n_c - n_c // 3, n_o, dtype=np.int32))),
            "o_clerk": ints(0, max(1, int(1000 * sf)) - 1, n_o),
            "p_name": ranks(n_p),
            "p_mfgr": ints(0, 4, n_p),
            "brand_n": ints(0, 4, n_p),
            "p_type": ints(0, len(TYPES) - 1, n_p),
            "p_size": ints(1, 50, n_p),
            "p_container": ints(0, len(CONTAINERS) - 1, n_p),
            "ps_availqty": ints(1, 9999, 4 * n_p),
            "ps_supplycost": cents(1.0, 1000.0, 4 * n_p),
            "c_address": ranks(n_c),
            "c_nationkey": ints(0, 24, n_c),
            "c_phone": ranks(n_c),
            "c_acctbal": cents(-999.99, 9999.99, n_c),
            "c_mktsegment": ints(0, len(SEGMENTS) - 1, n_c),
            "s_address": ranks(n_s),
            "s_nationkey": ints(0, 24, n_s),
            "s_phone": ranks(n_s),
            "s_acctbal": cents(-999.99, 9999.99, n_s),
            "ship_after": ints(1, 121, n_l),
            "commit_after": ints(30, 90, n_l),
            "receipt_after": ints(1, 30, n_l),
            "l_partkey": ints(1, n_p, n_l),
            "supp_i": ints(0, 3, n_l),
            "l_quantity": ints(1, 50, n_l),
            "discount": ints(0, 10, n_l),
            "tax": ints(0, 8, n_l),
            "ra": ints(0, 1, n_l),
            "l_shipmode": ints(0, len(SHIPMODES) - 1, n_l),
            "l_shipinstruct": ints(0, len(SHIPINSTRUCT) - 1, n_l),
        })
        o_orderkey = order_keys(n_o)
        lines = d.pop("lines")
        first_line = np.concatenate([[0], np.cumsum(lines)[:-1]])
        odate = np.repeat(d["o_orderdate"], lines)
        ship = odate + d.pop("ship_after")
        receipt = ship + d.pop("receipt_after")
        pk = d.pop("l_partkey")
        qty = d.pop("l_quantity").astype(np.float32)
        disc = d.pop("discount").astype(np.float32) / 100
        tax = d.pop("tax").astype(np.float32) / 100
        p_partkey = np.arange(1, n_p + 1, dtype=np.int32)
        li = run({
            "l_orderkey": lambda: np.repeat(o_orderkey, lines),
            "l_suppkey": lambda: supp_keys(pk, d.pop("supp_i"), n_s),
            "l_linenumber": lambda: (np.arange(n_l) - np.repeat(first_line, lines)
                                     + 1).astype(np.int32),
            "l_commitdate": lambda: odate + d.pop("commit_after"),
            # L_QUANTITY times P_RETAILPRICE of the line's part
            "l_extendedprice": lambda: qty * retail_price(pk),
            # 'R' or 'A' once received by CURRENTDATE, else 'N'
            "l_returnflag": lambda: np.where(
                receipt <= CURRENT,
                np.where(d.pop("ra") == 1, code(RETURNFLAGS, "R"),
                         code(RETURNFLAGS, "A")),
                code(RETURNFLAGS, "N")).astype(np.int32),
            # 'O' if shipped after CURRENTDATE, else 'F'
            "l_linestatus": lambda: np.where(
                ship > CURRENT, code(LINESTATUS, "O"),
                code(LINESTATUS, "F")).astype(np.int32),
        })
    charge = li["l_extendedprice"].astype(np.float64) * (1 + tax) * (1 - disc)
    n_open = np.add.reduceat(
        (li["l_linestatus"] == code(LINESTATUS, "O")).astype(np.int32), first_line)
    lineitem = {
        "l_orderkey": li["l_orderkey"], "l_partkey": pk, "l_suppkey": li["l_suppkey"],
        "l_linenumber": li["l_linenumber"], "l_quantity": qty,
        "l_extendedprice": li["l_extendedprice"], "l_discount": disc, "l_tax": tax,
        "l_returnflag": li["l_returnflag"], "l_linestatus": li["l_linestatus"],
        "l_shipdate": ship, "l_commitdate": li["l_commitdate"], "l_receiptdate": receipt,
        "l_shipinstruct": d.pop("l_shipinstruct"), "l_shipmode": d.pop("l_shipmode")}
    orders = {
        "o_orderkey": o_orderkey, "o_custkey": d.pop("o_custkey"),
        # 'F' if every line is 'F', 'O' if every line is 'O', else 'P'
        "o_orderstatus": np.select(
            [n_open == 0, n_open == lines],
            [code(ORDERSTATUS, "F"), code(ORDERSTATUS, "O")],
            code(ORDERSTATUS, "P")).astype(np.int32),
        "o_totalprice": np.add.reduceat(charge, first_line).astype(np.float32),
        "o_orderdate": d.pop("o_orderdate"), "o_orderpriority": d.pop("o_orderpriority"),
        "o_clerk": d.pop("o_clerk"), "o_shippriority": np.zeros(n_o, dtype=np.int32)}
    mfgr = d.pop("p_mfgr")
    part = {"p_partkey": p_partkey, "p_name": d.pop("p_name"), "p_mfgr": mfgr,
            "p_brand": 5 * mfgr + d.pop("brand_n"), "p_type": d.pop("p_type"),
            "p_size": d.pop("p_size"), "p_container": d.pop("p_container"),
            "p_retailprice": retail_price(p_partkey)}
    ps_partkey = np.repeat(p_partkey, 4)
    partsupp = {"ps_partkey": ps_partkey,
                "ps_suppkey": supp_keys(ps_partkey, np.tile(np.arange(4), n_p), n_s),
                "ps_availqty": d.pop("ps_availqty"),
                "ps_supplycost": d.pop("ps_supplycost")}
    customer = {"c_custkey": np.arange(1, n_c + 1, dtype=np.int32),
                "c_name": np.arange(n_c, dtype=np.int32),
                **{k: d.pop(k) for k in ("c_address", "c_nationkey", "c_phone",
                                         "c_acctbal", "c_mktsegment")}}
    supplier = {"s_suppkey": np.arange(1, n_s + 1, dtype=np.int32),
                "s_name": np.arange(n_s, dtype=np.int32),
                **{k: d.pop(k) for k in ("s_address", "s_nationkey", "s_phone",
                                         "s_acctbal")}}
    nation = {"n_nationkey": np.arange(25, dtype=np.int32),
              "n_name": np.arange(25, dtype=np.int32),
              "n_regionkey": np.asarray(NATION_REGIONS, dtype=np.int32)}
    region = {"r_regionkey": np.arange(5, dtype=np.int32),
              "r_name": np.arange(5, dtype=np.int32)}
    assert not d, sorted(d)
    return {"lineitem": lineitem, "orders": orders, "partsupp": partsupp, "part": part,
            "customer": customer, "supplier": supplier, "nation": nation,
            "region": region}
