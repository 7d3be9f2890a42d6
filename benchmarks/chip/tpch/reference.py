"""Vectorized numpy references of the six queries, and their control.

``REFERENCE`` reads the stored float32 columns and does all arithmetic and
every sum in float64: the oracle that decides ``correct``.  ``CONTROL`` is
the same code in the next precision below the configuration's float32:
every float column, literal and elementwise result is rounded to bfloat16
(sums still in float64).  It stands in for the program to show that the
comparison in ``checks.py`` fails a plan that computes in bfloat16.

Groups are summed with ``np.bincount`` over group codes and keys are looked
up through dense tables, so a reference over 60M rows takes seconds.
Nothing here imports the program.
"""

from __future__ import annotations

from typing import Callable, Dict

import ml_dtypes
import numpy as np

from .datagen import BRANDS, SHIPINSTRUCT, SHIPMODES, code, day
from .params import PROMO_TYPES, Q1_CUTOFF, Q19_ARMS, containers


class Precision:
    """Where floats are stored (``dtype``) and whether each elementwise
    result is rounded back to it (``rounds``); arithmetic is float64."""

    def __init__(self, dtype, rounds: bool) -> None:
        self.dtype, self.rounds = dtype, rounds

    def stored(self, a) -> np.ndarray:
        """A column or literal as stored: compare floats in this type."""
        return np.asarray(a, dtype=np.float32).astype(self.dtype)

    def val(self, a) -> np.ndarray:
        """A stored column as float64 operand."""
        return self.stored(a).astype(np.float64)

    def r(self, a) -> np.ndarray:
        """An elementwise result, rounded to the stored type if it rounds."""
        return a.astype(self.dtype).astype(np.float64) if self.rounds else a


REFERENCE = Precision(np.float32, rounds=False)
CONTROL = Precision(ml_dtypes.bfloat16, rounds=True)


def _lookup(keys: np.ndarray, values: np.ndarray) -> np.ndarray:
    """Dense table: ``out[k]`` is the value of key ``k``."""
    out = np.zeros(int(keys.max()) + 1, dtype=values.dtype)
    out[keys] = values
    return out


def _between(p: Precision, a, lo, hi) -> np.ndarray:
    a = p.stored(a)
    return (a >= p.stored(lo)) & (a <= p.stored(hi))


def ref_q1(t, p: Precision):
    li = t["lineitem"]
    m = li["l_shipdate"] <= Q1_CUTOFF
    g = (li["l_returnflag"][m] * 2 + li["l_linestatus"][m]).astype(np.int64)
    qty, ep, disc, tax = (p.val(li[c][m]) for c in
                          ("l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    disc_price = p.r(ep * p.r(1.0 - disc))
    charge = p.r(disc_price * p.r(1.0 + tax))
    n = np.bincount(g, minlength=6)
    s = {name: np.bincount(g, weights=w, minlength=6)
         for name, w in (("qty", qty), ("ep", ep), ("dp", disc_price),
                         ("ch", charge), ("disc", disc))}
    have = np.flatnonzero(n)
    return {
        "l_returnflag": have // 2, "l_linestatus": have % 2,
        "sum_qty": s["qty"][have], "sum_base_price": s["ep"][have],
        "sum_disc_price": s["dp"][have], "sum_charge": s["ch"][have],
        "avg_qty": s["qty"][have] / n[have], "avg_price": s["ep"][have] / n[have],
        "avg_disc": s["disc"][have] / n[have], "count_order": n[have],
    }


def ref_q4(t, p: Precision):
    li, o = t["lineitem"], t["orders"]
    late = np.zeros(int(o["o_orderkey"].max()) + 1, dtype=bool)
    late[li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]]] = True
    d = o["o_orderdate"]
    sel = (d >= day(1993, 7, 1)) & (d < day(1993, 10, 1))
    sel &= late[o["o_orderkey"]]
    n = np.bincount(o["o_orderpriority"][sel], minlength=5)
    have = np.flatnonzero(n)
    return {"o_orderpriority": have, "order_count": n[have]}


def ref_q6(t, p: Precision):
    li = t["lineitem"]
    d = li["l_shipdate"]
    m = ((d >= day(1994, 1, 1)) & (d < day(1995, 1, 1))
         & _between(p, li["l_discount"], 0.05, 0.07)
         & (p.stored(li["l_quantity"]) < p.stored(24.0)))
    rev = p.r(p.val(li["l_extendedprice"][m]) * p.val(li["l_discount"][m]))
    return {"revenue": np.asarray(rev.sum())}


def ref_q12(t, p: Precision):
    li, o = t["lineitem"], t["orders"]
    mode = li["l_shipmode"]
    m = ((mode == code(SHIPMODES, "MAIL")) | (mode == code(SHIPMODES, "SHIP")))
    m &= ((li["l_commitdate"] < li["l_receiptdate"])
          & (li["l_shipdate"] < li["l_commitdate"])
          & (li["l_receiptdate"] >= day(1994, 1, 1))
          & (li["l_receiptdate"] < day(1995, 1, 1)))
    prio = _lookup(o["o_orderkey"], o["o_orderpriority"])[li["l_orderkey"][m]]
    mode = mode[m]
    high = np.bincount(mode, weights=prio <= 1, minlength=len(SHIPMODES))
    n = np.bincount(mode, minlength=len(SHIPMODES))
    have = np.flatnonzero(n)
    return {"l_shipmode": have,
            "high_line_count": high[have].astype(np.int64),
            "low_line_count": n[have] - high[have].astype(np.int64)}


def ref_q14(t, p: Precision):
    li, pt = t["lineitem"], t["part"]
    d = li["l_shipdate"]
    m = (d >= day(1995, 9, 1)) & (d < day(1995, 10, 1))
    ptype = _lookup(pt["p_partkey"], pt["p_type"])[li["l_partkey"][m]]
    rev = p.r(p.val(li["l_extendedprice"][m]) * p.r(1.0 - p.val(li["l_discount"][m])))
    promo = rev * ((ptype >= PROMO_TYPES[0]) & (ptype <= PROMO_TYPES[1]))
    return {"promo_revenue": np.asarray(p.r(p.r(100.0 * promo.sum()) / rev.sum()))}


def ref_q19(t, p: Precision):
    li, pt = t["lineitem"], t["part"]
    mode = li["l_shipmode"]
    air = [code(SHIPMODES, m) for m in ("AIR", "AIR REG") if m in SHIPMODES]
    m = (np.isin(mode, air)
         & (li["l_shipinstruct"] == code(SHIPINSTRUCT, "DELIVER IN PERSON")))
    pk = li["l_partkey"][m]
    brand, cont, size = (_lookup(pt["p_partkey"], pt[c])[pk]
                         for c in ("p_brand", "p_container", "p_size"))
    q = li["l_quantity"][m]
    hit = np.zeros(len(pk), dtype=bool)
    for b, group, (qlo, qhi), smax in Q19_ARMS:
        hit |= ((brand == code(BRANDS, b)) & np.isin(cont, containers(group))
                & _between(p, q, qlo, qhi) & (size >= 1) & (size <= smax))
    ep, disc = p.val(li["l_extendedprice"][m][hit]), p.val(li["l_discount"][m][hit])
    return {"revenue": np.asarray(p.r(ep * p.r(1.0 - disc)).sum())}


REFERENCES: Dict[str, Callable] = {
    "q1": ref_q1, "q4": ref_q4, "q6": ref_q6, "q12": ref_q12, "q14": ref_q14,
    "q19": ref_q19,
}
