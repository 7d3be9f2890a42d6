"""The benchmark's TPC-H data, queries and references, at a tiny scale on
the CPU."""

import numpy as np
import pytest

from repro.relational import tpch as program_tpch
from tpch import datagen, params, queries, reference

SF = 0.002


@pytest.fixture(scope="module")
def tables():
    return datagen.generate(SF, 2**31 + 11)


def shapes(t):
    return {(name, c): (v.shape, v.dtype) for name, cols in t.items()
            for c, v in cols.items()}


def test_shapes_do_not_depend_on_the_seed(tables):
    other = datagen.generate(SF, 5)
    assert shapes(other) == shapes(tables)
    n = datagen.sizes(SF)
    assert {k: len(next(iter(v.values()))) for k, v in tables.items()} == n
    assert n["lineitem"] == 4 * n["orders"]
    assert not np.array_equal(other["lineitem"]["l_partkey"],
                              tables["lineitem"]["l_partkey"])


def test_same_seed_same_tables(tables):
    again = datagen.generate(SF, 2**31 + 11)
    for name, cols in tables.items():
        for c, v in cols.items():
            np.testing.assert_array_equal(again[name][c], v, err_msg=c)


def test_lines_per_order_are_even_and_sum_to_four_per_order():
    counts = datagen.lines_per_order(7 * 100 + 3, np.random.default_rng(0))
    assert counts.sum() == 4 * len(counts)
    assert np.bincount(counts)[1:].tolist() == [100, 100, 100, 103, 100, 100, 100]


@pytest.mark.parametrize("config", ["tpch_sf1", "tpch_sf10"])
def test_columns_are_the_configurations_columns(tables, config):
    import json
    from pathlib import Path

    cfg = json.loads((Path(reference.__file__).parents[1] / "configs"
                      / f"{config}.json").read_text())
    assert {t: sorted(c) for t, c in cfg["tables"].items()} == {
        t: sorted(c) for t, c in tables.items()}
    # the spec's eight tables, every column but the comments
    assert sum(map(len, cfg["tables"].values())) == 61 - 8
    read = {(t, c) for cols in queries.COLUMNS.values()
            for t, cs in cols.items() for c in cs}
    assert read <= {(t, c) for t, cs in tables.items() for c in cs}


def test_keys_and_values_are_the_specs(tables):
    li, o, p, ps = (tables[k] for k in ("lineitem", "orders", "part", "partsupp"))
    n = datagen.sizes(SF)
    # sparse order keys: the first 8 of every 32
    assert o["o_orderkey"][:10].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33, 34]
    assert o["o_orderkey"][-1] == 32 * ((n["orders"] - 1) // 8) + (n["orders"] - 1) % 8 + 1
    assert np.isin(li["l_orderkey"], o["o_orderkey"]).all()
    assert (o["o_custkey"] % 3 != 0).all()
    assert o["o_custkey"].max() <= n["customer"]
    # 25 of the 150 types are PROMO types, the last group in the spec's list
    promo = [t for t in datagen.TYPES if t.startswith("PROMO")]
    assert len(datagen.TYPES) == 150 and len(promo) == 25
    assert params.PROMO_TYPES == (125, 149)
    # brand follows the manufacturer; suppliers by the spec's formula
    assert (p["p_brand"] // 5 == p["p_mfgr"]).all()
    assert ps["ps_suppkey"].min() >= 1 and ps["ps_suppkey"].max() <= n["supplier"]
    pairs = set(zip(ps["ps_partkey"].tolist(), ps["ps_suppkey"].tolist()))
    assert set(zip(li["l_partkey"].tolist(), li["l_suppkey"].tolist())) <= pairs
    # line numbers count 1.. within each order; status and total from the lines
    starts = np.flatnonzero(np.diff(li["l_orderkey"], prepend=-1))
    assert (li["l_linenumber"][starts] == 1).all()
    total = np.zeros(o["o_orderkey"].max() + 1)
    np.add.at(total, li["l_orderkey"], li["l_extendedprice"].astype(np.float64)
              * (1 + li["l_tax"]) * (1 - li["l_discount"]))
    np.testing.assert_allclose(o["o_totalprice"], total[o["o_orderkey"]], rtol=1e-6)
    open_ = np.zeros(o["o_orderkey"].max() + 1, dtype=int)
    np.add.at(open_, li["l_orderkey"], li["l_linestatus"] == 0)
    status = np.where(open_[o["o_orderkey"]] == 0, 0, 2)
    status[open_[o["o_orderkey"]] == np.bincount(li["l_orderkey"])[o["o_orderkey"]]] = 1
    assert (o["o_orderstatus"] == status).all()


def in_program_codes(t):
    """The tables with each coded column in the word order of the program's
    own TPC-H module: the same strings under other codes.  The spec's
    'REG AIR' is no mode there, and takes a code of its own."""
    modes = program_tpch.SHIPMODES + ["REG AIR"]
    ship = np.asarray([modes.index(m) for m in datagen.SHIPMODES])
    cont = np.asarray([program_tpch.CONTAINERS.index(c) for c in datagen.CONTAINERS])
    ptype = t["part"]["p_type"]
    lo = params.PROMO_TYPES[0]
    promo = ptype >= lo  # below program_tpch.PROMO_PTYPES there, above it the rest
    return {**t,
            "lineitem": {**t["lineitem"], "l_shipmode": ship[t["lineitem"]["l_shipmode"]]},
            "part": {**t["part"], "p_container": cont[t["part"]["p_container"]],
                     "p_type": np.where(promo, ptype - lo, ptype + 30).astype(np.int32)}}, ship


@pytest.mark.parametrize("query", sorted(reference.REFERENCES))
def test_references_agree_with_the_programs(tables, query):
    import checks

    got = reference.REFERENCES[query](tables, reference.REFERENCE)
    theirs, ship = in_program_codes(tables)
    want = program_tpch.REFERENCES[query](theirs)
    if "l_shipmode" in want:  # back to the benchmark's codes
        back = np.zeros(ship.max() + 1, dtype=np.int64)
        back[ship] = np.arange(len(ship))
        want["l_shipmode"] = back[want["l_shipmode"]]
    found = checks.compare(got, want, queries.GROUP_KEYS.get(query, ()))
    # the program's ref_q14 rounds each line's revenue to float32 first
    assert found["wrong_exact"] == 0 and found["rel_err"] < 1e-7, found


@pytest.mark.parametrize("entry", ["prepared", "collect"])
def test_both_entries_answer_as_the_references(tables, entry):
    import bench
    import checks

    ctx = bench_context(tables)
    sources = ctx.sources()
    run = bench.ENTRIES[entry](ctx, queries.BUILDERS, sources,
                               {"target": "local", "parallel": None}).run
    for q in queries.BUILDERS:
        got = run(bench.Query(q))
        want = reference.REFERENCES[q](tables, reference.REFERENCE)
        found = checks.compare(got, want, queries.GROUP_KEYS.get(q, ()))
        assert found["wrong_exact"] == 0, (q, found)
        assert found["rel_err"] <= checks.LIMITS["rel_err"], (q, found)


def bench_context(tables):
    from repro.frontends.dataflow import Context

    ctx = Context(pad_to=256)
    for name, data in tables.items():
        ctx.register(name, data)
    return ctx


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_fails_the_limit(seed):
    """The reference computed in bfloat16 in place of the program reads
    above the limit on every seed; the program, on the same tables, below."""
    import checks

    t = datagen.generate(SF, seed)
    wants = {q: f(t, reference.REFERENCE) for q, f in reference.REFERENCES.items()}
    control = [(q, f(t, reference.CONTROL)) for q, f in reference.REFERENCES.items()]
    found = checks.judge(control, wants, queries.GROUP_KEYS, missing=0)
    assert not checks.passed(found), found
    assert found["rel_err"]["value"] > 3 * checks.LIMITS["rel_err"], found


def test_least_bytes_counts_each_read_column_once(tables):
    n = datagen.sizes(SF)["lineitem"]
    assert queries.least_bytes("q6", tables) == 4 * n * 4
