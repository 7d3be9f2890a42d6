"""Sort-free direct-table joins + whole-pipeline join fusion (ISSUE 8).

Contracts:
  * ``rt.hash_join_direct`` (dense direct-table probe) is row-for-row
    equivalent to ``sort_by_key + merge_join_sorted`` and to the interp
    oracle — across int and composite keys, duplicate probe keys, empty
    and all-invalid inputs, and out-of-domain probe keys (which must drop,
    never alias a clipped boundary bucket);
  * duplicate build-side keys resolve to the first occurrence on both vec
    tiers (and the lowering warns that the PK-FK assumption is unverified);
  * the ``join: sorted | hash`` strategy Choice is forceable through
    ``compile(...)`` and chosen by ``optimize="cost"`` from the key-domain
    statistics (low NDV → hash, domain past the bucket cap → sorted);
  * ``FuseJoinGroupAgg`` collapses MaskSelect → HashJoinDirect →
    GroupAggDirect into one ``vec.FusedJoinGroupAgg`` that never
    materializes the join, equal to the unfused plan and the oracle — on
    the jitted runtime path and the ``grouped_join_agg`` Pallas kernel;
  * resource admission prices the direct table and rejects/degrades plans
    whose bucket table exceeds the byte budget (``join=sorted`` rung);
  * on spmd, both tiers match the oracle and the costed search picks hash
    for the bounded-key join-group shape (subprocess: own device fleet).
"""

import json
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro.compiler import PlanCache
from repro.core.expr import AggSpec, col
from repro.frontends.dataflow import Context, count_, sum_
from repro.launch.hermetic import subprocess_env
from repro.relational import runtime as rt
from repro.relational.runtime import VecTable
from repro.robust.admission import AdmissionError, estimate_peak_bytes

ROOT = Path(__file__).resolve().parents[1]


def _rows(table):
    """Valid rows of a VecTable as a dict of numpy arrays."""
    v = np.asarray(table.valid)
    return {k: np.asarray(c)[v] for k, c in table.cols.items()}


def _sorted_rows(table, keys):
    arrs = [np.asarray(table[k]) for k in keys]
    order = np.lexsort(tuple(reversed(arrs)))
    return {k: np.asarray(v)[order] for k, v in table.items()}


def _assert_tables_equal(got, want, keys, rtol=1e-4):
    got, want = _sorted_rows(got, keys), _sorted_rows(want, keys)
    assert set(got) == set(want)
    for k in got:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.shape == w.shape, (k, g.shape, w.shape)
        if np.issubdtype(g.dtype, np.floating) or np.issubdtype(w.dtype, np.floating):
            np.testing.assert_allclose(g, w.astype(g.dtype), rtol=rtol, err_msg=k)
        else:
            np.testing.assert_array_equal(g, w, err_msg=k)


# ---------------------------------------------------------------------------
# runtime tier: hash_join_direct ≡ sort_by_key + merge_join_sorted
# ---------------------------------------------------------------------------


class TestRuntimeHashJoin:
    def _tables(self, lk_cols, rk_cols, n=400, m=64, lcap=512, rcap=64,
                seed=0, lvalid=None, rvalid=None):
        import jax.numpy as jnp
        rng = np.random.default_rng(seed)
        ldata = dict(lk_cols)
        ldata["x"] = rng.normal(size=n).astype(np.float32)
        rdata = dict(rk_cols)
        rdata["y"] = rng.normal(size=m).astype(np.float32)
        left = VecTable.from_numpy(ldata, lcap)
        right = VecTable.from_numpy(rdata, rcap)
        if lvalid is not None:
            left = VecTable(left.cols, jnp.asarray(lvalid, bool))
        if rvalid is not None:
            right = VecTable(right.cols, jnp.asarray(rvalid, bool))
        return left, right

    def _check(self, left, right, left_on, right_on, domains):
        cap = left.capacity
        hashed = rt.hash_join_direct(left, right, left_on, right_on, cap,
                                     key_domains=domains)
        srt = rt.merge_join_sorted(left, rt.sort_by_key(right, right_on),
                                   left_on, right_on, cap,
                                   key_domains=domains if len(left_on) > 1 else None)
        h, s = _rows(hashed), _rows(srt)
        assert set(h) == set(s)
        for k in h:
            np.testing.assert_allclose(h[k], s[k], rtol=1e-6, err_msg=k)
        return h

    def test_int_keys_duplicate_probe(self):
        rng = np.random.default_rng(1)
        lk = rng.integers(0, 64, 400).astype(np.int32)  # many probe dups
        left, right = self._tables({"k": lk}, {"k2": np.arange(64, dtype=np.int32)})
        h = self._check(left, right, ("k",), ("k2",), ((0, 63),))
        assert len(h["x"]) == 400  # every probe row matched

    def test_composite_keys(self):
        rng = np.random.default_rng(2)
        lk1 = rng.integers(0, 8, 400).astype(np.int32)
        lk2 = (rng.integers(0, 4, 400) * 70_000).astype(np.int32)  # >16-bit
        grid = np.stack(np.meshgrid(np.arange(8), np.arange(4) * 70_000),
                        -1).reshape(-1, 2)
        left, right = self._tables(
            {"a": lk1, "b": lk2},
            {"a2": grid[:, 0].astype(np.int32), "b2": grid[:, 1].astype(np.int32)},
            m=32, rcap=32)
        self._check(left, right, ("a", "b"), ("a2", "b2"),
                    ((0, 7), (0, 210_000)))

    def test_partial_match_and_out_of_domain(self):
        """Probe keys outside the declared domain (and unmatched in-domain
        keys) must drop — a clipped bucket id must not fabricate a match."""
        lk = np.array([0, 1, 5, 200, -3, 7] * 50, np.int32)
        left, right = self._tables({"k": lk}, {"k2": np.arange(8, dtype=np.int32)},
                                   n=300, m=8, rcap=8)
        h = self._check(left, right, ("k",), ("k2",), ((0, 7),))
        # 200 and -3 are out of domain; 0,1,5,7 match
        assert len(h["x"]) == 4 * 50
        assert set(h["k"].tolist()) == {0, 1, 5, 7}

    def test_duplicate_build_keys_first_occurrence(self):
        """Both vec tiers keep the FIRST build row per key (PK-FK)."""
        left, right = self._tables(
            {"k": np.array([3, 3, 1], np.int32)},
            {"k2": np.array([1, 3, 3, 1], np.int32)},
            n=3, m=4, lcap=4, rcap=4)
        h = self._check(left, right, ("k",), ("k2",), ((0, 3),))
        ry = np.asarray(right.cols["y"])
        np.testing.assert_allclose(h["y"], [ry[1], ry[1], ry[0]])

    def test_empty_and_all_invalid(self):
        left, right = self._tables(
            {"k": np.zeros(16, np.int32)}, {"k2": np.arange(4, dtype=np.int32)},
            n=16, m=4, lcap=16, rcap=4, lvalid=np.zeros(16, bool))
        h = self._check(left, right, ("k",), ("k2",), ((0, 3),))
        assert len(h["x"]) == 0
        # all-invalid build side: no probe row can match
        left2, right2 = self._tables(
            {"k": np.zeros(16, np.int32)}, {"k2": np.arange(4, dtype=np.int32)},
            n=16, m=4, lcap=16, rcap=4, rvalid=np.zeros(4, bool))
        assert len(self._check(left2, right2, ("k",), ("k2",), ((0, 3),))["x"]) == 0

    def test_dynamic_bounds_both_branches(self):
        """The joint-dynamic-bounds variant: when the measured key span fits
        ``num_buckets`` it takes the direct branch, otherwise the in-trace
        sorted fallback — both must equal the static answer."""
        rng = np.random.default_rng(3)
        lk = rng.integers(0, 32, 200).astype(np.int32)
        left, right = self._tables({"k": lk}, {"k2": np.arange(32, dtype=np.int32)},
                                   n=200, m=32, lcap=256, rcap=32)
        want = _rows(rt.hash_join_direct(left, right, ("k",), ("k2",), 256,
                                         key_domains=((0, 31),)))
        for nb in (64, 8):  # fits / does not fit
            got = _rows(rt.hash_join_direct(left, right, ("k",), ("k2",), 256,
                                            num_buckets=nb))
            for k in want:
                np.testing.assert_allclose(got[k], want[k], rtol=1e-6, err_msg=k)

    def test_requires_domains_or_buckets(self):
        left, right = self._tables({"k": np.zeros(8, np.int32)},
                                   {"k2": np.zeros(4, np.int32)},
                                   n=8, m=4, lcap=8, rcap=4)
        with pytest.raises(ValueError, match="needs a static num_buckets"):
            rt.hash_join_direct(left, right, ("k",), ("k2",), 8)


# ---------------------------------------------------------------------------
# forced strategies + the costed choice, through compile(...)
# ---------------------------------------------------------------------------


@pytest.fixture()
def join_ctx():
    rng = np.random.default_rng(7)
    n, m = 4096, 256
    ctx = Context(pad_to=512)
    ctx.register("orders", {
        "custkey": rng.integers(0, m, n).astype(np.int32),
        "price": rng.gamma(2.0, 100.0, n).astype(np.float32),
        "year": rng.integers(2018, 2026, n).astype(np.int32),
    })
    ctx.register("customer", {
        "ckey": np.arange(m).astype(np.int32),
        "nation": rng.integers(0, 8, m).astype(np.int32),
    })
    return ctx


def join_query(ctx):
    return ctx.table("orders").join(ctx.table("customer"),
                                    left_on=("custkey",), right_on=("ckey",))


def q3_query(ctx):
    """The TPC-H Q3/Q12 shape: select → join → group-aggregate."""
    return (ctx.table("orders").filter(col("year") >= 2020)
            .join(ctx.table("customer"), left_on=("custkey",), right_on=("ckey",))
            .group_by("nation", max_groups=16)
            .agg(sum_("price").as_("rev"), count_().as_("n")))


class TestStrategyChoice:
    def test_forced_hash_and_sorted_match_oracle(self, join_ctx):
        q = join_query(join_ctx)
        want = join_ctx.execute(q, target="interp")
        progs = {}
        for label in ("sorted", "hash"):
            res = join_ctx.compile(q, strategy={"join": label},
                                   cache=PlanCache())
            progs[label] = res.program.opcodes()
            (out,) = res(join_ctx.sources())
            _assert_tables_equal(out.to_numpy(), want, ("custkey", "price"))
        assert "vec.MergeJoinSorted" in progs["sorted"]
        assert "vec.HashJoinDirect" not in progs["sorted"]
        assert "vec.HashJoinDirect" in progs["hash"]
        assert "vec.SortByKey" not in progs["hash"]
        assert "vec.MergeJoinSorted" not in progs["hash"]

    def test_cost_low_ndv_selects_hash(self, join_ctx):
        res = join_ctx.compile(join_query(join_ctx), optimize="cost",
                               cache=PlanCache())
        assert dict(res.strategy)["join"] == "hash"
        assert "vec.HashJoinDirect" in res.program.opcodes()
        labels = [c.label() for c in res.decision.candidates]
        assert any("join=sorted" in l for l in labels)

    def test_cost_huge_domain_selects_sorted(self):
        """Join keys spread over a ~2^21 *raw* domain but only 2048 distinct
        values: the raw direct table would not fit the bucket cap (forcing
        encode=raw warns and degrades to sorted), while dictionary encoding
        shrinks the domain to rank space and the costed search keeps the
        O(n) hash tier."""
        rng = np.random.default_rng(13)
        n, m = 4096, 2048
        ctx = Context(pad_to=512)
        ctx.register("probe", {
            "k": (rng.integers(0, m, n) * 1024).astype(np.int32),
            "x": rng.normal(size=n).astype(np.float32),
        })
        ctx.register("build", {
            "bk": (np.arange(m) * 1024).astype(np.int32),
            "y": rng.normal(size=m).astype(np.float32),
        })
        q = ctx.table("probe").join(ctx.table("build"),
                                    left_on=("k",), right_on=("bk",))
        # encode=raw forced: the sparse raw span is over budget → warn and
        # degrade the join to the sorted tier, exactly the pre-dictionary
        # behaviour
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            raw = ctx.compile(q, strategy={"join": "hash", "encode": "raw"},
                              cache=PlanCache())
        assert "vec.HashJoinDirect" not in raw.program.opcodes()
        assert "vec.MergeJoinSorted" in raw.program.opcodes()
        assert any("hash_unavailable" in str(w.message) for w in caught)
        # costed search: dictionary ranks fit the cap, so the sort-free
        # tier stays available and wins
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            res = ctx.compile(q, optimize="cost", cache=PlanCache())
        chosen = dict(res.strategy)
        assert chosen["join"] == "hash" and chosen["encode"] == "dict"
        assert "vec.HashJoinDirect" in res.program.opcodes()

    def test_pkfk_unverified_warns(self):
        """Duplicate build-side keys break the PK-FK assumption the vec
        tiers rely on — the lowering must say so out loud."""
        ctx = Context(pad_to=64)
        ctx.register("l", {"k": (np.arange(32) % 4).astype(np.int32),
                           "x": np.ones(32, np.float32)})
        ctx.register("r", {"k2": np.array([0, 1, 2, 3, 0, 1], np.int32),
                           "y": np.arange(6).astype(np.float32)})
        q = ctx.table("l").join(ctx.table("r"), left_on=("k",), right_on=("k2",))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            ctx.compile(q, strategy={"join": "hash"}, cache=PlanCache())
        msgs = [str(w.message) for w in caught]
        assert any("join_pkfk_unverified" in m for m in msgs)

    def test_join_strategy_is_cache_keyed(self, join_ctx):
        cache = PlanCache()
        q = join_query(join_ctx)
        r1 = join_ctx.compile(q, strategy={"join": "hash"}, cache=cache)
        r2 = join_ctx.compile(q, strategy={"join": "sorted"}, cache=cache)
        r3 = join_ctx.compile(q, strategy={"join": "hash"}, cache=cache)
        assert not r1.cache_hit and not r2.cache_hit and r3.cache_hit

    def test_empty_selection_matches_oracle(self, join_ctx):
        q = (join_ctx.table("orders").filter(col("year") >= 3000)
             .join(join_ctx.table("customer"),
                   left_on=("custkey",), right_on=("ckey",)))
        want = join_ctx.execute(q, target="interp")
        assert len(np.asarray(want["price"]).ravel()) == 0
        for label in ("sorted", "hash"):
            got = join_ctx.execute(q, strategy={"join": label})
            assert len(got["price"]) == 0


# ---------------------------------------------------------------------------
# whole-pipeline fusion: select → join → group as one op / one kernel
# ---------------------------------------------------------------------------


class TestFusedJoinGroupAgg:
    def test_fused_equals_unfused_and_oracle(self, join_ctx):
        q = q3_query(join_ctx)
        want = join_ctx.execute(q, target="interp")
        fused = join_ctx.compile(q, strategy={"join": "hash",
                                              "groupby": "direct"},
                                 cache=PlanCache())
        ops = fused.program.opcodes()
        assert "vec.FusedJoinGroupAgg" in ops
        assert "vec.HashJoinDirect" not in ops  # join never materialized
        assert "vec.GroupAggDirect" not in ops
        assert "vec.MaskSelect" not in ops  # predicate folded in
        (out,) = fused(join_ctx.sources())
        _assert_tables_equal(out.to_numpy(), want, ("nation",))

        unfused = join_ctx.compile(q, strategy={"join": "hash",
                                                "groupby": "direct"},
                                   fuse=False, cache=PlanCache())
        assert "vec.HashJoinDirect" in unfused.program.opcodes()
        (out2,) = unfused(join_ctx.sources())
        _assert_tables_equal(out2.to_numpy(), want, ("nation",))

    def test_fused_kernel_matches_oracle(self, join_ctx):
        q = q3_query(join_ctx)
        want = join_ctx.execute(q, target="interp")
        res = join_ctx.compile(q, strategy={"join": "hash",
                                            "groupby": "direct"},
                               use_kernels=True, cache=PlanCache())
        assert "vec.FusedJoinGroupAgg" in res.program.opcodes()
        (out,) = res(join_ctx.sources())
        _assert_tables_equal(out.to_numpy(), want, ("nation",))

    def test_fused_runtime_op_matches_composition(self):
        """rt.fused_join_group_agg ≡ mask_select → hash_join → group_agg."""
        rng = np.random.default_rng(5)
        n, m = 512, 16
        left = VecTable.from_numpy({
            "k": rng.integers(0, m, n).astype(np.int32),
            "x": rng.normal(size=n).astype(np.float32)}, n)
        right = VecTable.from_numpy({
            "k2": np.arange(m).astype(np.int32),
            "g": rng.integers(0, 4, m).astype(np.int32),
            "w": rng.normal(size=m).astype(np.float32)}, m)
        pred = col("x") > 0.0
        aggs = (AggSpec("sum", col("x"), "sx"), AggSpec("count", col("x"), "c"),
                AggSpec("min", col("w"), "mw"))
        fused = rt.fused_join_group_agg(
            left, right, ("k",), ("k2",),
            join_key_domains=((0, m - 1),), join_num_buckets=m,
            keys=("g",), aggs=aggs, max_groups=8,
            key_domains=((0, 3),), num_buckets=4, pred=pred)
        sel = rt.mask_select(left, pred)
        joined = rt.hash_join_direct(sel, right, ("k",), ("k2",), n,
                                     key_domains=((0, m - 1),))
        ref = rt.group_agg_direct(joined, ("g",), aggs, 8, ((0, 3),), 4)
        f, r = _rows(fused), _rows(ref)
        for k in f:
            np.testing.assert_allclose(f[k], r[k], rtol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# resource admission: the direct table is priced, over-budget degrades
# ---------------------------------------------------------------------------


def make_big_domain_join_ctx():
    """Join keys over a ~2^19 domain: admissible for lowering (under the
    bucket cap) but the ~2 MB direct table busts a 1 MB budget."""
    rng = np.random.default_rng(17)
    n, m = 4096, 512
    ctx = Context(pad_to=512)
    ctx.register("probe", {
        "k": (rng.integers(0, m, n) * 1024).astype(np.int32),
        "x": rng.normal(size=n).astype(np.float32),
    })
    ctx.register("build", {
        "bk": (np.arange(m) * 1024).astype(np.int32),
        "y": rng.normal(size=m).astype(np.float32),
    })
    return ctx


class TestJoinAdmission:
    BUDGET = 1_000_000

    def test_direct_table_priced(self, join_ctx):
        res = join_ctx.compile(join_query(join_ctx), strategy={"join": "hash"},
                               cache=False, guard=False)
        est = estimate_peak_bytes(res.program)
        assert est.peak_site == "vec.HashJoinDirect"
        sites = dict(est.breakdown)
        assert sites["vec.HashJoinDirect"] > 256 * 4  # includes the table

    def test_over_budget_rejected_without_guard(self):
        ctx = make_big_domain_join_ctx()
        q = ctx.table("probe").join(ctx.table("build"),
                                    left_on=("k",), right_on=("bk",))
        with pytest.raises(AdmissionError, match="resource admission"):
            ctx.compile(q, strategy={"join": "hash"}, cache=False,
                        memory_budget=self.BUDGET, guard=False)

    def test_over_budget_degrades_to_sorted_with_guard(self):
        ctx = make_big_domain_join_ctx()
        q = ctx.table("probe").join(ctx.table("build"),
                                    left_on=("k",), right_on=("bk",))
        want = ctx.execute(q, target="interp")
        with warnings.catch_warnings(record=True):
            warnings.simplefilter("always")
            res = ctx.compile(q, strategy={"join": "hash"}, cache=PlanCache(),
                              memory_budget=self.BUDGET)
        assert ("join", "sorted") in res.strategy
        assert res.degraded
        assert "vec.MergeJoinSorted" in res.program.opcodes()
        (out,) = res(ctx.sources())
        _assert_tables_equal(out.to_numpy(), want, ("k", "x"))


# ---------------------------------------------------------------------------
# spmd acceptance: both tiers ≡ oracle, cost picks hash (own device fleet)
# ---------------------------------------------------------------------------

SPMD_JOIN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np

    from repro.compiler import compile as cvm_compile
    from repro.frontends.dataflow import Context, count_, sum_

    rng = np.random.default_rng(21)
    n, m = 8192, 128
    ctx = Context(pad_to=1024)
    ctx.register("orders", {
        "custkey": rng.integers(0, m, n).astype(np.int32),
        "price": rng.gamma(2.0, 100.0, n).astype(np.float32),
    })
    ctx.register("customer", {
        "ckey": np.arange(m).astype(np.int32),
        "nation": rng.integers(0, 8, m).astype(np.int32),
    })
    q = (ctx.table("orders")
         .join(ctx.table("customer"), left_on=("custkey",), right_on=("ckey",))
         .group_by("nation", max_groups=16)
         .agg(sum_("price").as_("rev"), count_().as_("n")))
    program = q.program()
    catalog = ctx.catalog()
    out = {}

    res = cvm_compile(program, target="spmd", parallel=8, catalog=catalog,
                      optimize="cost", cache=False)
    out["strategy"] = dict(res.strategy)

    want = ctx.execute(q, target="interp")
    o_w = np.argsort(np.asarray(want["nation"]).ravel())
    for label in ("sorted", "hash"):
        r = cvm_compile(program, target="spmd", parallel=8, catalog=catalog,
                        strategy={"join": label}, cache=False)
        (got_t,) = r(ctx.sources())
        got = got_t.to_numpy()
        o_g = np.argsort(got["nation"])
        np.testing.assert_allclose(got["rev"][o_g],
                                   np.asarray(want["rev"]).ravel()[o_w],
                                   rtol=1e-4)
        np.testing.assert_array_equal(got["n"][o_g],
                                      np.asarray(want["n"]).ravel()[o_w])
        out[label + "_ok"] = True
        out[label + "_ops"] = sorted(set(
            op for p in r.program.walk() for op in p.opcodes()))
    print("RESULTS" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def spmd_join_results():
    proc = subprocess.run(
        [sys.executable, "-c", SPMD_JOIN_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=subprocess_env(ROOT),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS")][0]
    return json.loads(line[len("RESULTS"):])


class TestSpmdJoin:
    def test_cost_selects_hash_on_spmd(self, spmd_join_results):
        assert spmd_join_results["strategy"]["join"] == "hash"

    def test_both_tiers_match_interp(self, spmd_join_results):
        assert spmd_join_results["sorted_ok"]
        assert spmd_join_results["hash_ok"]
        assert "vec.MergeJoinSorted" in spmd_join_results["sorted_ops"]
        assert "vec.HashJoinDirect" in spmd_join_results["hash_ops"]


# ---------------------------------------------------------------------------
# merge_join_sorted's two probes: binary search and descent
# ---------------------------------------------------------------------------

I32_MAX = np.iinfo(np.int32).max
I32_MIN = np.iinfo(np.int32).min


def _probe_reference(rk, rvalid, lk):
    """First valid build row holding each probe key, ``len(rk)`` if none:
    ``np.searchsorted`` over the valid build rows (key-sorted)."""
    rows = np.flatnonzero(rvalid)
    keys = rk[rows]
    at = np.searchsorted(keys, lk)
    found = at < len(keys)
    found[found] = keys[at[found]] == lk[found]
    return np.where(found, rows[np.minimum(at, max(len(rows) - 1, 0))]
                    if len(rows) else 0, len(rk)).astype(np.int32)


def _probe_case(name):
    """(build keys, build validity, probe keys) of one case; invalid build
    rows trail the valid ones holding the sentinel, as ``sort_by_key`` and
    ``merge_join_sorted`` leave them."""
    rng = np.random.default_rng(11)
    if name == "unique_with_misses":
        rk = np.arange(0, 80, 2, dtype=np.int32)
        return rk, np.ones(40, bool), rng.integers(-3, 84, 100).astype(np.int32)
    if name == "invalid_build_sentinel":
        rk = np.concatenate([np.arange(10, dtype=np.int32),
                             np.full(6, I32_MAX, np.int32)])
        return rk, np.arange(16) < 10, rng.integers(0, 12, 50).astype(np.int32)
    if name == "extreme_probe_keys":
        rk = np.array([I32_MIN, -5, 0, 7, I32_MAX, I32_MAX, I32_MAX], np.int32)
        lk = np.array([I32_MAX, I32_MIN, 7, 8, I32_MAX, -5, I32_MIN + 1], np.int32)
        return rk, np.arange(7) < 5, lk
    if name == "extreme_probe_keys_no_match":
        rk = np.concatenate([np.arange(1, 9, dtype=np.int32),
                             np.full(4, I32_MAX, np.int32)])
        lk = np.array([I32_MAX, I32_MIN, I32_MAX, 3, I32_MIN], np.int32)
        return rk, np.arange(12) < 8, lk
    if name == "valid_sentinel_key":
        rk = np.array([2, 5, I32_MAX], np.int32)
        return rk, np.ones(3, bool), np.array([I32_MAX, 5, 3, I32_MIN], np.int32)
    if name == "sentinel_probe_no_sentinel_key":
        rk = np.arange(1, 10, dtype=np.int32)
        return rk, np.ones(9, bool), np.array([I32_MAX, 9, 10, 0, 1], np.int32)
    if name == "no_valid_build_rows":
        return (np.full(8, I32_MAX, np.int32), np.zeros(8, bool),
                np.array([0, 1, I32_MAX, I32_MIN], np.int32))
    if name == "fewer_probe_than_build":
        rk = np.sort(rng.choice(1000, 300, replace=False)).astype(np.int32)
        return rk, np.ones(300, bool), rng.integers(0, 1000, 20).astype(np.int32)
    if name == "more_probe_than_build":
        rk = np.sort(rng.choice(50, 12, replace=False)).astype(np.int32)
        return rk, np.ones(12, bool), rng.integers(0, 50, 400).astype(np.int32)
    if name == "duplicate_build_keys":
        rk = np.array([1, 3, 3, 3, 5, 5, 8, I32_MAX, I32_MAX], np.int32)
        lk = np.array([3, 5, 8, 1, 2, 3, 5, 9], np.int32)
        return rk, np.arange(9) < 7, lk
    raise KeyError(name)


PROBE_CASES = ("unique_with_misses", "invalid_build_sentinel",
               "extreme_probe_keys", "extreme_probe_keys_no_match",
               "valid_sentinel_key", "sentinel_probe_no_sentinel_key",
               "no_valid_build_rows", "fewer_probe_than_build",
               "more_probe_than_build", "duplicate_build_keys")


@pytest.mark.parametrize("probe", ["probe_descent", "probe_search"])
@pytest.mark.parametrize("case", PROBE_CASES)
def test_probe_matches_reference(probe, case):
    """Both probes give each probe key the first valid build row holding
    it, and ``len(rk)`` where none does; an invalid build row never
    matches, even a probe key equal to its sentinel."""
    import jax.numpy as jnp
    rk, rvalid, lk = _probe_case(case)
    got = getattr(rt, probe)(jnp.asarray(rk), jnp.asarray(rvalid), jnp.asarray(lk))
    np.testing.assert_array_equal(np.asarray(got), _probe_reference(rk, rvalid, lk))


@pytest.mark.parametrize("fanout,chunk,nl,nr", [
    (4, 7, 50, 1), (4, 7, 50, 4), (4, 7, 50, 5), (4, 7, 49, 16),
    (4, 7, 49, 17), (4, 8, 64, 65), (4, 1000, 300, 257), (3, 5, 31, 100),
    (128, 1 << 19, 500, 16385),
])
def test_descent_tree_shapes(fanout, chunk, nl, nr, monkeypatch):
    """The descent answers like the reference whatever the tree's depth,
    the build side's padding to whole rows and the last partial chunk of
    probe rows: ``nr`` on both sides of a power of the fanout, ``nl`` on
    both sides of a multiple of the chunk."""
    import jax.numpy as jnp
    monkeypatch.setattr(rt, "DESCENT_FANOUT", fanout)
    monkeypatch.setattr(rt, "DESCENT_CHUNK", chunk)
    rng = np.random.default_rng(nl * 1000 + nr)
    valid = nr - nr // 5
    rk = np.concatenate([np.sort(rng.choice(4 * nr + 8, valid, replace=False)),
                         np.full(nr - valid, I32_MAX)]).astype(np.int32)
    rvalid = np.arange(nr) < valid
    lk = rng.integers(-2, 4 * nr + 10, nl).astype(np.int32)
    lk[:2] = (I32_MAX, I32_MIN)
    got = rt.probe_descent(jnp.asarray(rk), jnp.asarray(rvalid), jnp.asarray(lk))
    want = _probe_reference(rk, rvalid, lk)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("probe", ["probe_descent", "probe_search"])
def test_probe_composite_keys(probe):
    """Composite keys packed with ``key_domains`` probe like single keys."""
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    domains = ((0, 9), (100, 149))
    grid = np.stack(np.meshgrid(np.arange(10), np.arange(100, 150),
                                indexing="ij"), -1).reshape(-1, 2)
    build = grid[np.sort(rng.choice(len(grid), 200, replace=False))]
    right = VecTable.from_numpy({"a": build[:, 0].astype(np.int32),
                                 "b": build[:, 1].astype(np.int32)}, 256)
    left = VecTable.from_numpy(
        {"a": rng.integers(0, 10, 300).astype(np.int32),
         "b": rng.integers(100, 150, 300).astype(np.int32)}, 300)
    rk = rt._composite_key(right, ("a", "b"), key_domains=domains)
    lk = rt._composite_key(left, ("a", "b"), key_domains=domains)
    got = getattr(rt, probe)(rk, right.valid, lk)
    want = _probe_reference(np.asarray(rk), np.asarray(right.valid), np.asarray(lk))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (want < 256).sum() > 50  # the case has matches as well as misses


def _join_case(name):
    """(left, right, left_on, right_on, max_count, key_domains) of one
    merge-join case; the right side is key-sorted."""
    rng = np.random.default_rng(17)
    y = lambda m: rng.normal(size=m).astype(np.float32)  # noqa: E731
    if name in ("int_keys", "compacted", "no_valid_left", "no_valid_right"):
        left = VecTable.from_numpy({"k": rng.integers(0, 80, 300).astype(np.int32),
                                    "x": y(300)}, 320)
        right = VecTable.from_numpy({"k2": np.arange(0, 120, 3, dtype=np.int32),
                                     "y": y(40)}, 48)
        if name == "no_valid_left":
            left = VecTable(left.cols, np.zeros(320, bool))
        if name == "no_valid_right":
            right = VecTable(right.cols, np.zeros(48, bool))
        cap = 128 if name == "compacted" else 320
        return left, right, ("k",), ("k2",), cap, None
    if name == "duplicate_build_keys":
        left = VecTable.from_numpy({"k": np.array([3, 3, 1, 2], np.int32),
                                    "x": y(4)}, 4)
        right = VecTable.from_numpy({"k": np.array([1, 3, 3, 3], np.int32),
                                     "y": y(4)}, 6)
        return left, right, ("k",), ("k",), 4, None
    grid = np.stack(np.meshgrid(np.arange(6), np.arange(40), indexing="ij"),
                    -1).reshape(-1, 2)[::3]
    right = VecTable.from_numpy({"a2": grid[:, 0].astype(np.int32),
                                 "b2": grid[:, 1].astype(np.int32),
                                 "y": y(len(grid))}, 96)
    left = VecTable.from_numpy({"a": rng.integers(0, 6, 200).astype(np.int32),
                                "b": rng.integers(0, 40, 200).astype(np.int32),
                                "x": y(200)}, 256)
    domains = ((0, 5), (0, 39)) if name == "composite_domains" else None
    return left, right, ("a", "b"), ("a2", "b2"), 256, domains


@pytest.mark.parametrize("case", ["int_keys", "compacted", "no_valid_left",
                                  "no_valid_right", "duplicate_build_keys",
                                  "composite_domains", "composite_traced_bounds"])
def test_merge_join_same_under_both_probes(case, monkeypatch):
    """``merge_join_sorted`` gives the same table whichever probe the rule
    picks: the same valid rows, in the same order, with the same values."""
    left, right, left_on, right_on, cap, domains = _join_case(case)
    right = rt.sort_by_key(right, right_on)
    tables = {}
    for descent in (False, True):
        monkeypatch.setattr(rt, "probe_by_descent",
                            lambda nl, nr, platform, d=descent: d)
        tables[descent] = rt.merge_join_sorted(left, right, left_on, right_on, cap,
                                               key_domains=domains)
    search, desc = _rows(tables[False]), _rows(tables[True])
    assert set(search) == set(desc)
    for k in search:
        np.testing.assert_array_equal(desc[k], search[k], err_msg=k)
    np.testing.assert_array_equal(np.asarray(tables[True].valid),
                                  np.asarray(tables[False].valid))


@pytest.mark.parametrize("nl,nr,platform,descent", [
    (60_000_000, 15_000_064, "tpu", True),
    (60_000_000, 2_000_000, "tpu", True),
    (15_000_064, 15_000_064, "tpu", True),
    (1_000, 15_000_064, "tpu", False),
    (60_000_000, 15_000_064, "cpu", False),
    (60_000_000, 2_000_000, "cpu", False),
    (15_000_064, 15_000_064, "cpu", False),
    (1_000, 15_000_064, "cpu", False),
])
def test_probe_rule(nl, nr, platform, descent):
    """The probe is a pure function of the static row counts and the
    platform: search on the CPU always, the descent on a TPU for the
    benchmark's joins, search there for a few rows into a large build
    side."""
    assert rt.probe_by_descent(nl, nr, platform) is descent


@pytest.mark.parametrize("descent", [False, True])
def test_probe_counters(descent, monkeypatch):
    """One ``merge_join.probe_*`` count per traced join, none per call."""
    import jax
    from repro.obs.trace import tracing
    monkeypatch.setattr(rt, "probe_by_descent", lambda nl, nr, platform: descent)
    left, right, left_on, right_on, cap, _ = _join_case("int_keys")
    right = rt.sort_by_key(right, right_on)

    def two_joins(left, right):
        a = rt.merge_join_sorted(left, right, left_on, right_on, cap)
        b = rt.merge_join_sorted(left, right, left_on, right_on, 64)
        return a, b

    fn = jax.jit(two_joins)
    with tracing(cardinalities=False) as tracer:
        for _ in range(3):
            jax.block_until_ready(fn(left, right))
    taken, other = (("merge_join.probe_descent", "merge_join.probe_search") if descent
                    else ("merge_join.probe_search", "merge_join.probe_descent"))
    assert tracer.counters.get(taken) == 2
    assert other not in tracer.counters


@pytest.fixture(scope="module")
def tpch_small():
    from repro.relational import tpch
    tables = tpch.generate(sf=0.002, seed=5)
    return tables, tpch.make_context(tables, pad_to=256)


@pytest.mark.parametrize("qname", ["q1", "q4", "q6", "q12", "q14", "q19"])
def test_tpch_same_answers_with_descent_probe(qname, tpch_small, monkeypatch):
    """The benchmark's six queries (their ``relational/tpch.py`` siblings)
    answer bit for bit the same with the descent forced, and every join of
    Q4, Q12, Q14 and Q19 takes it."""
    from repro.obs.trace import tracing
    from repro.relational import tpch
    tables, ctx = tpch_small

    def answer():
        plan = ctx.compile(tpch.QUERIES[qname](ctx), cache=False)
        (out,) = plan(ctx.sources(plan))
        out = out.to_numpy() if isinstance(out, VecTable) else out
        return plan, {k: np.asarray(v) for k, v in out.items()}

    _, want = answer()
    monkeypatch.setattr(rt, "probe_by_descent", lambda nl, nr, platform: True)
    with tracing(cardinalities=False) as tracer:
        plan, got = answer()
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    joins = plan.program.opcodes().count("vec.MergeJoinSorted")
    assert joins == (0 if qname in ("q1", "q6") else 1)
    assert tracer.counters.get("merge_join.probe_descent", 0) == joins
    assert "merge_join.probe_search" not in tracer.counters
    tpch.assert_result_close(got, tpch.REFERENCES[qname](tables),
                             tpch.GROUP_KEYS.get(qname, ()))
