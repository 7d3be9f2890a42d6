"""Stepwise perf attribution on the three hillclimb cells.

Runs each cell under four configurations (subprocesses — env toggles must
precede jax init):

  base  : paper-faithful (f32 attention, repeat-KV decode, no donation)
  +A    : + buffer donation                        (memory capacity)
  +AB   : + bf16 attention matmuls                 (compute/memory terms)
  +ABC  : + grouped-head decode (no KV repeat)     (collective term)

Also reports the compilation driver's per-pass instrumentation
(``CompileResult.explain()``) for a representative analytics query on each
in-process target, including the plan-cache effect of a repeated compile.

Results → artifacts/perf_steps/<cell>__<step>.json,
artifacts/perf_steps/compile_passes__<target>.json (pass records + the
cost-model decision records when the costed search ran), BENCH_5.json at
the repo root (grouped-aggregation strategy trajectory: us/call for the
sorted vs direct physical tiers at low and high NDV, plus the costed
driver's decision), and markdown tables on stdout.

Usage: PYTHONPATH=src:. python benchmarks/perf_steps.py [--compile-only]
(--compile-only runs just the compile-pass/cost report — the artifact CI
uploads per PR; --groupby-bench runs just the BENCH_5.json group-by
strategy benchmark; --robust-bench measures the guarded
compile/execute path with no faults armed vs guard=False → BENCH_7.json
with its own <5% overhead guard plus the fault-recovery wall time;
--join-bench runs the BENCH_8.json join-strategy benchmark: sorted vs
hash direct-table joins at low and high NDV, the costed decisions, and
the fused select→join→group pipeline vs its unfused plan with a
streaming-bandwidth roofline check; --dict-bench runs the BENCH_9.json
dictionary-encoding benchmark: string and sparse-integer group-by/join
keys through the dict-encoded direct tiers vs the sorted tiers, the
costed encode=raw|dict decisions, and oracle checks in both directions;
--stream-bench runs the BENCH_10.json streaming benchmark: sustained
micro-batch fold throughput, the checkpointed-vs-bare snapshot overhead
ratio with its <1.10 guard, and the recovery-time-to-caught-up after an
injected mid-batch kill with an exactly-once oracle check.)
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "artifacts" / "perf_steps"

CELLS = [
    ("mixtral-8x7b", "train_4k"),
    ("qwen2-1.5b", "decode_32k"),
    ("granite-34b", "train_4k"),
]

STEPS = {
    # base..ABC keep the replicated grad accumulator (pre-ZeRO-2 semantics)
    "base": {"REPRO_NO_DONATE": "1", "REPRO_ATTN_F32": "1",
             "REPRO_DECODE_REPEAT": "1", "REPRO_NO_ZERO2": "1"},
    "A_donate": {"REPRO_ATTN_F32": "1", "REPRO_DECODE_REPEAT": "1",
                 "REPRO_NO_ZERO2": "1"},
    "AB_bf16attn": {"REPRO_DECODE_REPEAT": "1", "REPRO_NO_ZERO2": "1"},
    "ABC_groupdecode": {"REPRO_NO_ZERO2": "1"},
    # D: mask-based cache write (decode cells; no-op for train)
    "D_maskwrite": {"REPRO_NO_ZERO2": "1"},
    # E: + ZeRO-2 sharded gradient accumulator (train cells)
    "E_zero2accum": {},
}

SCRIPT = """
import os
{env_lines}
import json, sys
from repro.launch.dryrun import run_cell
rec = run_cell("{arch}", "{shape}", multi_pod=False, save=False, verbose=False,
               probes={probes})
print("REC" + json.dumps(rec, default=str))
"""


def run(arch, shape, step, env_over, probes=True):
    env_lines = "\n".join(f'os.environ["{k}"] = "{v}"' for k, v in env_over.items())
    code = SCRIPT.format(env_lines=env_lines, arch=arch, shape=shape,
                         probes=probes)
    from repro.launch.hermetic import subprocess_env

    env = subprocess_env(ROOT)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=3000, env=env)
    if proc.returncode != 0:
        return {"error": proc.stderr.strip().splitlines()[-1] if proc.stderr else "?"}
    line = [l for l in proc.stdout.splitlines() if l.startswith("REC")][0]
    return json.loads(line[3:])


def compile_pass_report():
    """Per-pass compile timings from the unified driver (in-process)."""
    # this is the first jax init in the parent process; without a platform
    # pin, containers with libtpu but no TPU hang in TPU init
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    from repro.compiler import PlanCache, compile as cvm_compile
    from repro.core.expr import col
    from repro.frontends.dataflow import Context, count_, sum_

    rng = np.random.default_rng(0)
    n = 65_536
    ctx = Context(pad_to=1024)
    ctx.register("sales", {
        "region": rng.integers(0, 16, n).astype(np.int32),
        "amount": rng.gamma(2.0, 50.0, n).astype(np.float32),
        "year": rng.integers(2018, 2026, n).astype(np.int32),
    })
    q = (ctx.table("sales")
         .filter(col("year") >= 2020)
         .group_by("region", max_groups=16)
         .agg(sum_("amount").as_("rev"), count_().as_("n")))
    program = q.program("sales_by_region")

    cache = PlanCache()
    for target in ("interp", "local"):
        # optimize="cost": the driver's costed strategy search runs (and is
        # reported) wherever the target declares Choice points
        res = cvm_compile(program, target=target, parallel=4,
                          catalog=ctx.catalog(), cache=cache, optimize="cost")
        payload = {"records": res.explain_records(),
                   "strategy": dict(res.strategy),
                   "decision": (res.decision.records()
                                if res.decision is not None else None)}
        (OUT / f"compile_passes__{target}.json").write_text(
            json.dumps(payload, indent=2))
        print(res.explain())
        print()

    t0 = time.perf_counter()
    res = cvm_compile(program, target="local", parallel=4,
                      catalog=ctx.catalog(), cache=cache, optimize="cost")
    lookup_ms = (time.perf_counter() - t0) * 1e3
    print(f"[perf] repeated compile: cache_hit={res.cache_hit} "
          f"lookup={lookup_ms:.3f} ms (first compile {res.total_s * 1e3:.2f} ms)")


def _groupby_cells():
    """The two grouped-aggregation cells shared by the BENCH_5 strategy
    benchmark and the BENCH_7 robustness benchmark: a TPC-H Q1-style
    low-NDV grouping (two small-domain keys, selective filter) and a
    high-NDV grouping whose key domain (2^20) ≫ rows (2^13)."""
    import numpy as np
    from repro.core.expr import col
    from repro.frontends.dataflow import Context, count_, sum_

    rng = np.random.default_rng(5)
    n = 1 << 17
    ctx = Context(pad_to=1024)
    ctx.register("lineitem", {
        "rf": rng.integers(0, 3, n).astype(np.int32),
        "ls": rng.integers(0, 2, n).astype(np.int32),
        "qty": rng.integers(1, 50, n).astype(np.int32),
        "price": rng.gamma(2.0, 100.0, n).astype(np.float32),
        "ship": rng.integers(0, 2500, n).astype(np.int32),
    })
    # high-NDV cell: the dense bucket table dwarfs one pass over the rows,
    # so sorted should hold this side of the crossover
    m = 1 << 13
    ctx.register("orders", {
        "okey": rng.integers(0, 1 << 20, m).astype(np.int32),
        "total": rng.gamma(2.0, 100.0, m).astype(np.float32),
    })
    cells = {
        "low_ndv_q1": (n, ctx.table("lineitem")
                       .filter(col("ship") <= 2000)
                       .group_by("rf", "ls", max_groups=8)
                       .agg(sum_("qty").as_("sum_qty"),
                            sum_("price").as_("rev"), count_().as_("cnt"))),
        "high_ndv": (m, ctx.table("orders")
                     .group_by("okey", max_groups=m)
                     .agg(sum_("total").as_("rev"), count_().as_("cnt"))),
    }
    return ctx, cells


def groupby_bench_report(reps: int = 20):
    """Forced sorted-vs-direct grouped-aggregation wall times → BENCH_5.json.

    Two cells (see :func:`_groupby_cells`): the sort-free tier must win the
    low-NDV side, the sorted tier should hold the high-NDV side.  Also
    records what ``optimize="cost"`` actually picked per cell, so future PRs
    have a perf + decision trajectory to compare against.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from repro.compiler import PlanCache

    ctx, cells = _groupby_cells()
    sources = ctx.sources()
    record = {"bench": "groupby_sorted_vs_direct", "reps": reps}
    for cell, (rows, q) in cells.items():
        entry = {"rows": rows}
        for label in ("sorted", "direct"):
            res = ctx.compile(q, strategy={"groupby": label}, cache=PlanCache())
            jax.block_until_ready(res(sources))  # compile + warm
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(res(sources))
            entry[label + "_us"] = (time.perf_counter() - t0) / reps * 1e6
        entry["speedup_direct"] = entry["sorted_us"] / entry["direct_us"]
        decided = ctx.compile(q, optimize="cost", cache=PlanCache())
        entry["decision"] = dict(decided.strategy).get("groupby")
        record[cell] = entry
        print(f"[perf] groupby {cell}: sorted {entry['sorted_us']:.0f} us, "
              f"direct {entry['direct_us']:.0f} us "
              f"({entry['speedup_direct']:.2f}x), "
              f"cost picks {entry['decision']}", flush=True)

    (ROOT / "BENCH_5.json").write_text(json.dumps(record, indent=2))
    print(f"[perf] wrote {ROOT / 'BENCH_5.json'}")


def _join_cells():
    """The three join cells for BENCH_8: a PK-FK probe join with a dense
    2^15 build domain (hash should win), a sparse full-2^20-domain join
    with a small build side (the direct-table build dwarfs the small sort —
    sorted should hold), and the TPC-H Q3/Q12 select→join→group shape for
    whole-pipeline fusion.  The build side carries payload columns the Q3
    query never reads — the unfused plan must materialize them through the
    join, the fused op must not."""
    import numpy as np
    from repro.core.expr import col
    from repro.frontends.dataflow import Context, count_, sum_

    rng = np.random.default_rng(9)
    n, m = 1 << 17, 1 << 15
    ns, ms = 1 << 14, 1 << 11
    ctx = Context(pad_to=1024)
    ctx.register("lineitem", {
        "okey": rng.integers(0, m, n).astype(np.int32),
        "qty": rng.integers(1, 50, n).astype(np.int32),
        "price": rng.gamma(2.0, 100.0, n).astype(np.float32),
        "ship": rng.integers(0, 2500, n).astype(np.int32),
    })
    ctx.register("orders", {
        "okey2": np.arange(m).astype(np.int32),
        "seg": rng.integers(0, 8, m).astype(np.int32),
        "pay1": rng.normal(size=m).astype(np.float32),
        "pay2": rng.normal(size=m).astype(np.float32),
        "pay3": rng.normal(size=m).astype(np.float32),
        "pay4": rng.normal(size=m).astype(np.float32),
    })
    ctx.register("sparse_probe", {
        "k": (rng.integers(0, ms, ns) * 512).astype(np.int32),
        "x": rng.normal(size=ns).astype(np.float32),
    })
    ctx.register("sparse_build", {
        "bk": (np.arange(ms) * 512).astype(np.int32),
        "y": rng.normal(size=ms).astype(np.float32),
    })
    join_low = ctx.table("lineitem").join(
        ctx.table("orders"), left_on=("okey",), right_on=("okey2",))
    join_high = ctx.table("sparse_probe").join(
        ctx.table("sparse_build"), left_on=("k",), right_on=("bk",))
    q3 = (ctx.table("lineitem").filter(col("ship") <= 2000)
          .join(ctx.table("orders"), left_on=("okey",), right_on=("okey2",))
          .group_by("seg", max_groups=8)
          .agg(sum_("price").as_("rev"), count_().as_("cnt")))
    return ctx, {"low_ndv": (n, join_low), "high_ndv": (ns, join_high)}, q3


def join_bench_report(reps: int = 15):
    """Forced sorted-vs-hash join wall times + whole-pipeline fusion →
    BENCH_8.json.

    Low NDV (dense 2^15 build domain): the direct-table probe must beat the
    sort+searchsorted tier and ``optimize="cost"`` must pick it.  High NDV
    (sparse ~2^19 domain, 2^13 build rows): the table build dwarfs the small
    sort, sorted must win and cost must keep it.  The Q3-shaped pipeline
    compares the fused ``vec.FusedJoinGroupAgg`` (jit and Pallas-kernel
    paths) against the unfused select→join→group plan, oracle-checked
    against interp.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax
    from repro.compiler import PlanCache
    from benchmarks.roofline import kernel_roofline, streaming_peak_gbps

    ctx, cells, q3 = _join_cells()
    sources = ctx.sources()

    def best_wall_us(res):
        # best-of-N: robust to scheduler noise on shared CPU runners, and
        # the systematic tier differences are what the bench is after
        jax.block_until_ready(res(sources))  # compile + warm
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(res(sources))
            walls.append(time.perf_counter() - t0)
        return float(min(walls) * 1e6)

    record = {"bench": "join_sorted_vs_hash", "reps": reps}
    for cell, (rows, q) in cells.items():
        entry = {"rows": rows}
        for label in ("sorted", "hash"):
            res = ctx.compile(q, strategy={"join": label}, cache=PlanCache())
            entry[label + "_us"] = best_wall_us(res)
            entry[label + "_ops"] = sorted(set(res.program.opcodes()))
        entry["speedup_hash"] = entry["sorted_us"] / entry["hash_us"]
        decided = ctx.compile(q, optimize="cost", cache=PlanCache())
        entry["decision"] = dict(decided.strategy).get("join")
        record[cell] = entry
        print(f"[perf] join {cell}: sorted {entry['sorted_us']:.0f} us, "
              f"hash {entry['hash_us']:.0f} us "
              f"({entry['speedup_hash']:.2f}x), "
              f"cost picks {entry['decision']}", flush=True)

    # whole-pipeline fusion on the Q3 shape: fused vs unfused, same strategy
    strat = {"join": "hash", "groupby": "direct"}
    fused = ctx.compile(q3, strategy=strat, cache=PlanCache())
    unfused = ctx.compile(q3, strategy=strat, fuse=False, cache=PlanCache())
    kernel = ctx.compile(q3, strategy=strat, use_kernels=True,
                         cache=PlanCache())
    assert "vec.FusedJoinGroupAgg" in fused.program.opcodes()
    assert "vec.HashJoinDirect" in unfused.program.opcodes()
    entry = {
        "fused_us": best_wall_us(fused),
        "unfused_us": best_wall_us(unfused),
        "fused_kernel_us": best_wall_us(kernel),
        "fused_ops": sorted(set(fused.program.opcodes())),
    }
    entry["speedup_fused"] = entry["unfused_us"] / entry["fused_us"]

    # oracle check: fused results must be bit-for-bit the interp answer's
    # groups (float sums compared to 1e-4)
    want = ctx.execute(q3, target="interp")
    ow = np.argsort(np.asarray(want["seg"]).ravel())
    oracle_ok = True
    for res in (fused, unfused, kernel):
        (out,) = res(sources)
        got = out.to_numpy()
        og = np.argsort(got["seg"])
        oracle_ok &= bool(np.allclose(
            got["rev"][og], np.asarray(want["rev"]).ravel()[ow], rtol=1e-4))
        oracle_ok &= bool(np.array_equal(
            got["cnt"][og], np.asarray(want["cnt"]).ravel()[ow]))
    entry["oracle_ok"] = oracle_ok

    # roofline: the fused kernel reads each probe column once and the dense
    # build tables once — compare achieved streaming bandwidth against a
    # measured copy peak
    n = cells["low_ndv"][0]
    probe_bytes = 4 * 4 * n                      # okey, qty, price, ship
    table_bytes = (1 << 15) * 4 * 2              # seg table + present
    entry["roofline"] = kernel_roofline(
        bytes_moved=probe_bytes + table_bytes,
        wall_s=entry["fused_kernel_us"] / 1e6,
        peak_gbps=streaming_peak_gbps())
    record["q3_fusion"] = entry
    print(f"[perf] q3 fusion: unfused {entry['unfused_us']:.0f} us, "
          f"fused {entry['fused_us']:.0f} us "
          f"({entry['speedup_fused']:.2f}x), kernel "
          f"{entry['fused_kernel_us']:.0f} us, oracle_ok={oracle_ok}",
          flush=True)

    (ROOT / "BENCH_8.json").write_text(json.dumps(record, indent=2))
    print(f"[perf] wrote {ROOT / 'BENCH_8.json'}")
    return (record["low_ndv"]["decision"] == "hash"
            and record["low_ndv"]["speedup_hash"] >= 2.0
            and record["high_ndv"]["decision"] == "sorted"
            and record["high_ndv"]["speedup_hash"] < 2.0
            and entry["speedup_fused"] > 1.0 and oracle_ok)


def _dict_cells():
    """The four dictionary-encoding cells for BENCH_9.

    A: Q1-shaped group-by on a low-cardinality *string* key (64 cities over
       2^17 rows) — dictionary ranks unlock the sort-free direct tier and
       the costed search must pick it.
    B: the same shape with every key distinct (~2^21 keys) — over
       ``DICT_MAX_CARD``, so no per-column dictionary exists, *and* over
       ``MAX_DIRECT_BUCKETS`` even as global codes, so the direct tier
       stays off and cost must keep sorted/raw.  (Below 2^20 distinct
       strings the global-code domain is itself direct-eligible — the
       encoding moves the sorted handoff from 2^20 raw span to 2^20
       *distinct values*.)
    C: sparse integer keys (512 distinct over a ~1.5e9 span) — the raw span
       overflows ``MAX_DIRECT_BUCKETS`` but the ``vec.DictEncode`` sandwich
       shrinks it to 512 ranks.
    D: a Q3-shaped string join (2^17 probe rows against 2^14 build keys)
       followed by a small group-by — ranks make the direct-table join
       available on string keys.

    Each cell gets its own :class:`Context` so each builds its own global
    string dictionary.
    """
    import numpy as np
    from repro.frontends.dataflow import Context, count_, sum_

    rng = np.random.default_rng(31)
    n = 1 << 17
    cells = {}

    # A — low-cardinality strings
    card_a = 64
    cities = np.array([f"city-{i:03d}" for i in range(card_a)])
    ctx_a = Context(pad_to=1024)
    ctx_a.register("sales", {
        "city": cities[rng.integers(0, card_a, n)],
        "amount": rng.gamma(2.0, 50.0, n).astype(np.float32),
    })
    q_a = (ctx_a.table("sales").group_by("city", max_groups=card_a)
           .agg(sum_("amount").as_("rev"), count_().as_("n")))
    cells["low_card_string"] = (ctx_a, n, card_a, q_a)

    # B — high-cardinality strings (> MAX_DIRECT_BUCKETS even as codes)
    nb = 1 << 21
    card_b = nb
    users = np.char.add("user-", np.arange(nb).astype(str))
    ctx_b = Context(pad_to=1024)
    ctx_b.register("sales", {
        "city": users,
        "amount": rng.gamma(2.0, 50.0, nb).astype(np.float32),
    })
    q_b = (ctx_b.table("sales").group_by("city", max_groups=nb)
           .agg(sum_("amount").as_("rev"), count_().as_("n")))
    cells["high_card_string"] = (ctx_b, nb, card_b, q_b)

    # C — sparse integer keys
    card_c = 512
    domain = rng.integers(0, 1_500_000_000, card_c).astype(np.int32)
    ctx_c = Context(pad_to=1024)
    ctx_c.register("sales", {
        "city": domain[rng.integers(0, card_c, n)],
        "amount": rng.gamma(2.0, 50.0, n).astype(np.float32),
    })
    q_c = (ctx_c.table("sales").group_by("city", max_groups=card_c)
           .agg(sum_("amount").as_("rev"), count_().as_("n")))
    cells["sparse_int"] = (ctx_c, n, card_c, q_c)

    # D — Q3-shaped string join
    m = 1 << 14
    skus = np.array([f"sku-{i:05d}" for i in range(m)])
    ctx_d = Context(pad_to=1024)
    ctx_d.register("lineitem", {
        "sku": skus[rng.integers(0, m, n)],
        "qty": rng.integers(1, 50, n).astype(np.int32),
        "price": rng.gamma(2.0, 100.0, n).astype(np.float32),
    })
    ctx_d.register("parts", {
        "psku": skus,
        "seg": rng.integers(0, 8, m).astype(np.int32),
    })
    q_d = (ctx_d.table("lineitem")
           .join(ctx_d.table("parts"), left_on=("sku",), right_on=("psku",))
           .group_by("seg", max_groups=8)
           .agg(sum_("price").as_("rev"), count_().as_("cnt")))
    cells["string_join"] = (ctx_d, n, m, q_d)
    return cells


def dict_bench_report(reps: int = 15):
    """Dictionary-encoded direct tiers vs sorted on string/sparse keys →
    BENCH_9.json.

    Per cell: forced ``encode=raw`` sorted tier vs forced dict-encoded
    direct tier wall times (best-of-N), what ``optimize="cost"`` actually
    chose, and an oracle check of both physical plans.  Cells A/C/D check
    against the interp oracle; cell B's ~70k-group aggregation is
    intractable for the O(groups×rows) reference interpreter, so it checks
    against a vectorized numpy oracle (recorded as ``oracle: "numpy"``).
    The dict-direct plan of cell A also gets a streaming-bandwidth
    roofline.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import warnings

    import numpy as np
    import jax
    from repro.compiler import PlanCache
    from benchmarks.roofline import kernel_roofline, streaming_peak_gbps

    cells = _dict_cells()

    def best_wall_us(ctx, res):
        sources = ctx.sources()
        jax.block_until_ready(res(sources))  # compile + warm
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(res(sources))
            walls.append(time.perf_counter() - t0)
        return float(min(walls) * 1e6)

    def numpy_oracle(ctx, table="sales", key="city", val="amount"):
        cols = ctx.tables[table]
        keys, inv = np.unique(cols[key], return_inverse=True)
        rev = np.zeros(len(keys), np.float64)
        np.add.at(rev, inv, cols[val].astype(np.float64))
        cnt = np.bincount(inv, minlength=len(keys))
        return {"city": keys, "rev": rev, "n": cnt}

    def oracle_matches(want, got, int_cols=("n", "cnt")):
        ow = np.argsort(np.asarray(want["city" if "city" in want else "seg"]
                                   ).ravel())
        og = np.argsort(np.asarray(got["city" if "city" in got else "seg"]
                                   ).ravel())
        ok = True
        for k in want:
            w = np.asarray(want[k]).ravel()[ow]
            g = np.asarray(got[k]).ravel()[og]
            if k in int_cols or g.dtype.kind in ("U", "S", "O", "i"):
                ok &= bool(np.array_equal(g.astype(w.dtype), w))
            else:
                ok &= bool(np.allclose(g, w, rtol=1e-3))
        return ok

    record = {"bench": "dict_encoding", "reps": reps,
              "peak_gbps": streaming_peak_gbps()}
    groupby_cells = ("low_card_string", "high_card_string", "sparse_int")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for cell in groupby_cells:
            ctx, rows, card, q = cells[cell]
            entry = {"rows": rows, "key_cardinality": card}
            raw = ctx.compile(q, strategy={"groupby": "sorted",
                                           "encode": "raw"},
                              cache=PlanCache())
            dct = ctx.compile(q, strategy={"groupby": "direct",
                                           "encode": "dict"},
                              cache=PlanCache())
            entry["sorted_raw_us"] = best_wall_us(ctx, raw)
            entry["direct_dict_us"] = best_wall_us(ctx, dct)
            entry["direct_dict_ops"] = sorted(set(dct.program.opcodes()))
            entry["speedup_dict"] = (entry["sorted_raw_us"]
                                     / entry["direct_dict_us"])
            decided = ctx.compile(q, optimize="cost", cache=PlanCache())
            entry["decision"] = {k: v for k, v in dict(decided.strategy
                                                       ).items()
                                 if k in ("groupby", "encode")}
            if cell == "high_card_string":
                entry["oracle"] = "numpy"
                want = numpy_oracle(ctx)
            else:
                entry["oracle"] = "interp"
                want = ctx.execute(q, target="interp")
            for label, strat in (("sorted_raw", {"groupby": "sorted",
                                                 "encode": "raw"}),
                                 ("direct_dict", {"groupby": "direct",
                                                  "encode": "dict"})):
                got = ctx.execute(q, target="local", strategy=strat)
                entry[f"oracle_ok_{label}"] = oracle_matches(want, got)
            if cell == "low_card_string":
                # dict-direct moves the i32 code column + f32 values once,
                # plus the compacted card-sized bucket epilogue
                entry["roofline"] = kernel_roofline(
                    bytes_moved=rows * 8 + card * 12,
                    wall_s=entry["direct_dict_us"] / 1e6,
                    peak_gbps=record["peak_gbps"])
            record[cell] = entry
            print(f"[perf] dict {cell}: sorted/raw "
                  f"{entry['sorted_raw_us']:.0f} us, direct/dict "
                  f"{entry['direct_dict_us']:.0f} us "
                  f"({entry['speedup_dict']:.2f}x), cost picks "
                  f"{entry['decision']}", flush=True)

        # D — the string join
        ctx, rows, m, q = cells["string_join"]
        entry = {"rows": rows, "build_keys": m}
        raw = ctx.compile(q, strategy={"join": "sorted", "encode": "raw"},
                          cache=PlanCache())
        dct = ctx.compile(q, strategy={"join": "hash", "encode": "dict"},
                          cache=PlanCache())
        entry["sorted_raw_us"] = best_wall_us(ctx, raw)
        entry["hash_dict_us"] = best_wall_us(ctx, dct)
        entry["hash_dict_ops"] = sorted(set(dct.program.opcodes()))
        entry["speedup_dict"] = entry["sorted_raw_us"] / entry["hash_dict_us"]
        decided = ctx.compile(q, optimize="cost", cache=PlanCache())
        entry["decision"] = {k: v for k, v in dict(decided.strategy).items()
                             if k in ("join", "encode")}
        entry["oracle"] = "interp"
        want = ctx.execute(q, target="interp")
        for label, strat in (("sorted_raw", {"join": "sorted",
                                             "encode": "raw"}),
                             ("hash_dict", {"join": "hash",
                                            "encode": "dict"})):
            got = ctx.execute(q, target="local", strategy=strat)
            entry[f"oracle_ok_{label}"] = oracle_matches(want, got)
        record["string_join"] = entry
        print(f"[perf] dict string_join: sorted/raw "
              f"{entry['sorted_raw_us']:.0f} us, hash/dict "
              f"{entry['hash_dict_us']:.0f} us "
              f"({entry['speedup_dict']:.2f}x), cost picks "
              f"{entry['decision']}", flush=True)

    (ROOT / "BENCH_9.json").write_text(json.dumps(record, indent=2))
    print(f"[perf] wrote {ROOT / 'BENCH_9.json'}")
    low = record["low_card_string"]
    high = record["high_card_string"]
    oracle_ok = all(v for c in ("low_card_string", "high_card_string",
                                "sparse_int", "string_join")
                    for k, v in record[c].items()
                    if k.startswith("oracle_ok_"))
    return (low["decision"] == {"groupby": "direct", "encode": "dict"}
            and low["speedup_dict"] >= 2.0
            and high["decision"].get("groupby") == "sorted"
            and high["decision"].get("encode", "raw") == "raw"
            and oracle_ok)


def _stream_cell():
    """The streaming cell for BENCH_10: a Q1-shaped filtered group-by over
    2^18 rows delivered as 8192-row micro-batches."""
    import numpy as np
    from repro.core.expr import col
    from repro.frontends.dataflow import Context, count_, sum_

    rng = np.random.default_rng(13)
    n = 1 << 18
    ctx = Context(pad_to=1024)
    ctx.register("sales", {
        "region": rng.integers(0, 8, n).astype(np.int32),
        "amount": rng.gamma(2.0, 50.0, n).astype(np.float32),
        "year": rng.integers(2018, 2026, n).astype(np.int32),
    })
    q = (ctx.table("sales").filter(col("year") >= 2020)
         .group_by("region", max_groups=8)
         .agg(sum_("amount").as_("rev"), count_().as_("n")))
    return ctx, n, q


def stream_bench_report(reps: int = 7):
    """Streaming-target trajectory → BENCH_10.json.

    Three numbers the streaming story stands on:

    * **sustained throughput** — rows/s folding the stream as sequenced
      micro-batches through :class:`StreamConsumer` (best-of-N, fold chain
      synced before the clock stops);
    * **snapshot overhead** — the same fold with a durable
      ``CheckpointManager`` snapshot every ``snapshot_every`` batches vs
      no checkpointing at all; the ratio must stay **< 1.10** (durability
      may not tax steady-state throughput more than 10%);
    * **recovery time to caught-up** — a ``stream.batch`` kill mid-stream,
      then the measured wall from failure to the consumer having restored
      and replayed the uncommitted suffix (``stream.recovery_s``), plus an
      exactly-once oracle check of the final answer.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import shutil
    import tempfile

    import numpy as np
    import jax
    from repro.compiler import PlanCache
    from repro.distributed.checkpoint import CheckpointManager
    from repro.frontends.dataflow import _to_numpy
    from repro.launch.serve import StreamConsumer, microbatches, stream_loop
    from repro.obs import tracing
    from repro.robust.inject import inject

    batch_rows, snapshot_every = 8192, 16
    ctx, n, q = _stream_cell()
    res = ctx.compile(q, target="stream", stream_table="sales",
                      batch_rows=batch_rows, cache=PlanCache())
    batches = microbatches(ctx.tables["sales"], batch_rows)
    sources = ctx.sources()

    def fold_wall(ckpt_dir=None):
        c = StreamConsumer(
            res, sources,
            checkpoint=(CheckpointManager(ckpt_dir, n_shards=1, keep=2)
                        if ckpt_dir else None),
            snapshot_every=snapshot_every)
        t0 = time.perf_counter()
        for mb in batches:
            c.process(mb)
        c.snapshot()
        jax.block_until_ready(c.results())  # the fold chain is async
        return time.perf_counter() - t0, c

    fold_wall()  # warm the jitted segments
    fold_wall()
    base_s = min(fold_wall()[0] for _ in range(reps))
    ckpt_walls = []
    snapshots = 0
    for _ in range(reps):
        d = tempfile.mkdtemp(prefix="stream_bench_ckpt_")
        try:
            wall, c = fold_wall(d)
            ckpt_walls.append(wall)
            snapshots = c.stats.snapshots
        finally:
            shutil.rmtree(d, ignore_errors=True)
    ckpt_s = min(ckpt_walls)

    # recovery: kill the first fold, measure failure → caught-up
    d = tempfile.mkdtemp(prefix="stream_bench_recover_")
    try:
        c = StreamConsumer(res, sources,
                           checkpoint=CheckpointManager(d, n_shards=1,
                                                        keep=2),
                           snapshot_every=snapshot_every)
        with tracing() as tr:
            with inject("stream.batch", rate=1.0, times=1, seed=0):
                out = stream_loop(batches, c, max_recoveries=3)
        recovery_s = tr.histograms["stream.recovery_s"][0]
    finally:
        shutil.rmtree(d, ignore_errors=True)
    want = ctx.execute(q, target="interp")
    got = _to_numpy(out[0])
    ow = np.argsort(np.asarray(want["region"]).ravel())
    og = np.argsort(np.asarray(got["region"]).ravel())
    oracle_ok = all(
        bool(np.allclose(np.asarray(got[k]).ravel()[og],
                         np.asarray(want[k]).ravel()[ow], rtol=1e-4))
        for k in want)

    record = {
        "bench": "stream", "reps": reps, "rows": n,
        "batch_rows": batch_rows, "n_batches": len(batches),
        "snapshot_every": snapshot_every, "snapshots": snapshots,
        "base_wall_s": base_s, "checkpointed_wall_s": ckpt_s,
        "snapshot_overhead_ratio": ckpt_s / base_s,
        "snapshot_overhead_guard": "<1.10",
        "throughput_rows_per_s": n / base_s,
        "batch_fold_ms": base_s / len(batches) * 1e3,
        "recovery_s": recovery_s,
        "recovery_restores": c.stats.restores,
        "recovery_replayed": c.stats.replayed,
        "oracle_ok_recovered": oracle_ok,
    }
    (ROOT / "BENCH_10.json").write_text(json.dumps(record, indent=2))
    print(f"[perf] stream: {n} rows in {len(batches)}x{batch_rows} batches, "
          f"{record['throughput_rows_per_s'] / 1e6:.2f} Mrows/s, snapshot "
          f"overhead {record['snapshot_overhead_ratio']:.3f}x, recovery "
          f"{recovery_s * 1e3:.1f} ms, oracle_ok={oracle_ok}", flush=True)
    print(f"[perf] wrote {ROOT / 'BENCH_10.json'}")
    return (record["snapshot_overhead_ratio"] < 1.10
            and recovery_s < 60.0 and oracle_ok)


def robust_bench_report(reps: int = 30):
    """Guarded-execution overhead with no faults armed → BENCH_7.json.

    The robustness layer must be free when nothing fails: on the low-NDV
    Q1-style hot path, a ``guard=True`` (default) compile+execute must stay
    within 5% of ``guard=False`` — the armed exec guard is one attribute
    check per call and every unarmed injection site is one list-truthiness
    check.  Also records, informationally, the wall time to *recover* from
    an injected backend-compile fault through the fallback ladder.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import statistics
    import warnings
    import jax
    from repro.compiler import PlanCache
    from repro.robust.inject import inject

    ctx, cells = _groupby_cells()
    sources = ctx.sources()
    q = cells["low_ndv_q1"][1]
    record = {"bench": "guarded_execution_overhead", "reps": reps,
              "cell": "low_ndv_q1"}

    def median_call(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    def median_compile(**kw):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            ctx.compile(q, cache=PlanCache(), **kw)
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    guarded = ctx.compile(q, cache=PlanCache())            # guard defaults on
    unguarded = ctx.compile(q, cache=PlanCache(), guard=False)
    jax.block_until_ready(guarded(sources))                # warm + disarm
    jax.block_until_ready(unguarded(sources))
    guarded_s = median_call(lambda: guarded(sources))
    unguarded_s = median_call(lambda: unguarded(sources))
    ratio = guarded_s / unguarded_s
    ok = ratio < 1.05
    record["overhead_guard"] = {
        "guarded_us": guarded_s * 1e6, "unguarded_us": unguarded_s * 1e6,
        "ratio": ratio, "threshold": 1.05, "pass": ok,
    }
    record["compile_overhead"] = {
        "guarded_ms": median_compile() * 1e3,
        "unguarded_ms": median_compile(guard=False) * 1e3,
    }
    print(f"[perf] guards-enabled no-fault overhead: guarded "
          f"{guarded_s * 1e6:.0f} us, unguarded {unguarded_s * 1e6:.0f} us "
          f"→ ratio {ratio:.3f} ({'PASS' if ok else 'FAIL'} < 1.05)",
          flush=True)

    # informational: how long one trip down the fallback ladder costs
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        t0 = time.perf_counter()
        with inject("backend.compile", mode="raise", times=1):
            res = ctx.compile(q, cache=PlanCache())
        jax.block_until_ready(res(sources))
        recover_s = time.perf_counter() - t0
    record["fault_recovery"] = {
        "point": "backend.compile", "wall_s": recover_s,
        "degraded": list(res.degraded),
    }
    print(f"[perf] fallback recovery (backend.compile fault): "
          f"{recover_s * 1e3:.0f} ms via {' → '.join(res.degraded)}",
          flush=True)

    (ROOT / "BENCH_7.json").write_text(json.dumps(record, indent=2))
    print(f"[perf] wrote {ROOT / 'BENCH_7.json'}")
    return ok


def main():
    OUT.mkdir(parents=True, exist_ok=True)
    if "--robust-bench" in sys.argv:
        if not robust_bench_report():
            sys.exit(1)
        return
    if "--groupby-bench" in sys.argv:
        groupby_bench_report()
        return
    if "--join-bench" in sys.argv:
        if not join_bench_report():
            sys.exit(1)
        return
    if "--dict-bench" in sys.argv:
        if not dict_bench_report():
            sys.exit(1)
        return
    if "--stream-bench" in sys.argv:
        if not stream_bench_report():
            sys.exit(1)
        return
    compile_pass_report()
    if "--compile-only" in sys.argv:
        return
    groupby_bench_report()
    for arch, shape in CELLS:
        for step, env_over in STEPS.items():
            out = OUT / f"{arch}__{shape}__{step}.json"
            if out.exists():
                print(f"[perf] {arch}×{shape} {step}: cached", flush=True)
                continue
            rec = run(arch, shape, step, env_over)
            out.write_text(json.dumps(rec, indent=2, default=str))
            keys = ("device_mem_gib", "t_compute_s", "t_memory_s", "t_collective_s",
                    "roofline_fraction")
            vals = {k: rec.get(k) for k in keys}
            print(f"[perf] {arch}×{shape} {step}: {vals}", flush=True)

    # markdown table
    print("\n| cell | step | GiB/dev | t_compute | t_memory | t_collective | roofline |")
    print("|---|---|---|---|---|---|---|")
    for arch, shape in CELLS:
        for step in STEPS:
            f = OUT / f"{arch}__{shape}__{step}.json"
            if not f.exists():
                continue
            r = json.loads(f.read_text())
            if "error" in r:
                print(f"| {arch}×{shape} | {step} | ERROR |  |  |  |  |")
                continue
            print(f"| {arch}×{shape} | {step} | {r.get('device_mem_gib','')} "
                  f"| {r.get('t_compute_s', 0):.3e} | {r.get('t_memory_s', 0):.3e} "
                  f"| {r.get('t_collective_s', 0):.3e} "
                  f"| {r.get('roofline_fraction', 0):.4f} |")


if __name__ == "__main__":
    main()
