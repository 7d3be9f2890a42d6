"""Quickstart: one frontend program, three execution strategies.

The CVM promise (paper §1): write the analysis once in the generic Python
frontend; the compiler rewrites it for each platform.  This script builds a
small analytics query and runs it

  1. sequentially (local JITQ-style backend: one fused XLA pipeline),
  2. parallelized (the Split/ConcurrentExecute/pre-aggregate rewrite),
  3. showing the rewritten IR at each stage.

Run: PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core.expr import col
from repro.frontends.dataflow import Context, avg_, count_, sum_
from repro.launch.cache import enable_compile_cache

enable_compile_cache()

# -- make a toy sales table ---------------------------------------------------
rng = np.random.default_rng(0)
n = 10_000
ctx = Context(pad_to=256)
ctx.register("sales", {
    "region": rng.integers(0, 8, n).astype(np.int32),
    "amount": rng.gamma(2.0, 50.0, n).astype(np.float32),
    "discount": rng.uniform(0, 0.2, n).astype(np.float32),
    "year": rng.integers(2018, 2026, n).astype(np.int32),
})

# -- one frontend program ------------------------------------------------------
q = (
    ctx.table("sales")
    .filter((col("year") >= 2020) & (col("discount") < 0.15))
    .with_columns(net=col("amount") * (1.0 - col("discount")))
    .group_by("region", max_groups=8)
    .agg(sum_("net").as_("revenue"), avg_("amount").as_("avg_amount"),
         count_().as_("n"))
    .order_by("region")
)

print("== logical CVM program (rel.* flavor) ==")
print(q.program("sales_by_region").render())

# -- 1. sequential local backend ----------------------------------------------
seq = q.collect()
print("\n== sequential result ==")
for i in range(len(seq["region"])):
    print(f"  region {seq['region'][i]}: revenue={seq['revenue'][i]:.0f} "
          f"avg={seq['avg_amount'][i]:.1f} n={seq['n'][i]}")

# -- 2. parallelized (paper Alg. 1 → Alg. 2) ------------------------------------
# ctx.compile routes through the unified driver: one entry point per target,
# declarative lowering path, per-pass instrumentation, structural plan cache.
compiled = ctx.compile(q, parallel=4)
print("\n== parallelized physical program (vec.* flavor, 4 workers) ==")
print(compiled.program.render())
print("\n== per-pass instrumentation ==")
print(compiled.explain())
par = q.collect(parallel=4)
assert np.allclose(np.sort(seq["revenue"]), np.sort(par["revenue"]), rtol=1e-5)
print("\nparallel == sequential ✓")

# the abstract machine itself is a registered target — the oracle agrees
oracle = q.collect(target="interp")
assert np.allclose(np.sort(seq["revenue"]), np.sort(np.asarray(oracle["revenue"])),
                   rtol=1e-5)
print("interp (abstract machine) == sequential ✓")

# recompiling the same frontend program is a structural-plan-cache hit
again = ctx.compile(q, parallel=4)
assert again.cache_hit and again.executable is compiled.executable
print("repeated compile hit the plan cache ✓")

# -- 3. scalar aggregate fuses into the single-pass kernel pipeline -------------
q6ish = (
    ctx.table("sales")
    .filter(col("discount").between(0.05, 0.07))
    .agg(sum_(col("amount") * col("discount")).as_("promo_revenue"))
)
c = ctx.compile(q6ish)
ops = c.program.opcodes()
print(f"\nscalar-agg pipeline ops: {ops}")
assert "vec.FusedSelectAgg" in ops, "fusion should produce the single-pass kernel op"
print("fused select+aggregate pipeline ✓ →", q6ish.collect())
