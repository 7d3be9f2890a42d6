"""TPC-H through CVM — the paper's main workload (Figs. 2–4).

Runs all six implemented queries through the full rewrite pipeline on the
local backend, validates against the numpy references, and prints the
optimized physical plans.

Run: PYTHONPATH=src python examples/tpch_cvm.py [--sf 0.005] [--parallel 4]
"""

import argparse
import time

import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.relational import tpch

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--sf", type=float, default=0.005)
ap.add_argument("--parallel", type=int, default=None)
args = ap.parse_args()

tables = tpch.generate(sf=args.sf, seed=0)
ctx = tpch.make_context(tables)
print(f"TPC-H sf={args.sf}: lineitem={len(tables['lineitem']['l_orderkey']):,} rows, "
      f"orders={len(tables['orders']['o_orderkey']):,}, part={len(tables['part']['p_partkey']):,}")

for qname in sorted(tpch.QUERIES):
    frame = tpch.QUERIES[qname](ctx)
    compiled = ctx.compile(frame, parallel=args.parallel)
    sources = ctx.sources()
    compiled(sources)  # warm-up (compile)
    t0 = time.time()
    reps = 3
    for _ in range(reps):
        (out,) = compiled(sources)
    dt = (time.time() - t0) / reps * 1e3

    from repro.frontends.dataflow import _to_numpy
    got = _to_numpy(out)
    want = tpch.REFERENCES[qname](tables)
    checks = []
    for kcol in want:
        g = np.sort(np.asarray(got[kcol], dtype=np.float64).ravel())
        w = np.sort(np.asarray(want[kcol], dtype=np.float64).ravel())
        checks.append(np.allclose(g, w, rtol=2e-3))
    status = "✓" if all(checks) else "✗ MISMATCH"
    n_ops = len(compiled.program.opcodes())
    print(f"  {qname:>4}: {dt:7.1f} ms   {n_ops:3d} physical ops   ref {status}")
