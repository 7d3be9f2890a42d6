"""Benchmark orchestrator — one function per paper figure/table.

Each figure runs in its own subprocess (fig3/fig4 need their own
``XLA_FLAGS`` device counts, which jax locks at first init).  Prints
``name,us_per_call,derived`` CSV.

Run: PYTHONPATH=src python -m benchmarks.run [--only NAME] [--explain]

``--explain`` prints the cost-based plan-selection decision for a TPC-H
grouped aggregation on the spmd target at low and high group cardinality:
candidates considered, estimated vs measured cost, and the winner.
"""

import argparse
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.hermetic import subprocess_env  # noqa: E402

FIGS = [
    ("fig2_tpch_single", "benchmarks.fig2_tpch_single"),
    ("fig2_kmeans", "benchmarks.fig2_kmeans"),
    ("fig3_tpch_parallel", "benchmarks.fig3_tpch_parallel"),
    ("fig4_elastic", "benchmarks.fig4_elastic"),
    ("roofline", "benchmarks.roofline"),
]

EXPLAIN_SCRIPT = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.compiler import compile as cvm_compile
from repro.frontends.dataflow import count_, sum_
from repro.relational import tpch

tables = tpch.generate(sf=0.01, seed=0)
ctx = tpch.make_context(tables, pad_to=1024)

# low group cardinality: Q1's (returnflag, linestatus) — 6 groups
low = tpch.q1(ctx)
# high group cardinality: per-order grouping — ~#orders groups
high = (ctx.table("lineitem")
        .group_by("l_orderkey", max_groups=ctx.capacity("orders"))
        .agg(sum_("l_quantity").as_("qty"), count_().as_("n")))

for name, frame in [("q1 (low NDV)", low), ("per-order (high NDV)", high)]:
    res = cvm_compile(frame.program(), target="spmd", parallel=8,
                      catalog=ctx.catalog(), optimize="cost", cache=False)
    print(f"=== {name} ===")
    print(res.explain())
    print()
'''


def _run(args, timeout: int) -> str:
    """Run one child benchmark; a child that fails fails the run."""
    env = subprocess_env(ROOT, extra_pythonpath=[ROOT])
    proc = subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=str(ROOT))
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        raise SystemExit(f"{' '.join(args)[:80]}: exit {proc.returncode}")
    return proc.stdout.strip()


def run_fig(module: str, timeout: int = 1800) -> str:
    return _run(["-m", module], timeout)


def run_explain(timeout: int = 1800) -> str:
    return _run(["-c", EXPLAIN_SCRIPT], timeout)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--explain", action="store_true",
                    help="print the cost-model plan decisions instead of "
                         "running the figures")
    args = ap.parse_args()

    if args.explain:
        print(run_explain())
        return

    print("name,us_per_call,derived")
    for name, module in FIGS:
        if args.only and args.only not in name:
            continue
        out = run_fig(module)
        for line in out.splitlines():
            if line and "," in line:
                print(line)


if __name__ == "__main__":
    main()
