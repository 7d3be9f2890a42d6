"""Chip smoke test: the TPC-H query path on a TPU, through ``Context.compile``.

Generates TPC-H from a seed, puts the tables on the device once, and runs all
six ``tpch.QUERIES`` through the normal entry points (``Context.compile``,
then a call on ``ctx.sources(plan)``), each checked against its numpy
reference with the comparison the tests use.  The fallback guard stays on, as
users get it; a query that steps down the ladder (``CompileResult.degraded``
non-empty or the ``robust.fallback.step`` counter above zero) fails the run.

One chip (default): two phases on the ``local`` target, ``use_kernels=False``
with the default strategy, then ``use_kernels=True`` with
``groupby=direct`` so that Q1 reaches the grouped Pallas kernel.  In the
kernel phase Q1 and Q6 must contain Mosaic kernels (``tpu_custom_call``).

``--chips 4``: the six queries on the ``spmd`` target with ``parallel=4``
over a mesh of four chips; the target places the tables, each device holding
its slice of the rows.

Each query prints one JSON line: ``plan_s`` (``Context.compile``),
``batch_compile_s`` (the XLA compile of the whole phase, all plans at once),
``first_traced_s`` (the guarded first call, under the tracer without its
cardinality taps, so on the executable users run), ``first_plain_s`` and
``warm_s`` (untraced calls), the ``tpu_custom_call``
count, ``degraded``, and each device's peak bytes since start.  The last
line is ``{"ok": true, "device": {...}}``.  Exits non-zero without that line
when JAX finds no TPU or any query fails.

Run: python chip_smoke.py [--sf 100] [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: queries whose kernel-phase plan the gates route to Mosaic kernels
KERNEL_QUERIES = ("q1", "q6")
KERNEL = 'custom_call_target="tpu_custom_call"'
WARM_RUNS = 2
#: XLA compiles run at once (each phase has six plans)
COMPILE_THREADS = 6


def fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return 1


def peak_bytes(devices) -> list:
    """Each device's ``peak_bytes_in_use``: the running maximum since the
    process started (JAX has no reset), not a per-query peak."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def fallback_steps(tracer) -> float:
    return tracer.counters.get("robust.fallback.step", 0.0)


def compile_plans(ctx, phase: str, **compile_kw):
    """``Context.compile`` for every query of one phase, traced; returns one
    record per query and the plans that compiled."""
    from repro.obs.trace import tracing
    from repro.relational import tpch

    recs, plans = {}, {}
    for qname in sorted(tpch.QUERIES):
        rec = recs[qname] = {"phase": phase, "query": qname, "ok": False}
        try:
            with tracing() as tracer:
                t0 = time.perf_counter()
                plans[qname] = ctx.compile(tpch.QUERIES[qname](ctx),
                                           **compile_kw)
                rec["plan_s"] = time.perf_counter() - t0
            rec["fallback_steps"] = fallback_steps(tracer)
        except Exception as e:  # reported below; the other queries still run
            rec["error"] = f"{type(e).__name__}: {e}"[:2000]
    return recs, plans


def xla_compile_all(plans, sources) -> dict:
    """XLA-compile every plan's jitted function at once, the one every call
    runs.  A sort over 60M rows takes minutes to compile for a TPU and
    compiles release the GIL; the calls then reuse these executables.
    Returns, per plan, the future of its compiled HLO text; returns when all
    have finished."""
    def compile_one(fn) -> str:
        return fn.lower(dict(sources)).compile().as_text()

    with ThreadPoolExecutor(COMPILE_THREADS) as pool:
        return {q: pool.submit(compile_one, res.executable.fn)
                for q, res in plans.items()}


def run_phase(sources, wants, recs, plans, devices) -> list:
    """Run every compiled plan of one phase; fills in and prints ``recs``.

    The first call of each plan runs with the guard armed, as users get it,
    under the tracer without its cardinality taps (so on the executable
    users run): that is where ``degraded`` and ``robust.fallback.step`` are
    read.  The timed calls then run with tracing off."""
    import jax

    from repro.frontends.dataflow import _to_numpy
    from repro.obs.trace import tracing
    from repro.relational import tpch

    t0 = time.perf_counter()
    compiled = xla_compile_all(plans, sources)
    batch_s = time.perf_counter() - t0
    for qname, rec in recs.items():
        try:
            if qname not in plans:
                continue
            res, keys = plans[qname], tpch.GROUP_KEYS.get(qname, ())
            rec["batch_compile_s"] = batch_s
            rec["tpu_custom_calls"] = compiled[qname].result().count(KERNEL)
            with tracing(cardinalities=False) as tracer:
                t0 = time.perf_counter()
                outs = jax.block_until_ready(res(sources))
                rec["first_traced_s"] = time.perf_counter() - t0
            rec["fallback_steps"] += fallback_steps(tracer)
            rec["degraded"] = list(res.degraded)
            tpch.assert_result_close(_to_numpy(outs[0]), wants[qname], keys)
            warm = []
            for _ in range(1 + WARM_RUNS):
                t0 = time.perf_counter()
                outs = jax.block_until_ready(res(sources))
                warm.append(time.perf_counter() - t0)
            rec["first_plain_s"], rec["warm_s"] = warm[0], warm[1:]
            rec["peak_bytes_in_use_since_start"] = peak_bytes(devices)
            tpch.assert_result_close(_to_numpy(outs[0]), wants[qname], keys)
            if rec["degraded"] or rec["fallback_steps"]:
                rec["error"] = "plan degraded through the fallback ladder"
            else:
                rec["ok"] = True
        except Exception as e:  # one query's failure is reported, the rest run
            rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        finally:
            print(json.dumps(rec), flush=True)
    return list(recs.values())


def run_phases(sf: float, chips: int, devices, seed: int = 0) -> list:
    """Generate TPC-H at ``sf`` and run every query of each phase (two
    ``local`` phases on one chip, one ``spmd`` phase on ``chips > 1``).

    The tables are placed once, as the first phase's plans read them: on
    the spmd target each device gets its slice of the rows."""
    import jax

    from repro.relational import tpch

    t0 = time.perf_counter()
    tables = tpch.generate(sf=sf, seed=seed)
    ctx = tpch.make_context(tables)
    wants = {q: ref(tables) for q, ref in tpch.REFERENCES.items()}
    print(json.dumps({"generate_s": time.perf_counter() - t0}), flush=True)

    if chips > 1:
        phases = [("spmd", dict(target="spmd", parallel=chips))]
    else:
        phases = [("xla", dict(target="local")),
                  ("kernels", dict(target="local", use_kernels=True,
                                   strategy={"groupby": "direct"}))]
    sources, records = None, []
    for phase, kw in phases:
        recs, plans = compile_plans(ctx, phase, **kw)
        if sources is None:
            t0 = time.perf_counter()
            sources = jax.block_until_ready(
                ctx.sources(next(iter(plans.values()), None)))
            for name, vt in sources.items():
                print(json.dumps({
                    "table": name,
                    "rows": len(next(iter(tables[name].values()))),
                    "capacity": vt.capacity,
                    "resident_bytes": sum(a.nbytes
                                          for a in jax.tree.leaves(vt))}))
            print(json.dumps({"place_s": time.perf_counter() - t0,
                              "peak_bytes_in_use": peak_bytes(devices)}),
                  flush=True)
        records += run_phase(sources, wants, recs, plans, devices)
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=100.0,
                    help="TPC-H scale for tpch.generate (100 gives the "
                         "spec's SF10 row counts)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX sees {devices[0].platform} devices")
    if len(devices) < args.chips:
        return fail(f"--chips {args.chips} but JAX sees {len(devices)} devices")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no repro package under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))

    from repro.launch.cache import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache(),
                      "device_kind": devices[0].device_kind,
                      "devices": len(devices), "sf": args.sf}), flush=True)
    records = run_phases(args.sf, args.chips, devices[:args.chips])
    bad = [r for r in records if not r["ok"]]
    if args.chips == 1:
        bad += [r for r in records if r["phase"] == "kernels"
                and r["query"] in KERNEL_QUERIES and r["ok"]
                and not r["tpu_custom_calls"]]
    if bad:
        return fail(f"{len(bad)} failed: "
                    + ", ".join(f"{r['phase']}/{r['query']}" for r in bad))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
