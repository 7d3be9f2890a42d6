"""Run one cell of the chip benchmark once and print its result line.

    python benchmarks/chip/bench.py --workload <config>.<traffic> --seed <n>
        --seconds <s> --trace <0|1>

A cell names a configuration (``configs/<config>.json``: the TPC-H scale,
the tables and columns registered, and the target with its number of
devices) and a traffic mix (``traffic/<traffic>.json``: the query stream,
the entry it is sent through and the compile options).  Each metric that ``BENCHMARK.json``
lists for the cell is computed by ``metrics/<name>.py``.  So a later change
adds a configuration, a mix or a metric as files and entries, and edits none.

The run: check that JAX sees enough TPUs (else exit 1, no result); make the
tables from ``--seed``, place them and compile the stream's plans (set-up);
then one client sends the stream again and again, each query after the last
one's answer, until ``--seconds`` have passed and the stream is whole.  With
``--trace 1`` the window runs under the JAX profiler and the per-layer
metrics are read from the trace.  Once the window has closed and the device
memory is read, every answer is compared with the float64 reference
(``checks.py``).  The last line of stdout is the result as JSON.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: compile cache and traces: a fixed path inside the checkout, so that only
#: the first run of a cell there compiles
CACHE = ROOT / ".bench_cache"


# ---------------------------------------------------------------------------
# the cell, as data
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    #: metric name -> unit, of the metrics the cell reports
    end_to_end: Dict[str, str]
    per_layer: Dict[str, str]

    def options(self) -> Dict[str, Any]:
        """Keyword arguments of ``Context.compile`` and ``Frame.collect``:
        the configuration's target and devices, the traffic's options."""
        return {"target": self.config["target"], "parallel": self.config.get("parallel"),
                **self.traffic.get("compile", {})}


def _load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration and
    traffic files and the names of the metrics it reports."""
    spec = _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = _load_json(HERE / "configs" / f"{w['config']}.json")
    devices = config.get("parallel") or 1
    if devices != w["chips"] or (config["target"] == "local") != (devices == 1):
        raise SystemExit(f"bench: {name} asks for {w['chips']} chips, its configuration "
                         f"runs target {config['target']!r} on {devices} devices")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=config,
        traffic=_load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end={m["name"]: m["unit"] for m in spec["end_to_end"] if _applies(m, name)},
        per_layer={m["name"]: m["unit"] for m in spec["per_layer"] if _applies(m, name)})


def load_reader(metric: str):
    """``read(run)`` of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    if spec is None or not path.is_file():
        raise SystemExit(f"bench: no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(kind: str) -> Dict[str, float]:
    """The chip's published peaks; a device not in ``peaks.json`` is an error."""
    table = _load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def require_devices(chips: int):
    """The first ``chips`` TPUs JAX sees; exits 1 when it sees fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU, JAX sees {devices[0].platform} devices")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPUs, JAX sees {len(devices)}")
    return devices[:chips]


def import_program() -> None:
    """Put the checkout's ``src`` first on the path: the system under test."""
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"bench: no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))


def use_checkout_dirs() -> None:
    """JAX's compile cache and the TPU runtime's logs go inside the
    checkout, at fixed paths; call before JAX starts its backend."""
    import os

    import jax

    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    # no size limit from the environment: an entry over the limit is never
    # written, and its program compiles again in every run
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# the entries the client calls
# ---------------------------------------------------------------------------


@dataclass
class Query:
    """One query of the window, timed on the host clock."""

    name: str
    latency_s: float = 0.0
    phases: Dict[str, float] = field(default_factory=dict)
    error: Optional[str] = None
    #: JAX compiles and compile-cache loads while the query ran
    compiles: int = 0


@contextlib.contextmanager
def phase(rec: Query, name: str):
    """Time one phase of a query, and mark it in the profiler's trace."""
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"bench/{name}/{rec.name}"):
        yield
    rec.phases[name] = time.perf_counter() - t0


def fetch(out: Any) -> Dict[str, np.ndarray]:
    """A plan's result as numpy columns on the host: a table's live rows, or
    a scalar aggregate's columns."""
    if isinstance(out, dict):
        return {k: np.asarray(v) for k, v in out.items()}
    return out.to_numpy()


class Prepared:
    """Build the Frame, ``Context.compile`` it (a plan-cache hit once warm)
    and call the plan on the tables placed once in set-up."""

    def __init__(self, ctx, builders, sources, options) -> None:
        self.ctx, self.builders, self.sources = ctx, builders, sources
        self.options = options

    def run(self, rec: Query) -> Dict[str, np.ndarray]:
        with phase(rec, "compile"):
            plan = self.ctx.compile(self.builders[rec.name](self.ctx), **self.options)
        with phase(rec, "call"):
            (out,) = plan(self.sources)
        with phase(rec, "fetch"):
            return fetch(out)


class Collect:
    """``Frame.collect``: the frontend's one call."""

    def __init__(self, ctx, builders, sources, options) -> None:
        self.ctx, self.builders, self.options = ctx, builders, options

    def run(self, rec: Query) -> Dict[str, np.ndarray]:
        with phase(rec, "call"):
            return self.builders[rec.name](self.ctx).collect(**self.options)


ENTRIES = {"prepared": Prepared, "collect": Collect}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclass
class Run:
    """What the metric readers read."""

    queries: List[Query]
    window_s: float
    setup_s: float
    peak_bytes: int
    peaks: Dict[str, float]
    least_bytes: Dict[str, int]
    trace: Any = None  # trace_reduce.Trace of the window, in a traced run

    def done(self) -> List[Query]:
        return [q for q in self.queries if q.error is None]


def precompile(plans, sources) -> None:
    """XLA-compile every plan's jitted function at once (compiles release
    the GIL, and a sort over tens of millions of rows takes minutes to
    compile for a TPU); the calls then find the executables compiled."""
    fns = [p.executable.fn for p in plans.values()]
    with ThreadPoolExecutor(len(fns)) as pool:
        for fut in [pool.submit(lambda f=f: f.lower(dict(sources)).compile())
                    for f in fns]:
            fut.result()


def set_up(cell: Cell, seed: int):
    """Tables from the seed, placed, and every plan of the stream compiled
    (or loaded from the compile cache); returns the tables, the context and
    the entry the window calls.  The executables are compiled for the
    shapes the window uses; queries run here only in the warm-up streams
    that the traffic asks for (``warmup_streams``), where an entry's first
    calls cost more than its later ones."""
    import jax

    from repro.frontends.dataflow import Context
    from tpch import datagen, queries

    t0 = time.perf_counter()
    tables = datagen.generate(cell.config["scale_factor"], seed)
    t1 = time.perf_counter()
    ctx = Context(pad_to=cell.config["pad_to"])
    for name, columns in cell.config["tables"].items():
        ctx.register(name, {c: tables[name][c] for c in columns})
    options = cell.options()
    plans = {q: ctx.compile(queries.BUILDERS[q](ctx), **options)
             for q in dict.fromkeys(cell.traffic["stream"])}
    sources = jax.block_until_ready(ctx.sources(next(iter(plans.values()))))
    t2 = time.perf_counter()
    precompile(plans, sources)
    t3 = time.perf_counter()
    entry_cls = ENTRIES[cell.traffic["entry"]]
    entry = entry_cls(ctx, queries.BUILDERS, sources if entry_cls is Prepared else None,
                      options)
    for _ in range(cell.traffic.get("warmup_streams", 0)):
        run_window(entry, cell.traffic["stream"], 0.0)
    print(f"set-up: process start to tables {t0 - T_START!r} s, tables {t1 - t0!r} s, "
          f"placed {t2 - t1!r} s, compiled {t3 - t2!r} s, "
          f"warm-up {time.perf_counter() - t3!r} s", file=sys.stderr)
    return tables, ctx, entry


class Watch:
    """What the process does besides the queries while the window is open:
    JAX's compiles and compile-cache loads (none may happen there: a query
    during which one does counts as failed), and Python's collections."""

    COMPILE = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        import jax

        self.on, self.compiles, self.collections = False, 0, []
        self._gc_start = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, event: str, duration: float, **_: Any) -> None:
        if self.on and event in self.COMPILE:
            self.compiles += 1

    def _gc(self, stage: str, info: Dict[str, int]) -> None:
        if stage == "start":
            self._gc_start = time.perf_counter()
        elif self.on:
            self.collections.append((info["generation"], time.perf_counter() - self._gc_start))

    def close(self) -> None:
        self.on = False
        gc.callbacks.remove(self._gc)

    def report(self) -> str:
        return ", ".join(
            f"gen{g} {len(s)} collections (longest {max(s, default=0.0)!r} s)"
            for g in range(3)
            for s in [[t for gen, t in self.collections if gen == g]])


def run_window(entry, stream: List[str], seconds: float, watch: Optional[Watch] = None):
    """The closed loop: whole streams until ``seconds`` have passed.
    Returns the queries, their answers and the window's length."""
    records, answers = [], []
    t0 = time.perf_counter()
    while True:
        for name in stream:
            rec = Query(name)
            compiles = watch.compiles if watch else 0
            start = time.perf_counter()
            try:
                with phase(rec, "query"):
                    answers.append((name, entry.run(rec)))
                rec.latency_s = time.perf_counter() - start
            except Exception as e:  # the window goes on; the query is missing
                rec.error = f"{type(e).__name__}: {e}"
                traceback.print_exc(file=sys.stderr)
            rec.compiles = (watch.compiles if watch else 0) - compiles
            records.append(rec)
        if time.perf_counter() - t0 >= seconds:
            return records, answers, time.perf_counter() - t0


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices) -> Dict[str, Any]:
    """One run of ``cell``: the result line as a dict."""
    import jax

    import trace_reduce
    from tpch import queries, reference
    import checks

    peaks = device_peaks(devices[0].device_kind)
    watch = Watch()
    tables, ctx, entry = set_up(cell, seed)
    setup_s = time.perf_counter() - T_START
    watch.on = True
    trace_dir = CACHE / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no Python call events: the spans suffice
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        records, answers, window_s = run_window(entry, cell.traffic["stream"], seconds,
                                                watch)
    finally:
        watch.close()
        if trace:
            jax.profiler.stop_trace()
    peak = peak_bytes(devices)
    print(f"window: {window_s!r} s, {len(records)} queries, {watch.compiles} "
          f"compiles or cache loads; {watch.report()}", file=sys.stderr)
    stream = list(dict.fromkeys(cell.traffic["stream"]))
    for q in stream:  # in the window's order
        ms = [r.latency_s * 1e3 for r in records if r.name == q and r.error is None]
        print(f"latency_ms {q}: {ms!r}", file=sys.stderr)
    degraded = {q for q in stream
                if ctx.compile(queries.BUILDERS[q](ctx), **cell.options()).degraded}
    del entry, ctx
    gc.collect()

    wants = {q: reference.REFERENCES[q](tables, reference.REFERENCE) for q in stream}
    found = checks.judge(answers, wants, queries.GROUP_KEYS,
                         missing=sum(r.error is not None for r in records))
    run = Run(queries=records, window_s=window_s, setup_s=setup_s, peak_bytes=peak,
              peaks=peaks,
              least_bytes={q: queries.least_bytes(q, tables) for q in stream})
    if trace:
        run.trace = trace_reduce.load(trace_reduce.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for name, unit in (cell.per_layer if trace else cell.end_to_end).items():
        value = load_reader(name)(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {
        "correct": checks.passed(found) and bool(answers),
        "attempted": len(records),
        "failed": sum(r.error is not None or r.name in degraded or r.compiles > 0
                      for r in records),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        lo, hi = run.trace.window()
        device["busy_s"] = run.trace.busy(lo, hi)
        device["window_s"] = hi - lo
        result["breakdown"] = trace_reduce.breakdown(run.trace)
    result["checks"] = found
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    import_program()
    sys.path.insert(0, str(HERE))
    use_checkout_dirs()
    devices = require_devices(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
