"""Read one cell's window by the program's own spans and operator scopes.

    python benchmarks/chip/program_trace.py --workload <cell> --seed <n>
        --seconds <s> [--out <path>]

The program mirrors each span of its tracer (``repro.obs.trace``) onto the
profiler's host timeline as ``cvm/<span>``, and traces every vec instruction
under a named scope ``<index>.<opcode>``, which the compiled module keeps in
each HLO op's ``op_name``.  This script runs the cell's set-up as
``bench.py`` does, then, in one process:

1. one stream under ``tracing(cardinalities=False)``: what each plan's first
   call spends, span by span, against its later calls;
2. a window under the profiler with the tracer off, as ``bench.py --trace 1``
   runs it: ``host_ms``, ``device_idle_pct``, ``query_roofline``;
3. the same window with the tracer on: the same readings (the difference is
   the tracer's cost), and what its spans and the plans' scopes let the
   trace say: ``place_ms``, ``transfer_ms``, ``plan_cache_hit_pct``,
   ``join_busy_pct``, device time per operator, the share of busy time
   whose ops carry a scope, and the breakdown with each device op named by
   its operator and each idle gap by the program span open in it.

The functions that read the trace are the ones the harness would call as
metric readers.  Prints one JSON line, and writes it to ``--out``.  Needs
the chip, as ``bench.py`` does.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import shutil
import statistics
import sys
from collections import Counter, defaultdict
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench  # noqa: E402
import trace_reduce as tr  # noqa: E402

#: the profiler annotation prefix of a program span (``repro.obs.trace``)
PROGRAM_PREFIX = "cvm/"
#: a vec instruction's scope in an op's ``op_name``: ``007.vec.SortByKey``
SCOPE = re.compile(r"^\d{3}\.[A-Za-z_]+\.\w+$")
JOIN_OPERATORS = ("vec.MergeJoinSorted", "vec.HashJoinDirect",
                  "vec.FusedJoinGroupAgg")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_REFERENCE = re.compile(r"%([\w.\-]+)")


# ---------------------------------------------------------------------------
# a compiled plan's ops -> their vec instruction
# ---------------------------------------------------------------------------


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``<index>.<opcode>`` scope of an op's ``op_name``."""
    found = [p for p in op_name.split("/") if SCOPE.match(p)]
    return found[-1] if found else None


def scopes_of(hlo_text: str) -> Dict[str, str]:
    """``{HLO instruction name: innermost <index>.<opcode> scope}`` of a
    compiled module's text (``Compiled.as_text()``).  An instruction whose
    own metadata names no scope (a fusion made by a pass) takes the scope
    most instructions of the computations it calls carry.  One with neither
    (a copy or a broadcast XLA inserted, a reduction JAX lowered outside the
    scope) takes the scope most of its operands carry, else its users; a
    parameter or an op with nothing around it to read is left out."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    params = set()
    members: Dict[str, List[str]] = defaultdict(list)
    computation = ""
    for line in hlo_text.splitlines():
        ins = _INSTRUCTION.match(line)
        if ins is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                computation = head.group(1)
            continue
        name = ins.group(1)
        members[computation].append(name)
        m = _OP_NAME.search(line)
        own[name] = scope_of(m.group(1)) if m else None
        called = _CALLS.findall(line)
        for group in _CALL_LISTS.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
        calls[name] = called
        if " parameter(" in line:
            params.add(name)
        operands[name] = _REFERENCE.findall(line, ins.end())

    memo: Dict[str, Optional[str]] = {}

    def of_computation(comp: str, seen: frozenset) -> Optional[str]:
        if comp in memo:
            return memo[comp]
        if comp in seen:
            return None
        votes = Counter(s for n in members.get(comp, ())
                        if (s := of_instruction(n, seen | {comp})) is not None)
        memo[comp] = votes.most_common(1)[0][0] if votes else None
        return memo[comp]

    def of_instruction(name: str, seen: frozenset) -> Optional[str]:
        if own.get(name):
            return own[name]
        votes = Counter(s for c in calls.get(name, ())
                        if (s := of_computation(c, seen)) is not None)
        return votes.most_common(1)[0][0] if votes else None

    out = {}
    for name in own:
        scope = of_instruction(name, frozenset())
        if scope is not None:
            out[name] = scope
    users: Dict[str, List[str]] = defaultdict(list)
    for name, refs in operands.items():
        operands[name] = [a for a in refs if a in own and a != name]
        for a in operands[name]:
            users[a].append(name)
    changed = True
    while changed:
        changed = False
        for name, args in operands.items():
            if name in out or name in params:
                continue
            votes = (Counter(out[a] for a in args if a in out)
                     or Counter(out[u] for u in users[name] if u in out))
            if votes:
                out[name] = votes.most_common(1)[0][0]
                changed = True
    return out


def operator(scope: str) -> str:
    """``007.vec.SortByKey`` -> ``vec.SortByKey``."""
    return scope.split(".", 1)[1]


# ---------------------------------------------------------------------------
# program spans on the profiler's host timeline
# ---------------------------------------------------------------------------


def program_spans(path: str) -> List[Tuple[str, float, float]]:
    """The ``cvm/<span>`` host events of a trace: ``(span, start, end)`` in
    seconds on the trace's clock, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        out.append((ev.name[len(PROGRAM_PREFIX):], ev.start_ns * 1e-9,
                                    ev.end_ns * 1e-9))
    return sorted(out, key=lambda s: (s[1], -s[2]))


class ProgramIndex:
    """Finds the innermost program span open at a time.  Program spans of
    one thread nest, so each span's parent is the last one still open when
    it starts."""

    def __init__(self, spans: Sequence[Tuple[str, float, float]]) -> None:
        self.spans = sorted(spans, key=lambda s: (s[1], -s[2]))
        self.starts = [s[1] for s in self.spans]
        self.parent: List[int] = []
        open_: List[int] = []
        for i, (_, start, _end) in enumerate(self.spans):
            while open_ and self.spans[open_[-1]][2] < start:
                open_.pop()
            self.parent.append(open_[-1] if open_ else -1)
            open_.append(i)

    def at(self, t: float) -> Optional[str]:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i][2] < t:
            i = self.parent[i]
        return self.spans[i][0] if i >= 0 else None


def _inside(spans, q: tr.Span, name: str) -> List[float]:
    return [e - s for n, s, e in spans if n == name and q.start <= s and e <= q.end]


# ---------------------------------------------------------------------------
# the readings
# ---------------------------------------------------------------------------


def place_ms(trace: tr.Trace, spans) -> Optional[float]:
    """Mean milliseconds per query of the ``sources`` spans inside it: the
    host padding and placing the tables on a call that places them."""
    per = [sum(_inside(spans, q, "sources")) for q in trace.queries()]
    if not any(n == "sources" for n, _, _ in spans) or not per:
        return None
    return 1e3 * sum(per) / len(per)


def transfer_ms(trace: tr.Trace, spans) -> Optional[float]:
    """Mean milliseconds of the ``fetch.copy`` spans, over the queries whose
    answer passed through the program's fetch: the device→host copies and
    the compaction, after the device has finished."""
    per = [sum(c) for q in trace.queries() if (c := _inside(spans, q, "fetch.copy"))]
    return 1e3 * sum(per) / len(per) if per else None


def plan_cache_hit_pct(counters: Dict[str, float]) -> Optional[float]:
    """Plan-cache hits over lookups in the window, in percent."""
    hit, miss = counters.get("plan_cache.hit", 0.0), counters.get("plan_cache.miss", 0.0)
    return 100.0 * hit / (hit + miss) if hit + miss else None


def _query_ops(trace: tr.Trace, scopes: Dict[str, Dict[str, str]]):
    """Each device op that starts inside a query: ``(device, query index,
    scope or None, interval clipped to the query)``."""
    queries = trace.queries()
    starts = [q.start for q in queries]
    for device, evs in trace.ops.items():
        for name, s, e in evs:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s <= queries[i].end:
                q = queries[i]
                yield device, i, scopes.get(q.query, {}).get(name), (s, min(e, q.end))


def _busy_in_queries(trace: tr.Trace) -> float:
    return sum(trace.busy(q.start, q.end) for q in trace.queries())


def operator_seconds(trace: tr.Trace, scopes: Dict[str, Dict[str, str]]
                     ) -> Tuple[Dict[str, float], float]:
    """Device seconds per operator over the window's queries (the union of
    each query's op intervals of that operator, averaged over the devices;
    ops with no scope under ``None``), and the device-busy seconds inside
    the queries."""
    by: Dict[tuple, List[tr.Interval]] = defaultdict(list)
    for device, i, scope, iv in _query_ops(trace, scopes):
        by[device, i, scope and operator(scope)].append(iv)
    seconds: Dict[str, float] = defaultdict(float)
    for (_, _, op), ivs in by.items():
        seconds[op] += sum(b - a for a, b in tr.union(ivs)) / len(trace.ops)
    return dict(seconds), _busy_in_queries(trace)


def join_busy_pct(trace: tr.Trace, scopes: Dict[str, Dict[str, str]]) -> Optional[float]:
    """The join operators' share of the device-busy time in the queries."""
    if not trace.ops:
        return None
    seconds, busy = operator_seconds(trace, scopes)
    joins = sum(v for k, v in seconds.items() if k in JOIN_OPERATORS)
    return 100.0 * joins / busy if busy > 0 else None


def scoped_busy_pct(trace: tr.Trace, scopes: Dict[str, Dict[str, str]]) -> Optional[float]:
    """The share of the device-busy time in the queries whose ops carry an
    operator scope (an op and the loop holding it count once)."""
    busy = _busy_in_queries(trace)
    if busy <= 0:
        return None
    by: Dict[tuple, List[tr.Interval]] = defaultdict(list)
    for device, i, scope, iv in _query_ops(trace, scopes):
        if scope is not None:
            by[device, i].append(iv)
    seconds = sum(b - a for ivs in by.values() for a, b in tr.union(ivs))
    return 100.0 * seconds / len(trace.ops) / busy


def breakdown(trace: tr.Trace, spans, scopes: Dict[str, Dict[str, str]]
              ) -> Dict[str, list]:
    """``trace_reduce.breakdown`` with more names: a device op gets its
    operator inserted (``q12/fetch/007.vec.FusedJoinGroupAgg/fusion.37``),
    an idle gap the program span open in it (``q12/call/sources.pad``)."""
    lo, hi = trace.window()
    index, program = tr.SpanIndex(trace.spans), ProgramIndex(spans)
    by_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for evs in trace.ops.values():
        for name, s, e in evs:
            if lo <= s <= hi and name.split(".")[0] not in tr.CONTAINERS:
                where = index.at(s)
                scope = scopes.get(where.split("/")[0], {}).get(name)
                by_op[f"{where}/{scope}/{name}" if scope else f"{where}/{name}"] += e - s
        merged = tr.union([(s, e) for _, s, e in evs if e >= lo and s <= hi])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                span = program.at(mid)
                gaps.append((f"{index.at(mid)}/{span}" if span else index.at(mid), b - a))
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:tr.TOP]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in sorted(gaps, key=lambda g: -g[1])[:tr.TOP]]}


# ---------------------------------------------------------------------------
# the run on the chip
# ---------------------------------------------------------------------------


class Recorded:
    """An entry whose calls keep the tracer spans each query recorded."""

    def __init__(self, entry) -> None:
        self.entry, self.calls = entry, []

    def run(self, rec):
        from repro.obs.trace import get_tracer

        spans = get_tracer().spans
        n = len(spans)
        try:
            return self.entry.run(rec)
        finally:
            self.calls.append((rec.name, spans[n:]))


def span_ms(calls) -> Dict[str, Dict[str, List[float]]]:
    """``{query: {span: [ms of each call]}}``, spans of one call summed."""
    out: Dict[str, Dict[str, List[float]]] = defaultdict(lambda: defaultdict(list))
    for q, spans in calls:
        per: Dict[str, float] = defaultdict(float)
        for s in spans:
            per[s.name.split(":")[0]] += s.dur_s * 1e3
        for name, ms in per.items():
            out[q][name].append(ms)
    return out


def first_calls(first, later) -> Dict[str, Dict[str, List[float]]]:
    """Per query and span: the first stream's ms and the later calls' median."""
    a, b = span_ms(first), span_ms(later)
    return {q: {name: [ms[0], statistics.median(b[q][name]) if b[q][name] else None]
                for name, ms in spans.items()} for q, spans in a.items()}


def profiled_window(entry, stream, seconds, trace_dir: Path):
    """One window under the profiler, as ``bench.py --trace 1`` runs it;
    returns its queries, its length and the trace file."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        records, _, window_s = bench.run_window(entry, stream, seconds)
    finally:
        jax.profiler.stop_trace()
    return records, window_s, tr.find_xplane(str(trace_dir))


def plan_scopes(ctx, entry, cell) -> Dict[str, Dict[str, str]]:
    """Each query's plan compiled for the window's shapes (a compile-cache
    load), read for its ops' scopes."""
    from tpch import queries

    out = {}
    for q in dict.fromkeys(cell.traffic["stream"]):
        plan = ctx.compile(queries.BUILDERS[q](ctx), **cell.options())
        src = entry.sources if isinstance(entry, bench.Prepared) else ctx.sources(plan)
        out[q] = scopes_of(plan.executable.fn.lower(dict(src)).compile().as_text())
    return out


def keep_scopes() -> None:
    """Key the compile cache by the ops' metadata too.  By default JAX
    leaves it out, so a plan cached by a build without the scopes (or with
    other instruction numbers) is loaded with that build's ``op_name``s."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def readings(run: bench.Run) -> Dict[str, Any]:
    return {m: bench.load_reader(m)(run)
            for m in ("host_ms", "device_idle_pct", "query_roofline", "compile_call_ms")}


def run_cell(cell, seed: int, seconds: float, devices) -> Dict[str, Any]:
    from repro.obs.trace import tracing
    from tpch import queries

    peaks = bench.device_peaks(devices[0].device_kind)
    tables, ctx, entry = bench.set_up(cell, seed)
    least = {q: queries.least_bytes(q, tables) for q in dict.fromkeys(cell.traffic["stream"])}
    del tables
    scopes = plan_scopes(ctx, entry, cell)
    stream = cell.traffic["stream"]
    recorded = Recorded(entry)

    with tracing(cardinalities=False):
        bench.run_window(recorded, stream, 0.0)
    first = recorded.calls
    off = profiled_window(entry, stream, seconds, bench.CACHE / "trace_off")
    recorded.calls = []
    with tracing(cardinalities=False) as tracer:
        on = profiled_window(recorded, stream, seconds, bench.CACHE / "trace_on")
    result: Dict[str, Any] = {"workload": cell.name, "seed": seed,
                              "device": devices[0].device_kind}
    for side, (records, window_s, path) in (("tracer_off", off), ("tracer_on", on)):
        trace = tr.load(path)
        run = bench.Run(queries=records, window_s=window_s, setup_s=0.0, peak_bytes=0,
                        peaks=peaks, least_bytes=least, trace=trace)
        result[side] = {"queries": len(records), "window_s": window_s, **readings(run)}
        if side == "tracer_on":
            spans = program_spans(path)
            seconds_by_op, busy = operator_seconds(trace, scopes)
            result[side].update(
                place_ms=place_ms(trace, spans), transfer_ms=transfer_ms(trace, spans),
                plan_cache_hit_pct=plan_cache_hit_pct(tracer.counters),
                join_busy_pct=join_busy_pct(trace, scopes),
                scoped_busy_pct=scoped_busy_pct(trace, scopes),
                busy_s=busy,
                operator_s=dict(sorted(((str(k), v) for k, v in seconds_by_op.items()),
                                       key=lambda kv: -kv[1])),
                breakdown=breakdown(trace, spans, scopes))
        shutil.rmtree(Path(path).parents[3], ignore_errors=True)
    result["first_call_ms"] = first_calls(first, recorded.calls)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cell = bench.load_cell(args.workload)
    bench.import_program()
    bench.use_checkout_dirs()
    keep_scopes()
    devices = bench.require_devices(cell.chips)
    line = json.dumps(run_cell(cell, args.seed, args.seconds, devices))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
