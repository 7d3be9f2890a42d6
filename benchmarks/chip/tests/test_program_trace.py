"""The reading of a window by the program's own spans and operator scopes
(``program_trace.py``): the HLO-text parser on hand-written and
CPU-compiled plans, the names on known intervals, each reading on a
hand-built trace, the harness's own readers unchanged on the recorded v5e
trace, and the script end to end on the CPU at a tiny scale."""

import re
from pathlib import Path

import jax
import pytest

import bench
import program_trace as pt
import trace_reduce as tr

RECORDED = Path(__file__).parent / "data" / "tpu_tiny.xplane.pb"
V5E = {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9, "bf16_flops_per_s": 197e12}

HLO = """\
HloModule jit_run, entry_computation_layout={()->s32[8]{0}}

%fused_computation.3 (param_0.1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  ROOT %gather.2 = s32[8]{0} gather(%param_0.1), metadata={op_name="jit(run)/004.vec.MergeJoinSorted/jit(searchsorted)/gather" stack_frame_id=3}
}

%body.1 (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  ROOT %fusion.3 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3
}

%cond.1 (p.1: s32[8]) -> pred[] {
  %p.1 = s32[8]{0} parameter(0)
  ROOT %c = pred[] constant(true)
}

ENTRY %main.9 (a: s32[8]) -> s32[8] {
  %a = s32[8]{0} parameter(0)
  %unused = s32[] constant(1)
  %copy.1 = s32[8]{0} copy(%a)
  %while.2 = s32[8]{0} while(%copy.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(run)/004.vec.MergeJoinSorted/jit(searchsorted)/while"}
  ROOT %sort.5 = s32[8]{0} sort(%while.2), metadata={op_name="jit(run)/002.cf.Call/001.vec.SortByKey/sort"}
}
"""


def test_scope_of_takes_the_innermost_instruction():
    assert pt.scope_of("jit(run)/002.cf.Call/001.vec.SortByKey/sort") == "001.vec.SortByKey"
    assert pt.scope_of("jit(run)/003.vec.MaskSelect") == "003.vec.MaskSelect"
    assert pt.scope_of("jit(run)/jit(searchsorted)/while") is None


def test_scopes_of_reads_metadata_and_called_computations():
    scopes = pt.scopes_of(HLO)
    assert scopes["gather.2"] == scopes["while.2"] == "004.vec.MergeJoinSorted"
    # no metadata of its own: the scope its fused computation carries
    assert scopes["fusion.3"] == "004.vec.MergeJoinSorted"
    assert scopes["sort.5"] == "001.vec.SortByKey"
    # a copy XLA inserted: the scope of the loop it feeds (its operand has none)
    assert scopes["copy.1"] == "004.vec.MergeJoinSorted"
    # parameters, and an op with nothing around it to read, are left out
    assert not {"a", "p", "p.1", "param_0.1", "unused"} & set(scopes)


@pytest.fixture(scope="module")
def compiled_q12():
    from repro.compiler import PlanCache
    from repro.frontends.dataflow import Context
    from tpch import datagen, queries

    config = bench.load_cell("tpch_sf1.power").config
    tables = datagen.generate(0.002, 5)
    ctx = Context(pad_to=config["pad_to"])
    for name, columns in config["tables"].items():
        ctx.register(name, {c: tables[name][c] for c in columns})
    plan = ctx.compile(queries.BUILDERS["q12"](ctx), cache=PlanCache())
    text = plan.executable.fn.lower(dict(ctx.sources(plan))).compile().as_text()
    return plan, text


def test_scopes_of_a_compiled_plan(compiled_q12):
    plan, text = compiled_q12
    scopes = pt.scopes_of(text)
    body = {f"{i:03d}.{ins.opcode}" for i, ins in enumerate(plan.program.body)}
    assert set(scopes.values()) <= body
    assert any(pt.operator(s) in pt.JOIN_OPERATORS for s in scopes.values())
    # every op of the entry computation but its parameters is named
    entry = text[text.index("\nENTRY "):]
    ops = re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = .*$", entry, re.M)
    assert ops and all(n in scopes for n in ops
                       if not re.search(rf"%{re.escape(n)} = \S+ parameter\(", entry))


def test_program_index_finds_the_innermost_open_span():
    index = pt.ProgramIndex([("sources", 1.0, 5.0), ("sources.pad", 1.0, 3.0),
                             ("sources.place", 3.5, 5.0), ("fetch", 7.0, 9.0),
                             ("fetch.wait", 7.0, 8.0)])
    assert index.at(2.0) == "sources.pad"
    assert index.at(3.2) == "sources"
    assert index.at(4.0) == "sources.place"
    assert index.at(6.0) is None
    assert index.at(8.5) == "fetch"
    assert index.at(0.5) is None


def known():
    """Two queries: q12 over [0, 10] s, q6 over [10, 20] s; the device is
    busy over [2, 8] s in q12 and 1.5 s in q6."""
    spans = [tr.Span("query", "q12", 0.0, 10.0), tr.Span("call", "q12", 0.0, 6.0),
             tr.Span("fetch", "q12", 6.0, 10.0),
             tr.Span("query", "q6", 10.0, 20.0), tr.Span("call", "q6", 10.0, 20.0)]
    ops = {"/device:TPU:0": [("fusion.37", 2.0, 5.0), ("while.1", 5.0, 7.0),
                             ("fusion.2", 5.5, 6.5), ("fusion.9", 7.0, 8.0),
                             ("fusion.37", 10.0, 10.5), ("fusion.37", 16.0, 17.0)]}
    program = [("sources", 0.5, 1.8), ("sources.pad", 0.5, 1.5),
               ("fetch", 7.0, 10.0), ("fetch.wait", 7.0, 8.0), ("fetch.copy", 8.0, 9.5),
               ("sources", 10.2, 15.0), ("sources.place", 12.0, 15.0)]
    scopes = {"q12": {"fusion.37": "007.vec.FusedJoinGroupAgg",
                      "while.1": "004.vec.MergeJoinSorted",
                      "fusion.2": "004.vec.MergeJoinSorted",
                      "fusion.9": "009.vec.SortByKey"},
              "q6": {"fusion.37": "001.vec.FusedSelectAgg"}}
    return tr.Trace(spans, ops), program, scopes


def test_breakdown_names_ops_by_operator_and_gaps_by_program_span():
    trace, program, scopes = known()
    b = pt.breakdown(trace, program, scopes)
    assert b["device_ops"] == [
        ["q12/call/007.vec.FusedJoinGroupAgg/fusion.37", 3.0],
        ["q6/call/001.vec.FusedSelectAgg/fusion.37", 1.5],
        ["q12/call/004.vec.MergeJoinSorted/fusion.2", 1.0],
        ["q12/fetch/009.vec.SortByKey/fusion.9", 1.0]]  # the loop holding fusion.2 is left out
    assert b["idle_gaps"] == [["q6/call/sources.place", 5.5], ["q6/call", 3.0],
                              ["q12/call/sources.pad", 2.0], ["q12/fetch/fetch.copy", 2.0]]


def test_breakdown_without_program_names_is_the_harness_breakdown():
    trace, _, _ = known()
    assert pt.breakdown(trace, [], {}) == tr.breakdown(trace)


def test_place_and_transfer_ms():
    trace, program, _ = known()
    assert pt.place_ms(trace, program) == pytest.approx(1e3 * (1.3 + 4.8) / 2)
    # only q12's answer passed through the program's fetch
    assert pt.transfer_ms(trace, program) == pytest.approx(1.5e3)
    bare = [s for s in program if not s[0].startswith("sources")]
    assert pt.place_ms(trace, bare) is None
    assert pt.transfer_ms(trace, []) is None


def test_plan_cache_hit_pct():
    assert pt.plan_cache_hit_pct({"plan_cache.hit": 3.0, "plan_cache.miss": 1.0}) == 75.0
    assert pt.plan_cache_hit_pct({"plan_cache.hit": 12.0}) == 100.0
    assert pt.plan_cache_hit_pct({"execute": 1.0}) is None


def test_join_busy_pct_and_operator_seconds():
    trace, _, scopes = known()
    seconds, busy = pt.operator_seconds(trace, scopes)
    assert busy == pytest.approx(7.5)
    # the loop and the op it holds count once
    assert seconds == pytest.approx({"vec.FusedJoinGroupAgg": 3.0,
                                     "vec.MergeJoinSorted": 2.0,
                                     "vec.SortByKey": 1.0, "vec.FusedSelectAgg": 1.5})
    assert pt.join_busy_pct(trace, scopes) == pytest.approx(100.0 * 5.0 / 7.5)
    assert pt.join_busy_pct(tr.Trace(trace.spans, {}), scopes) is None


def test_scoped_busy_pct_counts_ops_with_a_scope_once():
    trace, _, scopes = known()
    assert pt.scoped_busy_pct(trace, scopes) == pytest.approx(100.0)
    del scopes["q12"]["fusion.9"]
    assert pt.scoped_busy_pct(trace, scopes) == pytest.approx(100.0 * 6.5 / 7.5)
    # an op held by a scoped loop is covered by the loop
    del scopes["q12"]["fusion.2"]
    assert pt.scoped_busy_pct(trace, scopes) == pytest.approx(100.0 * 6.5 / 7.5)
    assert pt.operator_seconds(trace, scopes)[0][None] == pytest.approx(2.0)


@pytest.fixture(scope="module")
def recorded():
    return tr.load(str(RECORDED))


def test_the_harness_readers_read_the_recorded_trace_as_before(recorded):
    """The values ``host_ms`` and ``device_idle_pct`` read on the recorded v5e
    trace, pinned, and the harness's breakdown kept by the named one."""
    run = bench.Run(queries=[], window_s=1.0, setup_s=0.0, peak_bytes=0, peaks=V5E,
                    least_bytes={}, trace=recorded)
    assert bench.load_reader("host_ms")(run) == pytest.approx(2.661468712121213)
    assert bench.load_reader("device_idle_pct")(run) == pytest.approx(77.33178346202592)
    assert pt.breakdown(recorded, [], {}) == tr.breakdown(recorded)


@pytest.fixture
def on_cpu(monkeypatch):
    """A tiny scale, and the TPU check steered to the CPU."""
    load = bench.load_cell

    def tiny(name):
        cell = load(name)
        cell.config["scale_factor"] = 0.002
        return cell

    monkeypatch.setattr(bench, "load_cell", tiny)
    monkeypatch.setattr(bench, "require_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(bench, "device_peaks", lambda kind: V5E)
    monkeypatch.setattr(bench, "use_checkout_dirs", lambda: None)
    monkeypatch.setattr(bench, "CACHE", Path(__import__("tempfile").mkdtemp()))


@pytest.mark.parametrize("cell", ["tpch_sf1.power", "tpch_sf1.collect"])
def test_the_script_runs_on_the_cpu(on_cpu, cell, tmp_path, capsys):
    out = tmp_path / "line.json"
    assert pt.main(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "0.2",
                    "--out", str(out)]) == 0
    line = __import__("json").loads(out.read_text())
    on = line["tracer_on"]
    assert on["plan_cache_hit_pct"] == 100.0
    assert on["transfer_ms"] > 0
    assert (on["place_ms"] is not None) == (cell == "tpch_sf1.collect")
    assert line["tracer_off"]["queries"] % 6 == 0 and on["queries"] % 6 == 0
    # no device plane on the CPU: nothing for the device readings
    assert on["join_busy_pct"] is None and on["host_ms"] is None
    first = line["first_call_ms"]
    assert set(first) == {"q14", "q6", "q4", "q1", "q19", "q12"}
    assert all({"execute", "fetch.wait"} <= set(spans) or q in ("q6", "q14", "q19")
               for q, spans in first.items())
