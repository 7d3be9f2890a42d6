"""Observability: spans, exporters, runtime cardinality taps, feedback.

Covers the tracer core (nesting, disabled-mode fast path), the Chrome-trace
exporter's schema, the measured-cardinality capture on TPC-H Q1 across the
interp and local backends (against reference row counts computed in numpy),
the estimate-vs-actual report in ``explain()``, the plan-cache/plan-store
counters, corrupt-store warnings, and the feedback catalog that closes the
loop back into the statistics and cost calibration.
"""

import json
import warnings

import numpy as np
import pytest

from repro.compiler import PlanCache, compile as cvm_compile
from repro.compiler.cost import EXEC_CALIBRATION, CostCalibration
from repro.compiler.store import PlanStore
from repro.obs import (
    FEEDBACK,
    FeedbackCatalog,
    NULL_SPAN,
    ObsWarning,
    Tracer,
    chrome_trace,
    get_tracer,
    tracing,
    write_chrome_trace,
)
from repro.relational import tpch


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_parents(self):
        tr = Tracer()
        with tr.span("outer", cat="a") as outer:
            with tr.span("inner", cat="b") as inner:
                pass
        assert inner.parent_id == outer.span_id
        assert outer.parent_id is None
        # children record before parents (exit order)
        assert [s.name for s in tr.spans] == ["inner", "outer"]
        assert all(s.dur_s >= 0.0 for s in tr.spans)

    def test_span_attributes_set_late(self):
        tr = Tracer()
        with tr.span("work", rows=10) as sp:
            sp.set(result="ok")
        assert tr.spans[0].args == {"rows": 10, "result": "ok"}

    def test_disabled_mode_returns_shared_null_span(self):
        tr = Tracer(enabled=False)
        # zero-allocation fast path: every disabled span() is the same object
        assert tr.span("a") is tr.span("b") is NULL_SPAN
        with tr.span("a") as sp:
            sp.set(ignored=1)
        assert tr.spans == [] and tr.counters == {}
        tr.counter("n")
        tr.observe("h", 1.0)
        tr.event("e")
        assert tr.counters == {} and tr.histograms == {} and tr.events == []

    def test_global_tracer_disabled_by_default(self):
        assert get_tracer().enabled is False
        assert get_tracer().span("x") is NULL_SPAN

    def test_tracing_context_installs_and_restores(self):
        before = get_tracer()
        with tracing() as tr:
            assert get_tracer() is tr and tr.enabled
        assert get_tracer() is before

    def test_counters_and_histograms(self):
        tr = Tracer()
        tr.counter("hits")
        tr.counter("hits", 2.0)
        for v in (1.0, 2.0, 3.0, 4.0):
            tr.observe("lat", v)
        assert tr.counters["hits"] == 3.0
        h = tr.histogram_summary("lat")
        assert h["count"] == 4 and h["sum"] == 10.0 and h["min"] == 1.0
        assert h["max"] == 4.0 and h["p50"] == 3.0
        m = tr.metrics()
        assert m["counters"]["hits"] == 3.0
        assert m["histograms"]["lat"]["mean"] == 2.5

    def test_max_events_bounds_spans(self):
        tr = Tracer(max_events=2)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.spans) == 2 and tr.dropped == 3
        assert tr.metrics()["dropped"] == 3


# ---------------------------------------------------------------------------
# chrome trace export
# ---------------------------------------------------------------------------


class TestChromeTrace:
    def test_schema_and_roundtrip(self, tmp_path):
        tr = Tracer()
        with tr.span("outer", cat="compile"):
            with tr.span("inner", cat="compile.pass", stage="fuse"):
                pass
        tr.counter("plan_cache.hit", 3)
        tr.event("plan_store.corrupt", path="/x.json")
        path = write_chrome_trace(tmp_path / "t.json", tr)
        doc = json.loads(path.read_text())

        assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
        events = doc["traceEvents"]
        phases = {e["ph"] for e in events}
        assert phases == {"M", "X", "i", "C"}
        for e in events:
            assert {"name", "ph", "pid", "tid"} <= set(e)
        complete = [e for e in events if e["ph"] == "X"]
        assert [e["name"] for e in complete] == ["inner", "outer"]
        for e in complete:
            assert e["ts"] >= 0.0 and e["dur"] >= 0.0  # microseconds
        inner = complete[0]
        outer = complete[1]
        assert inner["args"]["parent"] == outer["id"]
        assert inner["args"]["stage"] == "fuse"
        counters = [e for e in events if e["ph"] == "C"]
        assert counters[0]["args"]["value"] == 3
        assert doc["metadata"]["metrics"]["counters"]["plan_cache.hit"] == 3

    def test_nesting_by_interval_containment(self):
        tr = Tracer()
        with tr.span("parent"):
            with tr.span("child"):
                pass
        doc = chrome_trace(tr)
        by_name = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        p, c = by_name["parent"], by_name["child"]
        assert p["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-3


# ---------------------------------------------------------------------------
# traced execution: measured cardinalities on TPC-H Q1
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def q1_setup():
    tables = tpch.generate(sf=0.002, seed=7)
    ctx = tpch.make_context(tables, pad_to=256)
    frame = tpch.QUERIES["q1"](ctx)
    # reference row counts straight from the data
    li = tables["lineitem"]
    rf = np.asarray(li["l_returnflag"])
    ls = np.asarray(li["l_linestatus"])
    n_groups = len(np.unique(np.rec.fromarrays([rf, ls], names=["a", "b"])))
    return tables, ctx, frame, len(rf), n_groups


class TestMeasuredCardinalities:
    def _run(self, ctx, frame, target, sources):
        with tracing():
            res = ctx.compile(frame, target=target, cache=PlanCache())
            res(sources)
        return res

    def test_q1_local_cardinalities(self, q1_setup):
        tables, ctx, frame, n_rows, n_groups = q1_setup
        res = self._run(ctx, frame, "local", ctx.sources())
        prof = res.profile
        assert prof is not None and prof.target == "local"
        by_op = {o.opcode: o for o in prof.observations}
        assert by_op["vec.ScanVec"].rows_out == n_rows
        assert by_op["vec.ScanVec"].table == "lineitem"
        # the grouped aggregation's output cardinality is the group count
        agg = next(o for o in prof.observations
                   if o.opcode in ("vec.GroupAggSorted", "vec.GroupAggDirect",
                                   "vec.FusedSelectAgg"))
        assert agg.rows_out == n_groups
        # every observation joined an estimate and computed its miss
        assert all(o.est_rows is not None for o in prof.observations)
        assert all(o.rel_miss is not None for o in prof.observations)

    def test_q1_interp_cardinalities_and_walls(self, q1_setup):
        tables, ctx, frame, n_rows, n_groups = q1_setup
        res = self._run(ctx, frame, "interp", tables)
        prof = res.profile
        by_op = {o.opcode: o for o in prof.observations}
        assert by_op["rel.Scan"].rows_out == n_rows
        assert by_op["rel.GroupByAggr"].rows_out == n_groups
        # the eager interpreter times individual operators
        assert all(o.wall_s is not None and o.wall_s >= 0.0
                   for o in prof.observations)

    def test_q1_interp_local_agree(self, q1_setup):
        """Both backends must measure the same selection cardinality."""
        tables, ctx, frame, n_rows, n_groups = q1_setup
        local = self._run(ctx, frame, "local", ctx.sources()).profile
        interp = self._run(ctx, frame, "interp", tables).profile
        sel_local = next(o.rows_out for o in local.observations
                         if o.opcode in ("vec.MaskSelect", "vec.FusedSelectAgg"))
        sel_interp = next(o.rows_out for o in interp.observations
                          if o.opcode == "rel.Select")
        assert sel_local == sel_interp

    def test_q1_trace_has_nested_compile_and_execute_spans(self, q1_setup):
        tables, ctx, frame, _, _ = q1_setup
        with tracing() as tr:
            res = ctx.compile(frame, target="local", cache=PlanCache())
            res(ctx.sources())
        doc = chrome_trace(tr)
        by_cat = {}
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                by_cat.setdefault(e.get("cat"), []).append(e)
        # a top-level compile span with nested per-pass spans
        assert len(by_cat["compile"]) == 1
        compile_id = by_cat["compile"][0]["id"]
        assert by_cat["compile.pass"]
        assert all(e["args"].get("parent") for e in by_cat["compile.pass"])
        assert any(e["args"]["parent"] == compile_id
                   for e in by_cat["compile.pass"])
        # an execute span plus per-operator cardinality annotations
        assert by_cat["execute"]
        ops = by_cat["execute.op"]
        assert ops and all("rows_out" in e["args"] for e in ops)

    def test_untraced_call_attaches_no_profile(self, q1_setup):
        tables, ctx, frame, _, _ = q1_setup
        res = ctx.compile(frame, target="local", cache=PlanCache())
        res(ctx.sources())
        assert res.profile is None


# ---------------------------------------------------------------------------
# explain(): cache provenance + estimate-vs-actual report
# ---------------------------------------------------------------------------


class TestExplain:
    def test_cache_hit_source_memory(self, q1_setup):
        tables, ctx, frame, _, _ = q1_setup
        cache = PlanCache()
        first = ctx.compile(frame, target="local", cache=cache)
        again = ctx.compile(frame, target="local", cache=cache)
        assert "cache=miss" in first.explain()
        assert again.cache_hit and again.cache_source == "memory"
        assert "cache=hit source=memory" in again.explain()
        assert cache.stats["hits"] == 1 and cache.stats["misses"] == 1

    def test_plan_cache_counters_reach_tracer(self, q1_setup):
        tables, ctx, frame, _, _ = q1_setup
        with tracing() as tr:
            cache = PlanCache()
            ctx.compile(frame, target="local", cache=cache)
            ctx.compile(frame, target="local", cache=cache)
        assert tr.counters["plan_cache.miss"] == 1
        assert tr.counters["plan_cache.hit"] == 1

    def test_plan_cache_eviction_counted(self):
        cache = PlanCache(capacity=1)
        cache.store(("a",), "r1")
        cache.store(("b",), "r2")
        assert cache.stats["evictions"] == 1 and len(cache) == 1

    def test_estimate_vs_actual_table_in_explain(self, q1_setup):
        tables, ctx, frame, n_rows, _ = q1_setup
        with tracing():
            res = ctx.compile(frame, target="local", cache=PlanCache())
            res(ctx.sources())
        text = res.explain()
        assert "| op | register | est rows | actual rows | miss | wall ms |"\
            in text
        assert f"{n_rows:,}" in text  # the measured scan cardinality
        assert "worst cardinality miss" in text

    def test_metrics_dict_is_json_ready(self, q1_setup):
        tables, ctx, frame, _, _ = q1_setup
        with tracing():
            res = ctx.compile(frame, target="local", cache=PlanCache())
            res(ctx.sources())
            m = res.metrics()
        json.dumps(m)  # must not raise
        assert m["cache_source"] == "miss"
        assert m["runtime"]["operators"]
        assert m["tracer"]["counters"]


# ---------------------------------------------------------------------------
# plan store: hit/miss/corruption
# ---------------------------------------------------------------------------


class TestPlanStoreObs:
    def test_corrupt_plan_warns_with_path_and_reason(self, tmp_path):
        store = PlanStore(tmp_path)
        store.save_plan("abc", {"strategy": []})
        (tmp_path / "abc.json").write_text("{not json")
        with pytest.warns(ObsWarning, match="plan_store.corrupt") as rec:
            assert store.load_plan("abc") is None
        msg = str(rec[0].message)
        assert "abc.json" in msg and "reason=" in msg

    def test_corrupt_counter_and_event_when_tracing(self, tmp_path):
        store = PlanStore(tmp_path)
        (tmp_path / "bad.json").write_text("][")
        with tracing() as tr:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                store.load_plan("bad")
        assert tr.counters["plan_store.corrupt"] == 1
        events = [e for e in tr.events if e["name"] == "plan_store.corrupt"]
        assert events and "bad.json" in events[0]["path"]

    def test_missing_plan_is_a_miss_not_a_warning(self, tmp_path):
        store = PlanStore(tmp_path)
        with tracing() as tr:
            with warnings.catch_warnings():
                warnings.simplefilter("error", ObsWarning)
                assert store.load_plan("nope") is None
        assert tr.counters["plan_store.miss"] == 1

    def test_hit_counter(self, tmp_path):
        store = PlanStore(tmp_path)
        store.save_plan("k", {"strategy": [["groupby", "direct"]]})
        with tracing() as tr:
            assert store.load_plan("k")["strategy"]
        assert tr.counters["plan_store.hit"] == 1

    def test_corrupt_calibration_warns_and_defaults(self, tmp_path):
        store = PlanStore(tmp_path)
        (tmp_path / "calibration.json").write_text("~~~")
        with pytest.warns(ObsWarning, match="plan_store.corrupt"):
            calib = store.load_calibration()
        assert calib.n == 0


# ---------------------------------------------------------------------------
# feedback: measured rows → observed statistics + runtime calibration
# ---------------------------------------------------------------------------


class TestFeedback:
    def test_feedback_accumulates_scan_rows(self, q1_setup):
        tables, ctx, frame, n_rows, _ = q1_setup
        FEEDBACK.clear()
        with tracing():
            res = ctx.compile(frame, target="local", cache=PlanCache())
            res(ctx.sources())
        assert FEEDBACK.runs == 1
        assert FEEDBACK.table_rows["lineitem"] == n_rows
        assert res.fingerprint in FEEDBACK.profiles

    def test_observed_statistics_override_rows(self, q1_setup):
        tables, ctx, frame, n_rows, _ = q1_setup
        FEEDBACK.clear()
        with tracing():
            res = ctx.compile(frame, target="local", cache=PlanCache())
            res(ctx.sources())
        base = ctx.catalog().stats
        obs = FEEDBACK.observed_statistics(base)
        assert obs.table("lineitem").rows == n_rows
        # NDV knowledge survives the row override
        base_t, obs_t = base.table("lineitem"), obs.table("lineitem")
        assert dict(obs_t.ndv).keys() == dict(base_t.ndv).keys()

    def test_exec_calibration_updates(self, q1_setup):
        tables, ctx, frame, _, _ = q1_setup
        n_before = EXEC_CALIBRATION.n
        with tracing():
            res = ctx.compile(frame, target="local", cache=PlanCache())
            res(ctx.sources())
        assert EXEC_CALIBRATION.n == n_before + 1
        assert EXEC_CALIBRATION.seconds(res.profile.est_cost) is not None

    def test_plans_over_threshold(self):
        from repro.obs import OpObservation, RuntimeProfile

        cat = FeedbackCatalog()
        obs = OpObservation(key="k", opcode="vec.MaskSelect", program="p",
                            register="v1", occurrences=1, rows_in=100,
                            rows_out=90, est_rows=10.0)
        cat.record(RuntimeProfile(target="local", program_name="p",
                                  fingerprint="fp1", wall_s=0.1,
                                  observations=(obs,)))
        flagged = cat.plans_over_threshold(threshold=1.0)
        assert flagged == [("fp1", obs.rel_miss)]
        assert cat.plans_over_threshold(threshold=100.0) == []

    def test_replan_with_observed_stats_shifts_estimates(self, q1_setup):
        """The loop closes: a re-compile under observed statistics produces
        estimates that match the measured cardinalities better."""
        tables, ctx, frame, n_rows, _ = q1_setup
        FEEDBACK.clear()
        with tracing():
            res = ctx.compile(frame, target="local", cache=PlanCache())
            res(ctx.sources())
        scan = next(o for o in res.profile.observations
                    if o.opcode == "vec.ScanVec")
        miss_before = abs(scan.rel_miss)

        catalog = ctx.catalog()
        catalog.stats = FEEDBACK.observed_statistics(catalog.stats)
        with tracing():
            res2 = cvm_compile(frame.program(), target="local",
                               catalog=catalog, cache=PlanCache())
            res2(ctx.sources())
        scan2 = next(o for o in res2.profile.observations
                     if o.opcode == "vec.ScanVec")
        assert abs(scan2.rel_miss) <= miss_before
        assert scan2.rows_out == n_rows


# ---------------------------------------------------------------------------
# calibration dataclass sanity (EXEC_CALIBRATION is a separate instance)
# ---------------------------------------------------------------------------


def test_exec_calibration_is_not_compile_calibration():
    from repro.compiler.cost import CALIBRATION

    assert EXEC_CALIBRATION is not CALIBRATION
    assert isinstance(EXEC_CALIBRATION, CostCalibration)


# ---------------------------------------------------------------------------
# spans without the cardinality taps, the profiler mirror, operator scopes
# ---------------------------------------------------------------------------


class TestQueryPathSpans:
    def test_no_cardinalities_runs_the_plain_executable(self, q1_setup,
                                                        monkeypatch):
        from repro.backends.local import Compiled

        def refuse(*a, **k):
            raise AssertionError("run_traced called")

        monkeypatch.setattr(Compiled, "run_traced", refuse)
        tables, ctx, frame, n_rows, _ = q1_setup
        cache = PlanCache()
        ctx.compile(frame, target="local", cache=cache)
        with tracing(cardinalities=False) as tr:
            res = ctx.compile(frame, target="local", cache=cache)
            (out,) = res(ctx.sources(res))
        assert res.profile is None
        names = [s.name for s in tr.spans]
        assert "frontend.compile" in names
        assert any(n.startswith("execute:") for n in names)
        assert tr.counters["plan_cache.hit"] == 1
        # the compile span nests in the frontend's, with the cache outcome
        by_name = {s.name: s for s in tr.spans}
        comp = next(s for s in tr.spans if s.name.startswith("compile:"))
        assert comp.parent_id == by_name["frontend.compile"].span_id
        assert comp.args["cache"] == "hit"

    def test_sources_and_fetch_spans_nest_with_sizes(self, q1_setup):
        from repro.frontends.dataflow import _to_numpy

        tables, ctx, frame, _, n_groups = q1_setup
        res = ctx.compile(frame, target="local", cache=PlanCache())
        with tracing(cardinalities=False) as tr:
            got = _to_numpy(res(ctx.sources(res))[0])
        by_name = {s.name: s for s in tr.spans}
        src, fetch = by_name["sources"], by_name["fetch"]
        assert src.args["tables"] == len(ctx.tables) and src.args["bytes"] > 0
        for child in ("sources.pad", "sources.place"):
            assert by_name[child].parent_id == src.span_id
        for child in ("fetch.wait", "fetch.copy"):
            assert by_name[child].parent_id == fetch.span_id
        assert fetch.args["rows"] == len(next(iter(got.values())))
        assert fetch.args["bytes"] == sum(a.nbytes for a in got.values())

    def test_disabled_tracer_creates_no_span_on_the_query_path(
            self, q1_setup, monkeypatch):
        import repro.obs.trace as trace_mod
        from repro.frontends.dataflow import _to_numpy

        def refuse(*a, **k):
            raise AssertionError("Span created with the tracer off")

        tables, ctx, frame, _, _ = q1_setup
        assert not get_tracer().enabled
        monkeypatch.setattr(trace_mod, "Span", refuse)
        res = ctx.compile(frame, target="local", cache=PlanCache())
        _to_numpy(res(ctx.sources(res))[0])
        assert res.profile is None

    def test_importing_the_tracer_imports_no_jax(self):
        import os
        import subprocess
        import sys
        from pathlib import Path

        code = ("import sys; import repro.obs.trace as t; "
                "t.Tracer(enabled=False).span('x'); "
                "assert 'jax' not in sys.modules")
        src = Path(__file__).resolve().parents[1] / "src"
        subprocess.run([sys.executable, "-c", code], check=True,
                       env={**os.environ, "PYTHONPATH": str(src)})

    def test_spans_reach_the_profilers_host_plane(self, q1_setup, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        from repro.frontends.dataflow import _to_numpy

        tables, ctx, frame, _, _ = q1_setup
        res = ctx.compile(frame, target="local", cache=PlanCache())
        _to_numpy(res(ctx.sources(res))[0])  # compiled before the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracing(cardinalities=False):
                _to_numpy(res(ctx.sources(res))[0])
                with get_tracer().span("annotated"):
                    get_tracer().record_complete("not.mirrored", "x", 0.0, 0.0)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        names = {ev.name for plane in ProfileData.from_file(path).planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for ev in line.events}
        assert {"cvm/sources.pad", "cvm/fetch.copy", "cvm/annotated",
                f"cvm/execute:{res.source.name}"} <= names
        assert "cvm/not.mirrored" not in names


class TestOperatorScopes:
    SCOPE = r'op_name="[^"]*?/(\d{3}\.vec\.\w+)'

    @staticmethod
    def _compiled(ctx, frame):
        res = ctx.compile(frame, target="local", cache=PlanCache())
        text = res.executable.fn.lower(
            dict(ctx.sources(res))).compile().as_text()
        return res, text

    def test_compiled_plan_names_each_op_by_its_instruction(self, q1_setup):
        import re

        tables, ctx, frame, _, _ = q1_setup
        res, text = self._compiled(ctx, frame)
        found = set(re.findall(self.SCOPE, text))
        body = {f"{i:03d}.{ins.opcode}" for i, ins in enumerate(res.program.body)}
        assert found and found <= body
        assert any("GroupAgg" in s or "FusedSelectAgg" in s for s in found)

    def test_scopes_change_only_metadata(self, q1_setup, monkeypatch):
        """The compiled ops are the same with and without the scopes: the
        optimized HLO differs only in source locations and ``op_name``."""
        import contextlib
        import re

        import repro.backends.emit as emit

        def strip(text):
            text = re.sub(r"\nFileNames\n.*?\nStackFrames\n(?:\d+ \{[^}]*\}\n)*",
                          "\n", text, flags=re.S)
            return re.sub(r",? metadata=\{[^}]*\}", "", text)

        tables, ctx, _, _, _ = q1_setup
        for q in ("q1", "q12"):
            frame = tpch.QUERIES[q](ctx)
            _, scoped = self._compiled(ctx, frame)
            monkeypatch.setattr(emit, "op_scope",
                                lambda i, ins: contextlib.nullcontext())
            _, plain = self._compiled(ctx, frame)
            monkeypatch.undo()
            assert re.search(self.SCOPE, scoped)
            assert not re.search(self.SCOPE, plain)
            assert strip(scoped) == strip(plain)
