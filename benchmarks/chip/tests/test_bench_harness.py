"""The harness on the CPU: its files, its names, one tiny cell end to end,
its refusal without a TPU, and its metric readers on known spans.

The TPU check is steered here, in the tests: ``require_devices`` and
``device_peaks`` are replaced so that the rest of a run drives the CPU."""

import json
import re
import shutil
import subprocess
import sys

import jax
import pytest

import bench
import trace_reduce

SPEC = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
V5E = {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9, "bf16_flops_per_s": 197e12}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_benchmark_json_keeps_to_its_shape():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"]
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}" and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer",
                                          "moves"}
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def test_names_and_units_use_only_the_allowed_characters():
    names = [SPEC[k][i]["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for i in range(len(SPEC[k]))]
    names += [w[k] for w in SPEC["workloads"] for k in ("config", "traffic")]
    names += [r for c in SPEC["configs"] for r in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(n for k in ("end_to_end", "per_layer") for n in
                   (m["name"] for m in SPEC[k]))) == len(SPEC["end_to_end"]) + len(SPEC["per_layer"])
    assert all(UNIT.match(m["unit"]) for k in ("end_to_end", "per_layer") for m in SPEC[k])
    assert all(m["better"] in ("lower", "higher") for k in ("end_to_end", "per_layer")
               for m in SPEC[k])


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_its_files_by_name(cell):
    c = bench.load_cell(cell)
    assert c.config["scale_factor"] > 0 and c.traffic["entry"] in bench.ENTRIES
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2 and c.per_layer
    for metric in [*c.end_to_end, *c.per_layer]:
        assert callable(bench.load_reader(metric))


@pytest.mark.parametrize("layout", [{"target": "spmd", "parallel": 4},
                                    {"target": "spmd", "parallel": 1},
                                    {"target": "local", "parallel": 4}])
def test_a_cell_whose_chips_do_not_match_its_target_is_refused(monkeypatch, layout):
    load = bench._load_json

    def config(path):
        data = load(path)
        return {**data, **layout} if path.parent.name == "configs" else data

    monkeypatch.setattr(bench, "_load_json", config)
    with pytest.raises(SystemExit, match="chips"):
        bench.load_cell("tpch_sf1.power")


def test_the_cell_passes_its_target_and_options_to_the_program():
    cell = bench.load_cell("tpch_sf10.power")
    assert cell.options() == {"target": "local", "parallel": None}
    cell.traffic["compile"] = {"use_kernels": True}
    assert cell.options()["use_kernels"] is True


def test_an_unknown_device_kind_raises():
    assert bench.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.device_peaks("TPU v9 imaginary")


def test_refuses_without_a_tpu(monkeypatch, capsys):
    monkeypatch.setattr(bench, "use_checkout_dirs", lambda: None)
    with pytest.raises(SystemExit) as e:
        bench.main(["--workload", "tpch_sf1.power", "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(bench.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload", "tpch_sf1.power",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""


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


def last_line(capsys):
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_cell_runs_end_to_end(on_cpu, capsys, trace):
    assert bench.main(["--workload", "tpch_sf1.power", "--seed", str(2**31 + 3),
                       "--seconds", "0.5", "--trace", str(trace)]) == 0
    result, err = last_line(capsys)
    keys = RESULT_KEYS + (["breakdown"] if trace else []) + ["checks"]
    assert list(result) == keys
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 6 and result["attempted"] % 6 == 0
    assert set(result["checks"]) == {"wrong_exact", "rel_err", "missing"}
    assert err.strip().splitlines()[-1].startswith("check missing 0 limit 0")
    cell = bench.load_cell("tpch_sf1.power")
    if trace:
        assert {"compile_call_ms"} <= set(result["metrics"]) <= set(cell.per_layer)
        assert result["device"]["window_s"] > 0
    else:
        assert {"queries_per_s", "geomean_ms", "setup_s"} <= set(result["metrics"])
        assert set(result["metrics"]) <= set(cell.end_to_end)
    assert all(set(m) == {"value", "unit"} for m in result["metrics"].values())


def test_the_collect_entry_runs_end_to_end(on_cpu, capsys):
    assert bench.main(["--workload", "tpch_sf1.collect", "--seed", "7",
                       "--seconds", "0.2", "--trace", "0"]) == 0
    result, _ = last_line(capsys)
    assert result["correct"] is True and result["attempted"] % 6 == 0


def test_a_degraded_plan_counts_as_failed(on_cpu, monkeypatch, capsys):
    import dataclasses

    from repro.frontends.dataflow import Context

    compile_ = Context.compile
    monkeypatch.setattr(Context, "compile", lambda self, frame, **kw: dataclasses.replace(
        compile_(self, frame, **kw), degraded=("groupby=sorted",)))
    bench.main(["--workload", "tpch_sf1.power", "--seed", "8", "--seconds", "0.2"])
    result, _ = last_line(capsys)
    assert result["failed"] == result["attempted"] > 0


def break_answers(entry):
    """An answer altered where it is produced: the first cell of every answer."""
    run = entry.run

    def altered(rec):
        out = dict(run(rec))
        k = next(iter(out))
        a = out[k].copy()
        a.flat[0] = a.flat[0] + 1 if a.dtype.kind in "iu" else a.flat[0] * 1.01
        out[k] = a
        return out

    entry.run = altered
    return entry


def drop_half_the_rows(entry):
    """Half of the lineitem rows left out under the plan, which then
    aggregates over the rest."""
    import jax.numpy as jnp

    from repro.relational.runtime import VecTable

    li = entry.sources["lineitem"]
    keep = jnp.arange(li.capacity) < li.capacity // 2
    entry.sources = dict(entry.sources, lineitem=VecTable(li.cols, li.valid & keep))
    return entry


def test_a_compile_in_the_window_counts_as_failed(on_cpu, monkeypatch, capsys):
    set_up = bench.set_up

    def compiling(cell, seed):
        tables, ctx, entry = set_up(cell, seed)
        run = entry.run

        def run_and_compile(rec):
            jax.jit(lambda x: x + len(rec.name))(1.0).block_until_ready()
            return run(rec)

        entry.run = run_and_compile
        return tables, ctx, entry

    monkeypatch.setattr(bench, "set_up", compiling)
    bench.main(["--workload", "tpch_sf1.power", "--seed", "10", "--seconds", "0.2"])
    result, err = last_line(capsys)
    assert result["failed"] == result["attempted"] > 0
    assert result["correct"] is True


@pytest.mark.parametrize("fault", [break_answers, drop_half_the_rows])
def test_a_broken_timed_path_is_not_correct(on_cpu, monkeypatch, capsys, fault):
    set_up = bench.set_up

    def broken(cell, seed):
        tables, ctx, entry = set_up(cell, seed)
        return tables, ctx, fault(entry)

    monkeypatch.setattr(bench, "set_up", broken)
    assert bench.main(["--workload", "tpch_sf1.power", "--seed", "9",
                       "--seconds", "0.2"]) == 0
    result, err = last_line(capsys)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


# -- the metric readers on a known run -----------------------------------------

def known_run():
    """Two queries: q6 over [0, 10] s with 4 s on the device, q1 over
    [10, 20] s with 6 s; the compile spans take 1 s each."""
    spans = [trace_reduce.Span("query", "q6", 0.0, 10.0),
             trace_reduce.Span("compile", "q6", 0.0, 1.0),
             trace_reduce.Span("query", "q1", 10.0, 20.0),
             trace_reduce.Span("compile", "q1", 10.0, 11.0)]
    ops = {"/device:TPU:0": [("fusion.1", 2.0, 4.0), ("fusion.2", 3.0, 6.0),
                             ("sort.1", 12.0, 18.0)]}
    queries = [bench.Query("q6", 10.0, {"compile": 1.0}),
               bench.Query("q1", 10.0, {"compile": 1.0})]
    return bench.Run(queries=queries, window_s=20.0, setup_s=30.0, peak_bytes=5e9,
                     peaks=V5E, least_bytes={"q6": 819e9, "q1": 2 * 819e9},
                     trace=trace_reduce.Trace(spans, ops))


@pytest.mark.parametrize("metric,value", [
    ("queries_per_s", 0.1), ("geomean_ms", 10_000.0),
    ("peak_hbm_gb", 5.0), ("setup_s", 30.0), ("compile_call_ms", 1000.0),
    ("host_ms", 4000.0), ("device_idle_pct", 50.0), ("query_roofline", 30.0)])
def test_each_reader_gives_its_known_value(metric, value):
    assert bench.load_reader(metric)(known_run()) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["host_ms", "device_idle_pct", "query_roofline"])
def test_trace_readers_return_nothing_without_a_device_trace(metric):
    run = known_run()
    run.trace = trace_reduce.Trace(run.trace.spans, {})
    assert bench.load_reader(metric)(run) is None
