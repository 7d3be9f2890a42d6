"""``chip_smoke.py`` on the CPU: its query phases against the TPC-H oracles.

The script itself refuses to run without a TPU; these tests drive its phase
function at a tiny scale, so a wrong path, argument or comparison in it shows
without a chip.  The four-chip path runs on four virtual host devices in a
subprocess (the device count is fixed when JAX starts).
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.launch.hermetic import subprocess_env

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main(["--sf", "0.002"]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_local_phases_match_oracles():
    records = chip_smoke.run_phases(0.002, 1, jax.devices()[:1])
    assert [(r["phase"], r["query"]) for r in records] == [
        (p, q) for p in ("xla", "kernels")
        for q in ("q1", "q12", "q14", "q19", "q4", "q6")]
    bad = [r for r in records if not r["ok"]]
    assert not bad, bad
    assert all(r["degraded"] == [] and r["fallback_steps"] == 0
               for r in records)


SPMD_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    sys.path.insert(0, os.getcwd())
    import chip_smoke
    records = chip_smoke.run_phases(0.002, 4, jax.devices()[:4])
    assert len(records) == 6 and {r["phase"] for r in records} == {"spmd"}
    bad = [r for r in records if not r["ok"]]
    assert not bad, bad
    # the spmd target places the tables itself: each device holds a
    # quarter of every table's rows, none holds a whole table
    from repro.relational import tpch
    ctx = tpch.make_context(tpch.generate(sf=0.002, seed=0))
    res = ctx.compile(tpch.QUERIES["q6"](ctx), target="spmd", parallel=4)
    for name, vt in ctx.sources(res).items():
        for a in jax.tree.leaves(vt):
            shards = a.addressable_shards
            assert len({s.device for s in shards}) == 4, name
            assert all(s.data.shape[0] * 4 == a.shape[0] for s in shards), name
    print("SPMD_OK")
""")


def test_spmd_phase_on_four_host_devices():
    proc = subprocess.run([sys.executable, "-c", SPMD_SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          env=subprocess_env(ROOT), cwd=str(ROOT))
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SPMD_OK" in proc.stdout


def test_first_calls_run_the_plain_executable(monkeypatch):
    """The guarded first call is traced without the cardinality taps: the
    traced twin never runs (a call to it would step down the ladder)."""
    from repro.backends.local import Compiled

    def refuse(*a, **k):
        raise AssertionError("run_traced called")

    monkeypatch.setattr(Compiled, "run_traced", refuse)
    records = chip_smoke.run_phases(0.002, 1, jax.devices()[:1])
    bad = [r for r in records if not r["ok"]]
    assert not bad, bad
