"""End-to-end TPC-H: frontend → CVM rewriting → JAX backend vs numpy oracle.

These are the paper's own workloads (Figs. 2–4).  Each query is validated
(a) on the abstract interpreter and (b) compiled through the full pipeline
(CSE/DCE → [Parallelize] → rel→vec lowering → fusion → jax.jit) on the
local backend, sequential and parallel.
"""

import numpy as np
import pytest

from repro.backends.interp import Interpreter
from repro.relational import tpch


@pytest.fixture(scope="module")
def tables():
    return tpch.generate(sf=0.002, seed=7)


@pytest.fixture(scope="module")
def ctx(tables):
    return tpch.make_context(tables, pad_to=256)


@pytest.mark.parametrize("qname", sorted(tpch.QUERIES))
class TestTPCH:
    def test_interpreter_matches_reference(self, qname, ctx, tables):
        frame = tpch.QUERIES[qname](ctx)
        program = frame.program(qname)
        (out,) = Interpreter(sources=tables).run(program)
        want = tpch.REFERENCES[qname](tables)
        got = out if isinstance(out, dict) else {"result": out}
        # interpreter returns exact tables; scalars come back as dicts
        got = {k: np.asarray(v) for k, v in got.items()}
        tpch.assert_result_close(got, want, tpch.GROUP_KEYS.get(qname, ()))

    def test_compiled_sequential(self, qname, ctx, tables):
        got = tpch.QUERIES[qname](ctx).collect()
        want = tpch.REFERENCES[qname](tables)
        tpch.assert_result_close(got, want, tpch.GROUP_KEYS.get(qname, ()))

    def test_compiled_parallel(self, qname, ctx, tables):
        got = tpch.QUERIES[qname](ctx).collect(parallel=4)
        want = tpch.REFERENCES[qname](tables)
        tpch.assert_result_close(got, want, tpch.GROUP_KEYS.get(qname, ()))


def test_parallel_rewrite_actually_fires_on_q6(ctx):
    """The compiled parallel plan must contain the Split/CE structure."""
    frame = tpch.QUERIES["q6"](ctx)
    compiled = ctx.compile(frame, parallel=4)
    ops = compiled.program.opcodes()
    assert "cf.Split" in ops and "cf.ConcurrentExecute" in ops
    assert "rel.CombinePartials" in ops


def test_fusion_fires_on_q6(ctx):
    """Sequential Q6 must collapse into the single-pass FusedSelectAgg."""
    frame = tpch.QUERIES["q6"](ctx)
    compiled = ctx.compile(frame, parallel=None)
    ops = compiled.program.opcodes()
    assert "vec.FusedSelectAgg" in ops
    assert "vec.MaskSelect" not in ops and "vec.AggrVec" not in ops
