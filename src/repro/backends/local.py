"""Local backend — the JITQ analogue.

Lowers a final-flavor CVM program into one ``jax.jit``-compiled callable:
tree-shaped data paths fuse inside XLA exactly like JITQ's pipeline JIT;
``ConcurrentExecute`` unrolls into per-chunk traces whose parallelism XLA
exploits on the host (thread-level).  ``compile`` returns an executable that
takes the source collections and returns the program results.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Mapping, Optional

import jax

from ..core.program import Program
from .emit import EvalCtx, evaluate_program


@dataclass
class Compiled:
    program: Program
    fn: Callable[..., List[Any]]
    #: variant of ``fn`` that also returns the cardinality taps (jitted
    #: separately — the traced path must not slow the plain one down)
    traced_fn: Optional[Callable[..., Any]] = None

    def __call__(self, sources: Optional[Mapping[str, Any]] = None, *args: Any) -> List[Any]:
        return self.fn(dict(sources or {}), *args)

    def run_traced(self, sources: Optional[Mapping[str, Any]] = None,
                   *args: Any):
        """Execute and measure: ``(results, {tap key → TapRecord}, {})``.

        Cardinalities come back as scalar outputs of the jitted body
        (host-callback-free); per-op wall times are not observable inside a
        fused XLA module, hence the empty third element."""
        from ..obs.feedback import TapRecord

        outs, taps = self.traced_fn(dict(sources or {}), *args)
        cards = {
            k: TapRecord(int(occ), None if ri is None else int(ri), int(ro))
            for k, (occ, ri, ro) in taps.items()
        }
        return outs, cards, {}


class LocalBackend:
    name = "local"

    def __init__(self, use_kernels: bool = False, jit: bool = True) -> None:
        from ..kernels import interpret_on

        self.use_kernels = use_kernels
        # kernels run where the jitted program runs: the default backend
        self.interpret = interpret_on(jax.default_backend())
        self.jit = jit

    def compile(self, program: Program) -> Compiled:
        def run(sources: Dict[str, Any], *args: Any) -> List[Any]:
            ctx = EvalCtx(sources=sources, use_kernels=self.use_kernels,
                          interpret=self.interpret)
            return evaluate_program(ctx, program, *args)

        def run_traced(sources: Dict[str, Any], *args: Any):
            ctx = EvalCtx(sources=sources, use_kernels=self.use_kernels,
                          interpret=self.interpret, taps={})
            outs = evaluate_program(ctx, program, *args)
            return outs, ctx.taps

        fn = jax.jit(run) if self.jit else run
        tfn = jax.jit(run_traced) if self.jit else run_traced
        return Compiled(program, fn, tfn)
