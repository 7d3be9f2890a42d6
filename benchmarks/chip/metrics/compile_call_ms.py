"""compile_call_ms: mean milliseconds per query of the benchmark's span
around building the Frame and ``Context.compile`` (frontend and driver;
a plan-cache hit once warm).  Only entries that compile apart from the call
have the span."""


def read(run):
    ms = [q.phases["compile"] * 1e3 for q in run.done() if "compile" in q.phases]
    return sum(ms) / len(ms) if ms else None
