"""host_ms: mean milliseconds per query that neither the device nor the
driver's compile call accounts for: the query's span, less its compile span,
less the device-busy time inside the span (dispatch, sources, result
transfer).  Read from the trace."""


def read(run):
    t = run.trace
    if t is None or not t.ops:
        return None
    compiles = [s for s in t.spans if s.phase == "compile"]
    ms = []
    for q in t.queries():
        compile_s = sum(c.end - c.start for c in compiles
                        if q.start <= c.start and c.end <= q.end)
        ms.append(((q.end - q.start) - compile_s - t.busy(q.start, q.end)) * 1e3)
    return sum(ms) / len(ms) if ms else None
