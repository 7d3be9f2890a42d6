"""query_roofline: the queries' share of their HBM roofline, in percent.

For the traced queries: the least time the chip could take, the bytes of
the live rows of every column each query reads over the peak HBM bandwidth
of the device kind (``peaks.json``), divided by the device-busy time inside
the queries' spans.  The queries do no floating-point work worth counting
against 197 TFLOP/s, so bandwidth bounds them."""


def read(run):
    t = run.trace
    if t is None or not t.ops:
        return None
    least = busy = 0.0
    for q in t.queries():
        least += run.least_bytes[q.query] / run.peaks["hbm_bytes_per_s"]
        busy += t.busy(q.start, q.end)
    return 100.0 * least / busy if busy > 0 else None
