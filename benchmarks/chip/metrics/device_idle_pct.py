"""device_idle_pct: the share of the traced window, from the first query's
start to the last one's end, in which no operation ran on the device
(averaged over the devices)."""


def read(run):
    t = run.trace
    if t is None or not t.ops:
        return None
    lo, hi = t.window()
    return 100.0 * (1.0 - t.busy(lo, hi) / (hi - lo)) if hi > lo else None
