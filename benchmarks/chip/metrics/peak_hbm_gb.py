"""peak_hbm_gb: the largest ``peak_bytes_in_use`` over the cell's devices
after the window, in GB (10^9 bytes).  It is a running maximum since the
process started, so it covers set-up too."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
