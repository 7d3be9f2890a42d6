"""setup_s: seconds from the start of the process to the start of the
window: JAX start-up, making the tables, placing them, compiling (or
loading from the compile cache) the plans of the stream, and the warm-up
streams the traffic asks for (``warmup_streams``)."""


def read(run):
    return run.setup_s
