"""queries_per_s: queries answered in the window over the window's seconds."""


def read(run):
    return len(run.done()) / run.window_s
