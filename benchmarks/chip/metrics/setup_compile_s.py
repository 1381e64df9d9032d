"""Seconds of the epoch program's first dispatch in set-up: tracing and
compiling it, or loading it from the persistent cache, and enqueueing
the first epoch."""
LAYER = "set-up: first dispatch of the epoch program"
UNIT = "s"
MOVES = "setup_s"


def read(run):
    return run["t_compile_s"]
