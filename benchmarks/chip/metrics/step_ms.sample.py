"""Device ms per step in the program scopes ``expand`` (seed layout,
in-step negatives) and ``sample`` (the neighbour draws), less the
SpotTarget test nested in it (``scopes.py``)."""
from benchmarks.chip import scopes

LAYER = "device step: sampling"
UNIT = "ms"
MOVES = "train_step_ms"


def read(run):
    return scopes.step_ms(scopes.summary(run), "sample")
