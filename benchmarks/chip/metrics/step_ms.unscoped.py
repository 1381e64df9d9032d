"""Device ms per step of ops that no program scope claims: the scan's
carry, loss rescaling, slices and copies around the epoch program
(``scopes.py``)."""
from benchmarks.chip import scopes

LAYER = "device step"
UNIT = "ms"
MOVES = "train_step_ms"


def read(run):
    return scopes.step_ms(scopes.summary(run), "unscoped")
