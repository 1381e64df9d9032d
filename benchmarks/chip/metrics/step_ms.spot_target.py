"""Device ms per step in the program scope ``spot_target``: the
in-step SpotTarget test of sampled edges against the batch's target
pairs (``scopes.py``)."""
from benchmarks.chip import scopes

LAYER = "device step: SpotTarget"
UNIT = "ms"
MOVES = "train_step_ms"


def read(run):
    return scopes.step_ms(scopes.summary(run), "spot_target")
