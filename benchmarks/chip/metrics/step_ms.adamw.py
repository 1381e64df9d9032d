"""Device ms per step in the program scope ``adamw``: the dense
weights' AdamW update at the step's learning rate (``scopes.py``)."""
from benchmarks.chip import scopes

LAYER = "device step: AdamW"
UNIT = "ms"
MOVES = "train_step_ms"


def read(run):
    return scopes.step_ms(scopes.summary(run), "adamw")
