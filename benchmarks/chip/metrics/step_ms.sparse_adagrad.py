"""Device ms per step in the program scope ``sparse_adagrad``: the
embedding tables' update from their gradient rows (``scopes.py``)."""
from benchmarks.chip import scopes

LAYER = "device step: sparse adagrad"
UNIT = "ms"
MOVES = "train_step_ms"


def read(run):
    return scopes.step_ms(scopes.summary(run), "sparse_adagrad")
