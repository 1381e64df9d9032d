"""Device ms per step in the program scopes ``encode`` (input
encoders), ``gnn.layer<l>`` and ``head`` (decoder and loss), forward
and backward (``scopes.py``)."""
from benchmarks.chip import scopes

LAYER = "device step: GNN and head"
UNIT = "ms"
MOVES = "train_step_ms"


def read(run):
    return scopes.step_ms(scopes.summary(run), "gnn")
