"""Device ms per step in the program scopes ``gnn.layer<l>``, forward
and backward (``scopes.py``): the GNN layers alone, without the input
encoders and the head."""
from benchmarks.chip import scopes

LAYER = "device step: GNN layers"
UNIT = "ms"
MOVES = "train_step_ms"


def read(run):
    s = scopes.summary(run)
    if not s:
        return None
    return sum(v for k, v in s["scope_ms"].items()
               if k.rstrip("0123456789") == "gnn.layer") / s["steps"]
