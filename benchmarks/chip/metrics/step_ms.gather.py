"""Device ms per step in the program scopes ``gather.features`` (rows of
the resident feature tables) and ``gather.embeddings`` (rows of the
learnable embedding tables) (``scopes.py``)."""
from benchmarks.chip import scopes

LAYER = "device step: gathers"
UNIT = "ms"
MOVES = "train_step_ms"


def read(run):
    return scopes.step_ms(scopes.summary(run), "gather")
