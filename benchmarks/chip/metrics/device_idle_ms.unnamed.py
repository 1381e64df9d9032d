"""Device idle ms of one recorded epoch in which no program span finer
than ``engine.run`` is open on the host: idle time the program's spans
cannot name (``scopes.py``)."""
from benchmarks.chip import scopes

LAYER = "device"
UNIT = "ms"
MOVES = "train_step_ms"


def read(run):
    return scopes.idle_ms(scopes.summary(run), "unnamed")
