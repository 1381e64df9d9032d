"""Share of the traced training window in which no operation ran on the
device: 1 - (union of device-op intervals) / window, averaged over the
cell's chips."""
LAYER = "device"
UNIT = "%"
MOVES = "train_step_ms"


def read(run):
    tr = run["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
