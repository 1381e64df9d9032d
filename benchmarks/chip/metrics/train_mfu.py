"""Model FLOPs of forward and backward per step and chip (from shapes,
``counts.py``) times steps per second of the traced window, over the
chip's published bf16 peak."""
LAYER = "device step"
UNIT = "%"
MOVES = "train_step_ms"


def read(run):
    if run["window_s"] <= 0:
        return None
    rate = run["steps"] / run["window_s"]
    return 100.0 * run["work"]["flops"] * rate / run["peaks"]["bf16_flops_per_s"]
