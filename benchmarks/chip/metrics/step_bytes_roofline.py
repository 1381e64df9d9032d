"""The whole step's share of its roofline: the least time one chip could
take for a step, max(FLOPs / bf16 peak, bytes / HBM bandwidth) with both
from shapes (``counts.py``), over the measured time per step."""
LAYER = "device step"
UNIT = "%"
MOVES = "train_step_ms"


def read(run):
    if run["window_s"] <= 0:
        return None
    w, p = run["work"], run["peaks"]
    least = max(w["flops"] / p["bf16_flops_per_s"],
                w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least * run["steps"] / run["window_s"]
