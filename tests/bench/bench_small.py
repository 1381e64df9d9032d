"""Cells of ``BENCHMARK.json`` cut to a size the CPU tests can run."""
import copy

from benchmarks.chip import spec


def small_cell(name: str, div: int = 2000, batch: int = 32,
               batches: int = 3, hidden: int = 32):
    """``name`` with every node and edge count divided by ``div``, fanout
    3x3, ``hidden`` wide, and ``batches`` batches of ``batch`` an epoch,
    compared with a reference whose products are whole float32."""
    c = copy.deepcopy(spec.find_cell(name))
    g = c.config["graph"]
    g["num_nodes"] = {k: max(64, v // div) for k, v in g["num_nodes"].items()}
    g["relations"] = [[s, r, d, max(200, n // div)]
                      for s, r, d, n in g["relations"]]
    gnn = c.config["gs"]["gnn"]
    gnn["hidden"], gnn["fanout"] = hidden, [3, 3]
    c.traffic = dict(c.traffic, batch_size=batch, batches_per_epoch=batches)
    # the CPU multiplies float32 inputs whole, where a TPU's default
    # product rounds them to bfloat16
    c.config["reference"]["products"] = "float32"
    return c
