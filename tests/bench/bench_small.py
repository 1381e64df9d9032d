"""Cells of ``BENCHMARK.json`` cut to a size the CPU tests can run."""
import copy
import types

import numpy as np

from benchmarks.chip import harness, spec


def small_cell(name: str, div: int = 2000, batch: int = 32,
               batches: int = 3, hidden: int = 32, bench=None,
               here=spec.HERE):
    """``name`` with every node and edge count divided by ``div``, fanout
    3x3, ``hidden`` wide, and ``batches`` batches of ``batch`` an epoch,
    compared with a reference whose products are whole float32.
    ``bench`` and ``here`` are as ``spec.find_cell`` takes them."""
    c = copy.deepcopy(spec.find_cell(name, bench, here))
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


def graph_of_counts(cell):
    """What ``step_work`` and ``weight_shapes`` read of a graph, from the
    configuration's counts: no edges are drawn."""
    g = cell.config["graph"]
    edges = {}
    for s, r, d, n in g["relations"]:
        edges[(s, r, d)] = edges[(d, r + "-rev", s)] = n
    return types.SimpleNamespace(
        num_nodes=dict(g["num_nodes"]), edges=edges,
        feat_dims={k: int(v) for k, v in g["features"].items()})


def first_blocks(cell, gd, steps=1):
    """``steps`` blocks of seeds (node classification) or target edges
    (link prediction), drawn from a fixed seed."""
    B = int(cell.traffic["batch_size"])
    rng = np.random.default_rng(5)
    gs = cell.config["gs"]
    if gs["task"] == "node_classification":
        n = gd.num_nodes[gs["node_classification"]["target_ntype"]]
        return {"seeds": rng.integers(0, n, (steps, B)).astype(np.int32),
                "seed_mask": np.ones((steps, B), bool)}
    src, dst = gd.edges[tuple(gs["link_prediction"]["target_etype"])]
    i = rng.integers(0, len(src), (steps, B))
    return {"src": src[i].astype(np.int32), "dst": dst[i].astype(np.int32)}


def reference_run(cell, seed=2 ** 31 + 77, steps=1):
    """``harness.run_reference`` over the cell's graph from ``seed`` and
    ``first_blocks``."""
    import jax
    pseed = harness.data_seed(seed)
    fam = spec.graph_family(cell.config["graph"]["family"])
    gd = fam.generate(cell.config["graph"], pseed)
    try:
        return harness.run_reference(cell, gd, pseed,
                                     first_blocks(cell, gd, steps))
    finally:
        # JAX hands the jaxprs traced here, with the reference's frames in
        # their locations, to any later trace of the same function at the
        # same shapes in this process: a program lowered later would name
        # the reference's functions (``sample``, ``adamw``) as its own
        jax.clear_caches()
