"""The GNN layers' share of their compute roofline: the FLOPs of every
layer of one step, as ``models/<gs.gnn.model>.py`` counts them (its
``layer_flops`` over ``reference.plan`` of one chip's batch), per
``step_ms.gnn_layers``, over the chip's bf16 peak."""
from benchmarks.chip import reference, spec

LAYER = "device step: GNN layers"
UNIT = "%"
MOVES = "train_step_ms"


def layer_flops(run):
    """Forward and backward FLOPs of the GNN layers of one step of a node
    classification cell on one chip."""
    cell = run["cell"]
    gs = cell.config["gs"]
    seeds = {gs["node_classification"]["target_ntype"]:
             int(cell.traffic["batch_size"])}
    etypes = sorted(tuple(e) for e in run["runner"].graph.etypes)
    gnn = spec.gnn_model(cell)
    return sum(sum(gnn.layer_flops(layer, gs["gnn"]["hidden"], gs["gnn"]))
               for layer in reference.plan(etypes, gs["gnn"]["fanout"], seeds))


def read(run):
    ms = spec.metric_reader("step_ms.gnn_layers").read(run)
    if not ms:
        return None
    return (100.0 * layer_flops(run) / (ms * 1e-3)
            / run["peaks"]["bf16_flops_per_s"])
