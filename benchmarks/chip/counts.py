"""Operations and bytes one training step needs, from the configuration's
shapes alone (not from the compiled program).

FLOPs count every multiply and add of the forward pass, and of the
backward pass what it needs: each matrix product's weight gradient, and
its input gradient except where the input is a raw feature row, which
takes no gradient.  Fanout means count one multiply and one add per
gathered element.  Elementwise activations are left out.

Bytes count what the step has to move at least once: every sampled
feature or embedding row read, each embedding row's gradient written and
read back, the sparse-adagrad rows (table row and accumulator) read and
written, the CSR reads of each draw (two row pointers per row, one
column per slot), and the dense parameters with their gradient and both
AdamW moments.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple


def step_counts(layers, input_dims: Dict[str, int], tables: Dict[str, int],
                hidden: int, head: dict, dense_params: int,
                layer_flops: Callable[..., Tuple[float, float]]
                ) -> Dict[str, float]:
    """``layers``: ``reference.plan`` of one chip's batch; ``input_dims``:
    every input node type's row width; ``tables``: the node types whose
    rows are learnable embeddings (and their width); ``head``: ``{"kind":
    "nc", "batch", "classes"}`` or ``{"kind": "lp", "batch", "k"}``;
    ``layer_flops(layer)``: one GNN layer's forward and backward FLOPs
    (``models/<gs.gnn.model>.py``)."""
    H = hidden
    rows0 = dict(layers[0].src)
    fwd = bwd = 0.0
    for nt, n in rows0.items():
        mm = 2.0 * n * input_dims[nt] * H
        fwd += mm
        bwd += mm * (2 if nt in tables else 1)
    for layer in layers:
        f, b = layer_flops(layer)
        fwd += f
        bwd += b
    B = head["batch"]
    if head["kind"] == "nc":
        mm = 2.0 * B * H * (H + head["classes"])
    else:
        mm = 3.0 * B * H * (1 + head["k"])
    fwd += mm
    bwd += 2 * mm

    nbytes = 0.0
    for nt, n in rows0.items():
        row = 4.0 * input_dims[nt]
        nbytes += n * row
        if nt in tables:
            nbytes += 2 * n * row + 2 * n * (row + 4)
    for layer in layers:
        for _, n, f, _ in layer.edges:
            nbytes += 8.0 * n + 4.0 * n * f
    nbytes += 7 * 4.0 * dense_params
    return {"flops": fwd + bwd, "bytes": nbytes}
