"""GraphStorm model template: input encoder -> graph encoder -> decoder.

``GSgnnModel`` mirrors the paper's three-component split (§3.1.3):
node input encoders project raw features (or embedding-table rows, or LM
embeddings) to the hidden width; the graph encoder is a stack of zoo
layers; the task decoder lives in repro.gnn.decoders.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.gnn.layers import LAYERS
from repro.gnn.schema import BlockSchema

GNN_ZOO = tuple(LAYERS)


@dataclasses.dataclass(frozen=True)
class GSgnnModel:
    kind: str            # zoo entry
    hidden: int
    num_layers: int
    nheads: int = 4
    ntypes: Tuple[str, ...] = ()
    etypes: Tuple[Tuple[str, str, str], ...] = ()  # (ekey, src_t, dst_t)
    feat_dims: Tuple[Tuple[str, int], ...] = ()    # per-ntype input dim
    # Pallas kernel routing (gnn.use_pallas in GSConfig); None inherits
    # the process default (set_use_pallas shim)
    use_pallas: Optional[bool] = None


def init_gnn_model(rng, model: GSgnnModel):
    if model.kind not in LAYERS:
        raise KeyError(f"unknown GNN {model.kind!r}; zoo: {GNN_ZOO}")
    init_fn, _ = LAYERS[model.kind]
    keys = jax.random.split(rng, model.num_layers + 1)
    feat_dims = dict(model.feat_dims)
    # input encoder: project each ntype's raw features to hidden
    k_in = jax.random.split(keys[0], max(len(feat_dims), 1))
    inp = {}
    for k, (nt, d) in zip(k_in, sorted(feat_dims.items())):
        inp[nt] = {
            "w": jax.random.normal(k, (d, model.hidden), jnp.float32)
            * (d ** -0.5),
            "b": jnp.zeros((model.hidden,), jnp.float32),
        }
    d_in = {nt: model.hidden for nt in model.ntypes}
    layers = [init_fn(keys[1 + i], list(model.ntypes), list(model.etypes),
                      d_in, model.hidden, model.nheads)
              for i in range(model.num_layers)]
    return {"input": inp, "layers": layers}


def input_encode(params, feats: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    out = {}
    for nt, x in feats.items():
        p = params["input"][nt]
        out[nt] = jax.nn.relu(x @ p["w"] + p["b"])
    return out


def gnn_apply_blocks(params, model: GSgnnModel, schema: BlockSchema,
                     arrays) -> Dict[str, jax.Array]:
    """Run the GNN over an MFG mini-batch; returns seed embeddings."""
    from repro.gnn.aggregate import routing
    from repro.trainer import tracing
    _, apply_fn = LAYERS[model.kind]
    with routing(model.use_pallas):
        with tracing.scope("encode"):
            h = input_encode(params, arrays["feats"])
        for l, lsch in enumerate(schema.layers):
            arrays_l = {"masks": arrays["masks"][l]}
            if arrays.get("delta_t") and l < len(arrays["delta_t"]):
                arrays_l["delta_t"] = arrays["delta_t"][l]
            with tracing.scope(f"gnn.layer{l}"):
                h = apply_fn(params["layers"][l], lsch, arrays_l, h)
                if l < schema.num_layers - 1:
                    h = {nt: jax.nn.relu(v) for nt, v in h.items()}
    return h


def model_meta_from_graph(graph, kind: str, hidden: int, num_layers: int,
                          nheads: int = 4,
                          extra_feat_dims: Optional[Dict[str, int]] = None,
                          feat_field: str = "feat",
                          use_pallas: Optional[bool] = None
                          ) -> GSgnnModel:
    from repro.gnn.schema import ekey
    feat_dims = {nt: graph.feat_dim(nt, feat_field) for nt in graph.ntypes
                 if graph.feat_dim(nt, feat_field)}
    if extra_feat_dims:
        feat_dims.update(extra_feat_dims)
    return GSgnnModel(
        kind=kind, hidden=hidden, num_layers=num_layers, nheads=nheads,
        ntypes=tuple(graph.ntypes),
        etypes=tuple((ekey(et), et[0], et[2]) for et in graph.etypes),
        feat_dims=tuple(sorted(feat_dims.items())),
        use_pallas=use_pallas,
    )
