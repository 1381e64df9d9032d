"""What the benchmark knows of how the program lays out its state: the
trainer's weight tree, its embedding tables, its train loader and the
hook around its epoch program.  Everything else under
``benchmarks/chip`` reads the program only through these functions, so
a change of the program's layout (stacked per-relation weights, say)
meets the benchmark here alone.
"""
from __future__ import annotations

from typing import Callable, Dict

import numpy as np

from benchmarks.chip import reference, spec


def weight_shapes(cell: spec.Cell, gd):
    """The dense weights' tree (shapes only), in the layout the program
    keeps them (input encoders, RGCN layers, the task head), and the
    ``(rows, dim)`` of each featureless node type's embedding table.
    The reference computes over the same tree."""
    import jax
    import jax.numpy as jnp
    gs = cell.config["gs"]
    H = gs["gnn"]["hidden"]
    dims = dict(gd.feat_dims)
    for nt in gd.num_nodes:
        dims.setdefault(nt, gs["gnn"]["sparse_embed_dim"])
    ekeys = sorted(reference.ekey(e) for e in gd.edges)
    z = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    ntypes = sorted(gd.num_nodes)
    layer = {"w_rel": {e: z(H, H) for e in ekeys},
             "w_self": {nt: z(H, H) for nt in ntypes},
             "b": {nt: z(H) for nt in ntypes}}
    if gs["task"] == "node_classification":
        C = gs["node_classification"]["num_classes"]
        dec = {"w1": z(H, H), "b1": z(H), "w2": z(H, C), "b2": z(C)}
    else:
        dec = {"rel": z(len(ekeys), H)}
    dense = {"dec": dec, "gnn": {
        "input": {nt: {"b": z(H), "w": z(dims[nt], H)}
                  for nt in sorted(dims)},
        "layers": [layer] * gs["gnn"]["num_layers"]}}
    tables = {nt: (gd.num_nodes[nt], gs["gnn"]["sparse_embed_dim"])
              for nt in ntypes if nt not in gd.feat_dims}
    return dense, tables


def install(runner, cell: spec.Cell, gd, params, tables) -> None:
    """Put the benchmark's weights and tables into the runner's trainer,
    with a fresh optimizer state, after checking that the trainer lays
    them out as ``weight_shapes`` does."""
    import jax
    import jax.numpy as jnp
    tr = runner.trainer
    template, table_shapes = weight_shapes(cell, gd)
    got = (jax.tree_util.tree_map(lambda x: tuple(x.shape), tr.params),
           {nt: (e.num_nodes, e.dim) for nt, e in tr.sparse_embeds.items()})
    want = (jax.tree_util.tree_map(lambda x: tuple(x.shape), template),
            table_shapes)
    if got != want:
        raise ValueError(f"the program's weights {got} are not laid out "
                         f"as the benchmark makes them {want}")
    tr.params = params
    tr.opt_state = tr.optimizer.init(params)
    for nt, emb in tr.sparse_embeds.items():
        emb.table = tables[nt]
        emb.gsum = jnp.zeros((emb.num_nodes,), jnp.float32)
        emb._place()
    if tr.mesh is not None:
        tr._place_on_mesh(tr.mesh)


def state(runner) -> Dict[str, Dict[str, np.ndarray]]:
    """A host copy of the trainer's weights and tables, as
    ``{"params": tree, "tables": {ntype: (rows, dim)}}``."""
    import jax
    tr = runner.trainer
    return {"params": jax.device_get(tr.params),
            "tables": {nt: emb.state_dict()["table"]
                       for nt, emb in tr.sparse_embeds.items()}}


def train_loader(runner, cell: spec.Cell):
    """The runner's train loader over the first ``batches_per_epoch``
    batches of its shuffled train split."""
    n = int(cell.traffic["batches_per_epoch"]) * int(cell.traffic["batch_size"])
    if cell.config["gs"]["task"] == "link_prediction":
        runner.tr_e = runner.tr_e[:n]
        return runner._train_loader()
    tr_ids, _, _ = runner.data.train_val_test_nodes(
        runner.target_ntype, rng=runner._split_rng())
    return runner._train_loader(tr_ids[:n])


def wrap_epoch(trainer, wrap: Callable[[Callable], Callable]) -> None:
    """Wrap the epoch program the engine gets from ``trainer``."""
    fns_for = trainer._engine_fns_for

    def wrapped(*a):
        fns = dict(fns_for(*a))
        fns["epoch"] = wrap(fns["epoch"])
        return fns
    trainer._engine_fns_for = wrapped
