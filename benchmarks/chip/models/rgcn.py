"""RGCN (Schlichtkrull et al. 2018, arXiv:1703.06103): one message-passing
layer of a configuration whose ``gs.gnn.model`` is ``rgcn``.

- Layer: ``h'_v = b + h_v W_self + sum_r mean_{unmasked draws u of
  r}(h_u) W_r``, one ``W_self`` and ``b`` per node type, one ``W_r`` per
  relation, all ``hidden x hidden``; ReLU between layers, none after the
  last.
- FLOPs: a destination row's self product; per relation, the mean over
  its fanout and one product per destination row.

Plain ``jax.numpy``; imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import ekey


def layer_shapes(ntypes, ekeys, hidden: int, gnn: dict) -> dict:
    """One entry of ``params["gnn"]["layers"]``, in the program's layout."""
    z = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    return {"w_rel": {e: z(hidden, hidden) for e in ekeys},
            "w_self": {nt: z(hidden, hidden) for nt in ntypes},
            "b": {nt: z(hidden) for nt in ntypes}}


def layer(p, plan_layer, masks, h, mm, last: bool) -> dict:
    """The layer's destination rows per node type from the source
    frontier ``h``; ``masks`` holds each relation's unmasked draws."""
    out = {}
    for nt, n in plan_layer.dst:
        acc = mm(h[nt][:n], p["w_self"][nt]) + p["b"][nt]
        for et, nd, f, off in plan_layer.edges:
            if et[2] != nt:
                continue
            rows = h[et[0]][off:off + nd * f].reshape(nd, f, -1)
            w = masks[ekey(et)].astype(rows.dtype)
            agg = (rows * w[..., None]).sum(1) / jnp.maximum(
                w.sum(1), 1)[:, None]
            acc = acc + mm(agg, p["w_rel"][ekey(et)])
        out[nt] = acc
    return out if last else {nt: jax.nn.relu(v) for nt, v in out.items()}


def layer_flops(plan_layer, hidden: int, gnn: dict):
    """The layer's forward and backward FLOPs, as ``counts.py`` counts."""
    H = hidden
    fwd = bwd = 0.0
    for _, n in plan_layer.dst:
        fwd += 2.0 * n * H * H
        bwd += 4.0 * n * H * H
    for _, n, f, _ in plan_layer.edges:
        agg = 2.0 * n * f * H
        mm = 2.0 * n * H * H
        fwd += agg + mm
        bwd += agg + 2 * mm
    return fwd, bwd
