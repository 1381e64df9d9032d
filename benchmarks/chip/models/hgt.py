"""HGT, the Heterogeneous Graph Transformer (Hu, Dong, Wang and Sun, WWW
2020, arXiv:2003.01332, §3 eqs. 1-5; the authors' pyHGT ``HGTConv``):
one layer of a configuration whose ``gs.gnn.model`` is ``hgt``.

- Layer, with ``d = hidden``, ``H = gnn.nheads`` heads of ``dh = d / H``
  and ``h`` the layer's input rows: ``q_t = h_t Q_type(t)``, ``k_s = h_s
  K_type(s)``, ``v_s = h_s V_type(s)``.  Per head ``i``, the score of a
  sampled slot ``(s, r)`` of destination ``t`` is ``(k_s,i W^att_r,i .
  q_t,i) * mu_r,i / sqrt(dh)``; ``alpha_i`` is one softmax over all
  unmasked slots of ``t`` across every relation (masked slots weigh 0);
  ``m_t = concat_i sum alpha_i * (v_s,i W^msg_r,i)``, 0 where ``t`` has
  no unmasked slot.  ``out_t = LayerNorm_type(t)(sig(beta) * (GELU(m_t)
  A_type(t)) + (1 - sig(beta)) * h_t)``, eps 1e-5, exact GELU; no ReLU
  between layers.
- Departures from the paper: no relative temporal encoding, no dropout,
  no biases in the K/Q/V/A projections; the shared input encoder
  ``ReLU(x W + b)`` stands in for pyHGT's tanh adapter.
- The reference computes it literally, slot by slot: K and V of every
  slot's row, then the relation's matrices, the joint softmax, and every
  product (q . k and alpha . v too) through ``mm``.
- FLOPs (``layer_flops``): the least dense association of the same
  math, so that no exact implementation reads over 100% of its roofline.
  Per destination type, Q and A (``4 n d^2``); per relation block of
  ``n`` destinations and fanout ``f``, ``W^att`` then ``K^T`` on the
  query side and ``V`` then ``W^msg`` on the alpha-weighted sums of
  source rows (``4 n (d^2 + d dh)``), the scores and those sums (``4 n
  f H d``); backward twice forward; element-wise work left out, as
  ``counts.py`` does.  The program as written (K and V of every slot,
  then ``W^att`` and ``W^msg`` on every slot) does more arithmetic than
  this count.

Plain ``jax.numpy``; imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import ekey

EPS = 1e-5


def layer_shapes(ntypes, ekeys, hidden: int, gnn: dict) -> dict:
    """One entry of ``params["gnn"]["layers"]``, in the program's layout."""
    H = int(gnn["nheads"])
    dh = hidden // H
    z = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    out = {name: {nt: z(hidden, hidden) for nt in ntypes}
           for name in ("k", "q", "v", "a")}
    out["skip"] = {nt: z(1) for nt in ntypes}
    out["norm"] = {nt: {"scale": z(hidden), "bias": z(hidden)}
                   for nt in ntypes}
    out["w_att"] = {e: z(H, dh, dh) for e in ekeys}
    out["w_msg"] = {e: z(H, dh, dh) for e in ekeys}
    out["prior"] = {e: z(H) for e in ekeys}
    return out


def _heads(x, n, f, H):
    """``(n * f, d)`` slot rows as ``(H, n, f, dh)``."""
    return x.reshape(n, f, H, -1).transpose(2, 0, 1, 3)


def _softmax(s, mask):
    """Softmax over the last axis among unmasked entries; 0 where masked
    and where a row has none unmasked."""
    top = jnp.max(jnp.where(mask, s, -jnp.inf), axis=-1, keepdims=True)
    top = jax.lax.stop_gradient(jnp.where(jnp.isfinite(top), top, 0.0))
    e = jnp.where(mask, jnp.exp(jnp.where(mask, s - top, 0.0)), 0.0)
    den = e.sum(-1, keepdims=True)
    return e / jnp.where(den > 0, den, 1.0)


def layer(p, plan_layer, masks, h, mm, last: bool) -> dict:
    """The layer's destination rows per node type from the source
    frontier ``h``; ``masks`` holds each relation's unmasked draws."""
    out = {}
    H = next(iter(p["prior"].values())).shape[0]
    for nt, n in plan_layer.dst:
        ht = h[nt][:n]
        d = ht.shape[-1]
        dh = d // H
        q = mm(ht, p["q"][nt]).reshape(n, H, dh).transpose(1, 0, 2)[..., None]
        scores, values, unmasked = [], [], []
        for et, nd, f, off in plan_layer.edges:
            if et[2] != nt:
                continue
            e, s = ekey(et), et[0]
            rows = h[s][off:off + nd * f]
            k = mm(mm(rows, p["k"][s]).reshape(nd * f, H, dh)
                   .transpose(1, 0, 2), p["w_att"][e])    # (H, n f, dh)
            v = mm(mm(rows, p["v"][s]).reshape(nd * f, H, dh)
                   .transpose(1, 0, 2), p["w_msg"][e])
            k = k.reshape(H, nd, f, dh)
            scores.append(mm(k, q)[..., 0] * p["prior"][e][:, None, None]
                          * dh ** -0.5)                   # (H, n, f)
            values.append(v.reshape(H, nd, f, dh))
            unmasked.append(masks[e])
        if scores:
            alpha = _softmax(jnp.concatenate(scores, -1),
                             jnp.concatenate(unmasked, -1)[None])
            m = mm(alpha[:, :, None, :], jnp.concatenate(values, 2))
            m = m[:, :, 0].transpose(1, 0, 2).reshape(n, d)
            upd = mm(jax.nn.gelu(m, approximate=False), p["a"][nt])
        else:
            upd = 0.0
        gate = jax.nn.sigmoid(p["skip"][nt])
        x = gate * upd + (1 - gate) * ht
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        out[nt] = ((x - mu) / jnp.sqrt(var + EPS) * p["norm"][nt]["scale"]
                   + p["norm"][nt]["bias"])
    return out


def layer_flops(plan_layer, hidden: int, gnn: dict):
    """The layer's forward and backward FLOPs, by the least dense
    association of its math (module docstring)."""
    d = hidden
    H = int(gnn["nheads"])
    dh = d // H
    fwd = sum(4.0 * n * d * d for _, n in plan_layer.dst)
    for _, n, f, _ in plan_layer.edges:
        fwd += 4.0 * n * (d * d + d * dh) + 4.0 * n * f * H * d
    return fwd, 2 * fwd
