"""GNN layer zoo over padded MFG blocks.

Every layer implements
    init(rng, ntypes, etypes, d_in: {nt: int}, d_out, nheads) -> params
    apply(params, lsch: LayerSchema, arrays_l, src_h) -> {nt: (n_dst, d_out)}

where ``src_h`` maps ntype -> (src_count, d) hidden rows of the input
frontier, and arrays_l carries the masks (and Δt for temporal graphs).

Zoo (paper §3.1.4): GCN, GAT, GraphSAGE (homogeneous), RGCN, RGAT, HGT
(heterogeneous), TGAT (temporal).  The homogeneous models generalize to
multiple edge types by summing per-etype messages — on a 1-etype graph
they reduce exactly to their published forms.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.gnn.aggregate import masked_mean, masked_softmax, masked_sum
from repro.gnn.schema import LayerSchema


def _nbr_rows(src_h, em):
    h = src_h[em.src_t]
    rows = jax.lax.slice_in_dim(h, em.src_offset,
                                em.src_offset + em.num_dst * em.fanout, axis=0)
    return rows.reshape(em.num_dst, em.fanout, h.shape[-1])


def _agg_fanout(src_h, em, mask, reduce: str):
    """Aggregate an edge block's fanout rows: the sampler lays them out
    contiguously in the frontier (see repro.core.sampling), so they are a
    free static slice, reduced by the masked mean or sum (the Pallas
    ``seg_aggr`` kernel when routed)."""
    nbr = _nbr_rows(src_h, em)
    return (masked_mean if reduce == "mean" else masked_sum)(nbr, mask)


def _self_rows(src_h, lsch: LayerSchema, nt: str):
    off = lsch.self_offset(nt)
    n = lsch.dst_count(nt)
    return jax.lax.slice_in_dim(src_h[nt], off, off + n, axis=0)


def _glorot(key, shape):
    fan = shape[0] + shape[-1]
    s = (2.0 / fan) ** 0.5
    return jax.random.normal(key, shape, jnp.float32) * s


def _keys(rng, n):
    return list(jax.random.split(rng, n))


# ---------------------------------------------------------------------------
# GCN  [13]
# ---------------------------------------------------------------------------
def gcn_init(rng, ntypes, etypes, d_in, d_out, nheads=1):
    ks = _keys(rng, len(etypes) + len(ntypes))
    return {
        "w": {ek: _glorot(k, (d_in[st], d_out))
              for k, (ek, st, dt) in zip(ks, etypes)},
        "b": {nt: jnp.zeros((d_out,), jnp.float32) for nt in ntypes},
    }


def gcn_apply(params, lsch: LayerSchema, arrays_l, src_h):
    out = {}
    for em in lsch.edges:
        mask = arrays_l["masks"][em.ekey]
        # include self in the mean (Â = A + I normalization, fixed-fanout)
        selfh = _self_rows(src_h, lsch, em.dst_t)
        s = _agg_fanout(src_h, em, mask, "sum") + selfh
        cnt = mask.sum(axis=1).astype(s.dtype) + 1.0
        agg = s / cnt[:, None]
        msg = agg @ params["w"][em.ekey]
        out[em.dst_t] = out.get(em.dst_t, 0.0) + msg
    return {nt: v + params["b"][nt] for nt, v in out.items()}


# ---------------------------------------------------------------------------
# GraphSAGE  [8]  (mean aggregator)
# ---------------------------------------------------------------------------
def sage_init(rng, ntypes, etypes, d_in, d_out, nheads=1):
    ks = _keys(rng, len(etypes) + len(ntypes))
    return {
        "w_nbr": {ek: _glorot(k, (d_in[st], d_out))
                  for k, (ek, st, dt) in zip(ks, etypes)},
        "w_self": {nt: _glorot(ks[len(etypes) + i], (d_in[nt], d_out))
                   for i, nt in enumerate(ntypes)},
        "b": {nt: jnp.zeros((d_out,), jnp.float32) for nt in ntypes},
    }


def sage_apply(params, lsch: LayerSchema, arrays_l, src_h):
    out = {}
    for em in lsch.edges:
        agg = _agg_fanout(src_h, em, arrays_l["masks"][em.ekey], "mean")
        out[em.dst_t] = out.get(em.dst_t, 0.0) + agg @ params["w_nbr"][em.ekey]
    res = {}
    for nt, v in out.items():
        selfh = _self_rows(src_h, lsch, nt)
        res[nt] = v + selfh @ params["w_self"][nt] + params["b"][nt]
    return res


# ---------------------------------------------------------------------------
# GAT  [20]  (multi-head additive attention)
# ---------------------------------------------------------------------------
def gat_init(rng, ntypes, etypes, d_in, d_out, nheads=4):
    dh = d_out // nheads
    ks = _keys(rng, 3 * len(etypes))
    p = {"w": {}, "a_src": {}, "a_dst": {}, "nheads": nheads}
    for i, (ek, st, dt) in enumerate(etypes):
        p["w"][ek] = _glorot(ks[3 * i], (d_in[st], d_out))
        p["a_src"][ek] = _glorot(ks[3 * i + 1], (nheads, dh))
        p["a_dst"][ek] = _glorot(ks[3 * i + 2], (nheads, dh))
    return p


def _gat_edge(params, em, arrays_l, src_h, lsch, extra_nbr=None):
    nheads = params["nheads"]
    w = params["w"][em.ekey]
    dh = w.shape[1] // nheads
    nbr = _nbr_rows(src_h, em)
    if extra_nbr is not None:
        nbr = nbr + extra_nbr
    mask = arrays_l["masks"][em.ekey]
    hn = (nbr @ w).reshape(em.num_dst, em.fanout, nheads, dh)
    hd = (_self_rows(src_h, lsch, em.dst_t) @ w).reshape(em.num_dst, nheads, dh)
    sc = jnp.einsum("nfhd,hd->nfh", hn, params["a_src"][em.ekey]) \
        + jnp.einsum("nhd,hd->nh", hd, params["a_dst"][em.ekey])[:, None]
    sc = jax.nn.leaky_relu(sc, 0.2)
    att = masked_softmax(sc.transpose(0, 2, 1).reshape(-1, em.fanout),
                         jnp.repeat(mask, nheads, axis=0))
    att = att.reshape(em.num_dst, nheads, em.fanout).transpose(0, 2, 1)
    return jnp.einsum("nfh,nfhd->nhd", att, hn).reshape(em.num_dst, -1)


def gat_apply(params, lsch: LayerSchema, arrays_l, src_h):
    out = {}
    for em in lsch.edges:
        msg = _gat_edge(params, em, arrays_l, src_h, lsch)
        out[em.dst_t] = out.get(em.dst_t, 0.0) + msg
    return out


# ---------------------------------------------------------------------------
# RGCN  [18]
# ---------------------------------------------------------------------------
def rgcn_init(rng, ntypes, etypes, d_in, d_out, nheads=1):
    ks = _keys(rng, len(etypes) + len(ntypes))
    return {
        "w_rel": {ek: _glorot(k, (d_in[st], d_out))
                  for k, (ek, st, dt) in zip(ks, etypes)},
        "w_self": {nt: _glorot(ks[len(etypes) + i], (d_in[nt], d_out))
                   for i, nt in enumerate(ntypes)},
        "b": {nt: jnp.zeros((d_out,), jnp.float32) for nt in ntypes},
    }


def rgcn_apply(params, lsch: LayerSchema, arrays_l, src_h):
    out = {}
    for em in lsch.edges:
        agg = _agg_fanout(src_h, em, arrays_l["masks"][em.ekey], "mean")
        out[em.dst_t] = out.get(em.dst_t, 0.0) + agg @ params["w_rel"][em.ekey]
    res = {}
    for nt in dict(lsch.dst_counts):
        v = out.get(nt, 0.0)
        selfh = _self_rows(src_h, lsch, nt)
        res[nt] = v + selfh @ params["w_self"][nt] + params["b"][nt]
    return res


# ---------------------------------------------------------------------------
# RGAT  [3]  (per-relation GAT, summed)
# ---------------------------------------------------------------------------
def rgat_init(rng, ntypes, etypes, d_in, d_out, nheads=4):
    p = gat_init(rng, ntypes, etypes, d_in, d_out, nheads)
    k2 = jax.random.split(jax.random.PRNGKey(7), len(ntypes))
    p["w_self"] = {nt: _glorot(k, (d_in[nt], d_out))
                   for k, nt in zip(k2, ntypes)}
    return p


def rgat_apply(params, lsch: LayerSchema, arrays_l, src_h):
    out = gat_apply(params, lsch, arrays_l, src_h)
    res = {}
    for nt in dict(lsch.dst_counts):
        v = out.get(nt, 0.0)
        res[nt] = v + _self_rows(src_h, lsch, nt) @ params["w_self"][nt]
    return res


# ---------------------------------------------------------------------------
# HGT  [9]  (typed Q/K/V projections + per-relation message/attention mats)
# ---------------------------------------------------------------------------
def hgt_init(rng, ntypes, etypes, d_in, d_out, nheads=4):
    dh = d_out // nheads
    nk = 4 * len(ntypes) + 2 * len(etypes)
    ks = _keys(rng, nk)
    i = iter(ks)
    p = {"nheads": nheads,
         "k_proj": {}, "q_proj": {}, "v_proj": {},
         "w_att": {}, "w_msg": {}, "prior": {}, "skip": {}}
    for nt in ntypes:
        p["k_proj"][nt] = _glorot(next(i), (d_in[nt], d_out))
        p["q_proj"][nt] = _glorot(next(i), (d_in[nt], d_out))
        p["v_proj"][nt] = _glorot(next(i), (d_in[nt], d_out))
    for ek, st, dt in etypes:
        p["w_att"][ek] = jnp.stack([jnp.eye(dh)] * nheads)
        p["w_msg"][ek] = jnp.stack([jnp.eye(dh)] * nheads)
        p["prior"][ek] = jnp.ones((nheads,), jnp.float32)
    # typed skip projection
    p["skip"] = {nt: _glorot(next(i), (d_in[nt], d_out)) for nt in ntypes}
    return p


def hgt_apply(params, lsch: LayerSchema, arrays_l, src_h):
    H = params["nheads"]
    out = {}
    for em in lsch.edges:
        w = params["k_proj"][em.src_t]
        d_out = w.shape[1]
        dh = d_out // H
        nbr = _nbr_rows(src_h, em)
        mask = arrays_l["masks"][em.ekey]
        k = (nbr @ w).reshape(em.num_dst, em.fanout, H, dh)
        v = (nbr @ params["v_proj"][em.src_t]).reshape(
            em.num_dst, em.fanout, H, dh)
        q = (_self_rows(src_h, lsch, em.dst_t)
             @ params["q_proj"][em.dst_t]).reshape(em.num_dst, H, dh)
        k = jnp.einsum("nfhd,hde->nfhe", k, params["w_att"][em.ekey])
        v = jnp.einsum("nfhd,hde->nfhe", v, params["w_msg"][em.ekey])
        sc = jnp.einsum("nfhd,nhd->nfh", k, q) * (dh ** -0.5)
        sc = sc * params["prior"][em.ekey][None, None, :]
        att = masked_softmax(sc.transpose(0, 2, 1).reshape(-1, em.fanout),
                             jnp.repeat(mask, H, axis=0))
        att = att.reshape(em.num_dst, H, em.fanout).transpose(0, 2, 1)
        msg = jnp.einsum("nfh,nfhd->nhd", att, v).reshape(em.num_dst, -1)
        out[em.dst_t] = out.get(em.dst_t, 0.0) + msg
    res = {}
    for nt in dict(lsch.dst_counts):
        skip = _self_rows(src_h, lsch, nt) @ params["skip"][nt]
        res[nt] = jax.nn.gelu(out.get(nt, 0.0)) + skip
    return res


# ---------------------------------------------------------------------------
# TGAT  [5]  (GAT + functional time encoding on neighbors)
# ---------------------------------------------------------------------------
def tgat_init(rng, ntypes, etypes, d_in, d_out, nheads=4):
    p = gat_init(rng, ntypes, etypes, d_in, d_out, nheads)
    d_any = max(d_in.values())
    k = jax.random.PRNGKey(23)
    p["time_w"] = jax.random.normal(k, (d_any,), jnp.float32)
    p["time_b"] = jnp.zeros((d_any,), jnp.float32)
    return p


def time_encode(dt, w, b, d):
    """Φ(Δt)_i = cos(w_i Δt + b_i): functional time encoding (Bochner)."""
    return jnp.cos(dt[..., None] * w[:d] + b[:d])


def tgat_apply(params, lsch: LayerSchema, arrays_l, src_h):
    out = {}
    for em in lsch.edges:
        dt = arrays_l.get("delta_t", {}).get(em.ekey)
        extra = None
        if dt is not None:
            d = src_h[em.src_t].shape[-1]
            extra = time_encode(dt, params["time_w"], params["time_b"], d)
        msg = _gat_edge(params, em, arrays_l, src_h, lsch, extra_nbr=extra)
        out[em.dst_t] = out.get(em.dst_t, 0.0) + msg
    return out


LAYERS = {
    "gcn": (gcn_init, gcn_apply),
    "sage": (sage_init, sage_apply),
    "gat": (gat_init, gat_apply),
    "rgcn": (rgcn_init, rgcn_apply),
    "rgat": (rgat_init, rgat_apply),
    "hgt": (hgt_init, hgt_apply),
    "tgat": (tgat_init, tgat_apply),
}
