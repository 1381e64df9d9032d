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
import numpy as np

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
    p = {"w": {}, "a_src": {}, "a_dst": {}}
    for i, (ek, st, dt) in enumerate(etypes):
        p["w"][ek] = _glorot(ks[3 * i], (d_in[st], d_out))
        p["a_src"][ek] = _glorot(ks[3 * i + 1], (nheads, dh))
        p["a_dst"][ek] = _glorot(ks[3 * i + 2], (nheads, dh))
    return p


def _gat_edge(params, em, arrays_l, src_h, lsch, extra_nbr=None):
    nheads = params["a_src"][em.ekey].shape[0]
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
# HGT  [9]  (Hu et al. WWW 2020, arXiv:2003.01332, §3 eqs. 1-5)
# ---------------------------------------------------------------------------
def hgt_init(rng, ntypes, etypes, d_in, d_out, nheads=4):
    """Per node type: ``k``, ``q``, ``v``, ``a`` (d x d), the gate
    ``skip`` (shape (1,)) and LayerNorm ``norm`` ``scale``/``bias``; per
    relation: ``w_att``, ``w_msg`` (H, dh, dh) and ``prior`` (H,).  The
    head count is ``prior.shape[0]``."""
    dh = d_out // nheads
    i = iter(_keys(rng, 4 * len(ntypes) + 2 * len(etypes)))
    p = {name: {} for name in ("k", "q", "v", "a", "skip", "norm",
                               "w_att", "w_msg", "prior")}
    for nt in ntypes:
        for name in ("k", "q", "v"):
            p[name][nt] = _glorot(next(i), (d_in[nt], d_out))
        p["a"][nt] = _glorot(next(i), (d_out, d_out))
        p["skip"][nt] = jnp.ones((1,), jnp.float32)
        p["norm"][nt] = {"scale": jnp.ones((d_out,), jnp.float32),
                         "bias": jnp.zeros((d_out,), jnp.float32)}
    for ek, _, _ in etypes:
        for name in ("w_att", "w_msg"):
            p[name][ek] = jax.random.normal(
                next(i), (nheads, dh, dh), jnp.float32) * dh ** -0.5
        p["prior"][ek] = jnp.ones((nheads,), jnp.float32)
    return p


def _joint_softmax(scores, masks):
    """Attention over every unmasked slot of a destination across all of
    its relations: ``scores`` (n, f_r, H) and ``masks`` (n, f_r), one of
    each per relation into one destination type, give each relation's
    weights (n, f_r, H).  Masked slots, and every slot of a destination
    with none unmasked, weigh 0."""
    att = masked_softmax(jnp.concatenate(scores, axis=1),
                         jnp.concatenate(masks, axis=1)[..., None])
    ends = np.cumsum([x.shape[1] for x in scores])
    return [att[:, e - x.shape[1]:e] for e, x in zip(ends, scores)]


def hgt_attention(params, lsch: LayerSchema, arrays_l, src_h):
    """Each relation's attention weights ``{ekey: (n, f, H)}`` and each
    destination type's message ``{ntype: (n, d)}`` (eqs. 1-4, before
    the GELU)."""
    H = next(iter(params["prior"].values())).shape[0]
    by_dst = {}
    for em in lsch.edges:
        by_dst.setdefault(em.dst_t, []).append(em)
    att, msg = {}, {}
    for nt, ems in by_dst.items():
        n = lsch.dst_count(nt)
        q = (_self_rows(src_h, lsch, nt) @ params["q"][nt]).reshape(n, H, -1)
        dh = q.shape[-1]
        scores, values = [], []
        for em in ems:
            nbr = _nbr_rows(src_h, em)
            k = (nbr @ params["k"][em.src_t]).reshape(n, em.fanout, H, dh)
            v = (nbr @ params["v"][em.src_t]).reshape(n, em.fanout, H, dh)
            k = jnp.einsum("nfhd,hde->nfhe", k, params["w_att"][em.ekey])
            scores.append(jnp.einsum("nfhe,nhe->nfh", k, q)
                          * params["prior"][em.ekey] * dh ** -0.5)
            values.append(jnp.einsum("nfhd,hde->nfhe", v,
                                     params["w_msg"][em.ekey]))
        alphas = _joint_softmax(
            scores, [arrays_l["masks"][em.ekey] for em in ems])
        m = sum(jnp.einsum("nfh,nfhe->nhe", a, v)
                for a, v in zip(alphas, values))
        msg[nt] = m.reshape(n, H * dh)
        att.update({em.ekey: a for em, a in zip(ems, alphas)})
    return att, msg


def _layer_norm(x, p, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def hgt_apply(params, lsch: LayerSchema, arrays_l, src_h):
    """The published layer:

        out_t = LayerNorm_t(s * (GELU(m_t) A_t) + (1 - s) * h_t),
        s = sigmoid(skip_t),

    with ``m_t`` from ``hgt_attention``: per head ``i``, the score of a
    slot (s, r) is ``(k_s,i W^att_r,i . q_t,i) * prior_r,i / sqrt(dh)``
    and one softmax runs over all unmasked slots of ``t`` across every
    relation; ``m_t`` concatenates the heads' sums of ``alpha * (v_s,i
    W^msg_r,i)``.  A destination with no unmasked slot gets ``m_t = 0``.
    Departures: no relative temporal encoding, no dropout, no biases in
    the K/Q/V/A projections; the shared input encoder ReLU(x W + b)
    stands in for pyHGT's tanh adapter."""
    _, msg = hgt_attention(params, lsch, arrays_l, src_h)
    out = {}
    for nt in dict(lsch.dst_counts):
        h = _self_rows(src_h, lsch, nt)
        gate = jax.nn.sigmoid(params["skip"][nt])
        upd = (jax.nn.gelu(msg[nt], approximate=False) @ params["a"][nt]
               if nt in msg else 0.0)
        out[nt] = _layer_norm(gate * upd + (1 - gate) * h,
                              params["norm"][nt])
    return out


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


# layers that end in their own normalization, so no ReLU follows them
# between layers (HGT's LayerNorm, eq. 5)
NORMALIZED = frozenset({"hgt"})

LAYERS = {
    "gcn": (gcn_init, gcn_apply),
    "sage": (sage_init, sage_apply),
    "gat": (gat_init, gat_apply),
    "rgcn": (rgcn_init, rgcn_apply),
    "rgat": (rgat_init, rgat_apply),
    "hgt": (hgt_init, hgt_apply),
    "tgat": (tgat_init, tgat_apply),
}
