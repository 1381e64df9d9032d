"""Topic-assortative heterogeneous graphs at a public dataset's shape.

The configuration's ``graph`` section gives every node type's count,
every relation's published edge count, the feature widths and the label
classes.  Each node gets a topic (one of ``topics``); an edge picks its
source uniformly and, with probability ``p_same``, a destination of the
same topic, else a uniform one.  Relations hold no duplicate pairs, as in
the OGB graphs, so the reverse copy of edge ``i`` is reverse edge ``i``;
each relation's edges come sorted by source.
Labels are the topic; features are unit normals plus ``feat_snr`` on the
topic's coordinate, made on the device in one jitted call.

Host arrays come from ``numpy.random.default_rng(seed)``; the device
features from ``jax.random.PRNGKey(seed)``: one seed gives one graph.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

EType = Tuple[str, str, str]


@dataclasses.dataclass
class GraphData:
    num_nodes: Dict[str, int]
    edges: Dict[EType, Tuple[np.ndarray, np.ndarray]]
    topics: Dict[str, np.ndarray]
    labels: Dict[str, np.ndarray]
    feat_dims: Dict[str, int]
    feat_snr: float
    seed: int


def _group_index(groups: np.ndarray, n_groups: int):
    order = np.argsort(groups, kind="stable")
    starts = np.searchsorted(groups[order], np.arange(n_groups + 1))
    return order, starts


def _draw(rng, n_src, topic_src, dst_index, n_dst, count, p_same):
    order, starts = dst_index
    src = rng.integers(0, n_src, count)
    dst = rng.integers(0, n_dst, count)
    same = rng.random(count) < p_same
    g = topic_src[src[same]]
    lo, size = starts[g], starts[g + 1] - starts[g]
    pick = lo + (rng.random(len(g)) * np.maximum(size, 1)).astype(np.int64)
    ok = size > 0
    rows = np.nonzero(same)[0][ok]
    dst[rows] = order[pick[ok]]
    return src, dst


def _unique_edges(rng, n_src, topic_src, dst_index, n_dst, count, p_same):
    """``count`` distinct (src, dst) pairs, sorted by source."""
    codes = np.zeros(0, np.int64)
    while len(codes) < count:
        need = count - len(codes)
        s, d = _draw(rng, n_src, topic_src, dst_index, n_dst,
                     need + need // 8 + 16, p_same)
        codes = np.unique(np.concatenate([codes, s * np.int64(n_dst) + d]))
    keep = np.ones(len(codes), bool)
    keep[rng.choice(len(codes), len(codes) - count, replace=False)] = False
    codes = codes[keep]
    return codes // n_dst, codes % n_dst


def generate(shape: dict, seed: int) -> GraphData:
    rng = np.random.default_rng(seed)
    num_nodes = {nt: int(n) for nt, n in shape["num_nodes"].items()}
    n_topics = int(shape["topics"])
    topics = {nt: rng.integers(0, n_topics, n).astype(np.int32)
              for nt, n in sorted(num_nodes.items())}
    index = {nt: _group_index(t, n_topics) for nt, t in topics.items()}
    edges: Dict[EType, Tuple[np.ndarray, np.ndarray]] = {}
    for s, r, d, count in shape["relations"]:
        src, dst = _unique_edges(rng, num_nodes[s], topics[s], index[d],
                                 num_nodes[d], int(count),
                                 float(shape["p_same"]))
        edges[(s, r, d)] = (src, dst)
        if shape.get("reverse", True):
            edges[(d, r + "-rev", s)] = (dst.copy(), src.copy())
    return GraphData(
        num_nodes=num_nodes, edges=edges, topics=topics,
        labels={nt: topics[nt] for nt in shape.get("labels", {})},
        feat_dims={nt: int(v) for nt, v in shape["features"].items()},
        feat_snr=float(shape["feat_snr"]), seed=int(seed))


def device_features(g: GraphData) -> dict:
    """Every featured node type's (n, d) float32 table, made on the device
    in one jitted call from the graph's seed."""
    import jax
    import jax.numpy as jnp

    nts = sorted(g.feat_dims)

    def make(key, topics):
        out = {}
        for i, nt in enumerate(nts):
            n, d = g.num_nodes[nt], g.feat_dims[nt]
            x = jax.random.normal(jax.random.fold_in(key, i), (n, d),
                                  jnp.float32)
            out[nt] = x.at[jnp.arange(n), topics[nt] % d].add(g.feat_snr)
        return out

    return jax.jit(make)(jax.random.PRNGKey(g.seed),
                         {nt: jnp.asarray(g.topics[nt]) for nt in nts})
