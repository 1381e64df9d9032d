"""The benchmark's graph generator at a small scale."""
import numpy as np
import pytest

from bench_small import small_cell
from benchmarks.chip import spec


@pytest.mark.parametrize("cell", ["mag-nc.train", "citation2-lp.train"])
def test_relations_have_the_configured_counts(cell):
    c = small_cell(cell, div=500)
    shape = c.config["graph"]
    g = spec.graph_family(shape["family"]).generate(shape, 11)
    assert g.num_nodes == shape["num_nodes"]
    for s, r, d, n in shape["relations"]:
        src, dst = g.edges[(s, r, d)]
        assert len(src) == n
        assert src.max() < g.num_nodes[s] and dst.max() < g.num_nodes[d]
        codes = src * g.num_nodes[d] + dst
        assert len(np.unique(codes)) == n          # no duplicate pairs
        rs, rd = g.edges[(d, r + "-rev", s)]
        np.testing.assert_array_equal(rs, dst)
        np.testing.assert_array_equal(rd, src)
    # relation sizes keep the published ratios
    counts = [n for *_, n in shape["relations"]]
    got = [len(g.edges[tuple(rel[:3])][0]) for rel in shape["relations"]]
    np.testing.assert_allclose(np.array(got) / got[0],
                               np.array(counts) / counts[0])


def test_one_seed_one_graph():
    shape = small_cell("mag-nc.train", div=1000).config["graph"]
    fam = spec.graph_family(shape["family"])
    a, b, c = fam.generate(shape, 5), fam.generate(shape, 5), \
        fam.generate(shape, 6)
    for et in a.edges:
        for x, y in zip(a.edges[et], b.edges[et]):
            np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(a.edges[et][0], c.edges[et][0])
               for et in a.edges)
    fa, fb = fam.device_features(a), fam.device_features(b)
    np.testing.assert_array_equal(np.asarray(fa["paper"]),
                                  np.asarray(fb["paper"]))
    assert fa["paper"].shape == (shape["num_nodes"]["paper"], 128)
    np.testing.assert_array_equal(a.labels["paper"], a.topics["paper"])
    assert a.labels["paper"].max() < shape["labels"]["paper"]


def test_edges_prefer_the_source_topic():
    shape = small_cell("citation2-lp.train", div=200).config["graph"]
    g = spec.graph_family(shape["family"]).generate(shape, 3)
    src, dst = g.edges[("paper", "cites", "paper")]
    t = g.topics["paper"]
    same = (t[src] == t[dst]).mean()
    assert shape["p_same"] - 0.05 < same < shape["p_same"] + 0.05
