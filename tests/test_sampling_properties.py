"""Hypothesis property tests on the sampler / graph invariants."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.graph import CSC, HeteroGraph
from repro.core.sampling import (DeviceNeighborSampler, NeighborSampler,
                                 exclusion_pairs, pad_seeds)
from repro.data import make_mag_like


# ---------------------------------------------------------------------------
# CSC construction
# ---------------------------------------------------------------------------
@given(st.lists(st.tuples(st.integers(0, 19), st.integers(0, 19)),
                min_size=0, max_size=200))
@settings(max_examples=50, deadline=None)
def test_csc_roundtrip(edges):
    src = np.array([e[0] for e in edges], np.int64)
    dst = np.array([e[1] for e in edges], np.int64)
    csc = CSC.from_coo(src, dst, 20)
    # every edge appears exactly once under its dst
    assert csc.indptr[-1] == len(edges)
    for j in range(20):
        nbrs = sorted(csc.indices[csc.indptr[j]:csc.indptr[j + 1]].tolist())
        expect = sorted(src[dst == j].tolist())
        assert nbrs == expect
    # edge_ids are a permutation
    assert sorted(csc.edge_ids.tolist()) == list(range(len(edges)))


# ---------------------------------------------------------------------------
# neighbor sampling invariants
# ---------------------------------------------------------------------------
@given(st.integers(1, 8), st.integers(1, 6), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_sampled_neighbors_are_real_edges(fanout, batch, seed):
    g = make_mag_like(n_paper=50, n_author=30, n_inst=8, n_field=4,
                      avg_cites=3, seed=seed % 100)
    sampler = NeighborSampler(g, [fanout], seed=seed)
    rng = np.random.default_rng(seed)
    seeds = {"paper": rng.integers(0, 50, batch)}
    mb = sampler.sample(seeds)
    edge_sets = {et: set(zip(s.tolist(), d.tolist()))
                 for et, (s, d) in g.edges.items()}
    for blk in mb.blocks:
        for eb in blk.edge_blocks:
            dsts = blk.dst_nodes[eb.etype[2]]
            for i in range(eb.num_dst):
                for f in range(eb.fanout):
                    if eb.mask[i, f]:
                        pair = (int(eb.nbr_global[i, f]), int(dsts[i]))
                        assert pair in edge_sets[eb.etype], (eb.etype, pair)


@given(st.integers(1, 5), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_frontier_offsets_consistent(batch, seed):
    """Self rows sit at offset 0; etype rows at their recorded offsets."""
    g = make_mag_like(n_paper=40, n_author=20, n_inst=8, n_field=4, seed=3)
    sampler = NeighborSampler(g, [3, 3], seed=seed)
    rng = np.random.default_rng(seed)
    seeds = {"paper": rng.integers(0, 40, batch)}
    mb = sampler.sample(seeds)
    for blk in mb.blocks:
        for nt, off in blk.self_offsets.items():
            n = blk.dst_counts[nt]
            np.testing.assert_array_equal(
                blk.src_nodes[nt][off:off + n], blk.dst_nodes[nt])
        for eb in blk.edge_blocks:
            rows = blk.src_nodes[eb.etype[0]][
                eb.src_offset:eb.src_offset + eb.num_dst * eb.fanout]
            np.testing.assert_array_equal(
                rows, eb.nbr_global.reshape(-1))
        # layer l-1 frontier == next block's dst? (checked via chain below)
    # chain: blocks[i].src == blocks[i-1]? blocks are input->output ordered
    for a, b in zip(mb.blocks[:-1], mb.blocks[1:]):
        for nt, ids in b.dst_nodes.items():
            pass  # dst of the LAST block are the seeds:
    for nt, ids in mb.blocks[-1].dst_nodes.items():
        np.testing.assert_array_equal(ids, mb.seeds[nt])


@given(st.integers(2, 64))
@settings(max_examples=20, deadline=None)
def test_pad_seeds(n):
    ids = np.arange(n)
    padded, mask = pad_seeds(ids, 64)
    assert padded.shape == (64,) and mask.sum() == n
    np.testing.assert_array_equal(padded[:n], ids)
    assert not mask[n:].any()


def test_isolated_nodes_fully_masked():
    g = HeteroGraph({"a": 5, "b": 5},
                    {("a", "r", "b"): (np.array([0, 1]), np.array([0, 1]))})
    sampler = NeighborSampler(g, [4], seed=0)
    mb = sampler.sample({"b": np.array([0, 1, 4])})  # node 4 isolated
    eb = mb.blocks[0].edge_blocks[0]
    assert eb.mask[0].all() and eb.mask[1].all()
    assert not eb.mask[2].any()


def test_exclude_pairs_masks_target_edges():
    src = np.array([0, 1, 2, 3])
    dst = np.array([0, 0, 0, 0])
    g = HeteroGraph({"a": 5, "b": 1}, {("a", "r", "b"): (src, dst)})
    sampler = NeighborSampler(g, [16], seed=0)
    mb = sampler.sample({"b": np.array([0])},
                        exclude_pairs={("a", "r", "b"): {(0, 0), (1, 0)}})
    eb = mb.blocks[0].edge_blocks[0]
    hit = eb.nbr_global[eb.mask]
    assert not np.isin(hit, [0, 1]).any()  # excluded srcs never pass mask


# ---------------------------------------------------------------------------
# device sampler parity vs the host sampler (same layout, same semantics;
# only the random stream differs)
# ---------------------------------------------------------------------------
def _dev_sample(sampler, plan, seeds, step=0, exclude=None):
    import jax.numpy as jnp
    seeds = {nt: jnp.asarray(ids, jnp.int32) for nt, ids in seeds.items()}
    masks, dts, frontier = sampler.sample(sampler.tables, plan, seeds,
                                          jnp.int32(step), exclude=exclude)
    return ([{k: np.asarray(v) for k, v in m.items()} for m in masks],
            {nt: np.asarray(v) for nt, v in frontier.items()})


@given(st.integers(1, 8), st.integers(2, 6), st.integers(0, 99))
@settings(max_examples=10, deadline=None)
def test_device_schema_matches_host(fanout, batch, gseed):
    """Self-row offsets, frontier sizes, edge offsets: the device plan's
    BlockSchema must equal the host sampler's for the same seed counts."""
    from repro.gnn.schema import schema_of, schema_of_plan
    g = make_mag_like(n_paper=50, n_author=30, n_inst=8, n_field=4,
                      avg_cites=3, seed=gseed)
    host = NeighborSampler(g, [fanout, fanout], seed=0)
    ids, _ = pad_seeds(np.arange(batch), batch)
    mb = host.sample({"paper": ids})
    dev = DeviceNeighborSampler(g, [fanout, fanout], seed=0)
    plan = dev.plan_for({"paper": batch})
    assert schema_of_plan(plan) == schema_of(mb)


def test_device_zero_degree_rows_fully_masked():
    """Isolated seeds get all-false mask rows at the exact same positions
    as the host sampler; connected rows are all-true (with replacement)."""
    g = HeteroGraph({"a": 5, "b": 5},
                    {("a", "r", "b"): (np.array([0, 1]), np.array([0, 1]))})
    host = NeighborSampler(g, [4], seed=0)
    seeds = np.array([0, 1, 4])  # node 4 isolated
    mb = host.sample({"b": seeds})
    dev = DeviceNeighborSampler(g, [4], seed=0)
    plan = dev.plan_for({"b": 3})
    masks, _ = _dev_sample(dev, plan, {"b": seeds})
    hm = mb.blocks[0].edge_blocks[0].mask
    np.testing.assert_array_equal(masks[0]["a___r___b"], hm)
    assert not masks[0]["a___r___b"][2].any()


def test_device_sampled_neighbors_are_real_edges():
    """Decode the frontier through the plan's offsets: every unmasked
    draw must be an existing (src, dst) edge, and padded layout must put
    each edge block's rows at its recorded src_offset."""
    g = make_mag_like(n_paper=50, n_author=30, n_inst=8, n_field=4,
                      avg_cites=3, seed=7)
    dev = DeviceNeighborSampler(g, [5], seed=3)
    seeds = np.arange(8)
    plan = dev.plan_for({"paper": 8})
    masks, frontier = _dev_sample(dev, plan, {"paper": seeds}, step=11)
    edge_sets = {et: set(zip(s.tolist(), d.tolist()))
                 for et, (s, d) in g.edges.items()}
    for pe in plan.layers[0].edges:
        ek = "___".join(pe.etype)
        rows = frontier[pe.etype[0]][
            pe.src_offset:pe.src_offset + pe.num_dst * pe.fanout]
        nbr = rows.reshape(pe.num_dst, pe.fanout)
        m = masks[0][ek]
        for i in range(pe.num_dst):
            for f in range(pe.fanout):
                if m[i, f]:
                    assert (int(nbr[i, f]), int(seeds[i])) \
                        in edge_sets[pe.etype]


def test_device_exclusion_masks_target_edges():
    """SpotTarget parity: excluded (src, dst) codes never survive the
    device sampler's mask."""
    src = np.array([0, 1, 2, 3])
    dst = np.array([0, 0, 0, 0])
    g = HeteroGraph({"a": 5, "b": 1}, {("a", "r", "b"): (src, dst)})
    dev = DeviceNeighborSampler(g, [16], seed=0)
    plan = dev.plan_for({"b": 1})
    import jax.numpy as jnp
    ex = tuple(jnp.asarray(a) for a in exclusion_pairs(
        np.array([0, 1]), np.array([0, 0]), pad_to=4))
    for step in range(5):
        masks, frontier = _dev_sample(dev, plan, {"b": np.array([0])},
                                      step=step,
                                      exclude={("a", "r", "b"): ex})
        pe = plan.layers[0].edges[0]
        nbr = frontier["a"][pe.src_offset:pe.src_offset + 16]
        hit = nbr[masks[0]["a___r___b"][0]]
        assert not np.isin(hit, [0, 1]).any()
        assert masks[0]["a___r___b"].any()  # srcs 2, 3 still sampled


def test_device_sampler_unbiased_marginals():
    """Per-neighbor marginal frequency over many counter steps must be
    uniform over the dst's CSR segment (with-replacement draw)."""
    deg = 5
    g = HeteroGraph({"a": deg, "b": 1},
                    {("a", "r", "b"): (np.arange(deg),
                                       np.zeros(deg, np.int64))})
    dev = DeviceNeighborSampler(g, [4], seed=0)
    plan = dev.plan_for({"b": 64})
    counts = np.zeros(deg)
    steps = 12
    for step in range(steps):
        _, frontier = _dev_sample(dev, plan,
                                  {"b": np.zeros(64, np.int64)}, step=step)
        pe = plan.layers[0].edges[0]
        nbr = frontier["a"][pe.src_offset:pe.src_offset + 64 * 4]
        counts += np.bincount(nbr, minlength=deg)
    freq = counts / counts.sum()
    np.testing.assert_allclose(freq, 1.0 / deg, atol=0.04)


def test_device_sampler_stream_is_counter_based():
    """One config seed fully determines the stream: same (seed, step) ->
    identical draws; different steps or seeds -> different draws."""
    g = make_mag_like(n_paper=50, n_author=30, n_inst=8, n_field=4, seed=1)
    seeds = np.arange(16)
    dev = DeviceNeighborSampler(g, [4, 4], seed=5)
    plan = dev.plan_for({"paper": 16})
    _, f0 = _dev_sample(dev, plan, {"paper": seeds}, step=0)
    _, f0b = _dev_sample(dev, plan, {"paper": seeds}, step=0)
    _, f1 = _dev_sample(dev, plan, {"paper": seeds}, step=1)
    for nt in f0:
        np.testing.assert_array_equal(f0[nt], f0b[nt])
    assert any((f0[nt] != f1[nt]).any() for nt in f0)
    dev2 = DeviceNeighborSampler(g, [4, 4], seed=6)
    _, g0 = _dev_sample(dev2, dev2.plan_for({"paper": 16}),
                        {"paper": seeds}, step=0)
    assert any((f0[nt] != g0[nt]).any() for nt in f0)


@pytest.mark.parametrize("n,f,e,v,kind", [
    (40, 3, 9, 25, "random"),
    (200, 5, 64, 50, "random"),
    (64, 4, 1, 10, "random"),
    (32, 3, 0, 10, "random"),          # no target pairs: all false
    (48, 4, 16, 10, "padding"),        # a list of -1 pads alone
    (16, 4, 1024, 40, "random"),       # a whole batch of pairs
    (24, 5, 12, 30, "members"),        # every query is a target pair
    (4, 2, 46341, 20, "random"),       # e * (e + 2) >= 2**31
])
def test_pair_exclusion_hit_matches_dense_compare(n, f, e, v, kind):
    """The in-jit SpotTarget membership test agrees exactly with a
    numpy broadcast compare, including -1 pads and duplicate pairs."""
    import jax.numpy as jnp
    from repro.core.sampling import _pair_exclusion_hit
    rng = np.random.default_rng(7 + n + e)
    nbr = rng.integers(0, v, (n, f)).astype(np.int32)
    dst = rng.integers(0, v, n).astype(np.int32)
    ex_s = rng.integers(0, v, e).astype(np.int32)
    ex_d = rng.integers(0, v, e).astype(np.int32)
    if kind == "padding":
        ex_s[:] = -1
        ex_d[:] = -1
    elif kind == "members":
        # every sampled pair, shuffled in among e random ones
        perm = rng.permutation(e + n * f)
        ex_s = np.concatenate([ex_s, nbr.reshape(-1)])[perm]
        ex_d = np.concatenate([ex_d, np.repeat(dst, f)])[perm]
    elif e > 4:
        ex_s[-2:] = -1
        ex_d[-2:] = -1                    # padding convention
        ex_s[0], ex_d[0] = ex_s[1], ex_d[1]   # duplicate pair
    dense = ((nbr[:, :, None] == ex_s[None, None, :])
             & (dst[:, None, None] == ex_d[None, None, :])).any(-1)
    if kind == "members":
        assert dense.all()
    fast = np.asarray(_pair_exclusion_hit(
        jnp.asarray(nbr), jnp.asarray(dst), jnp.asarray(ex_s),
        jnp.asarray(ex_d)))
    assert fast.shape == (n, f) and fast.dtype == np.bool_
    np.testing.assert_array_equal(fast, dense)
