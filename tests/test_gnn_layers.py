"""GNN zoo: every layer forward over real sampled blocks; aggregation
properties (permutation invariance, mask correctness)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.sampling import NeighborSampler, fetch_features
from repro.data import make_mag_like, make_temporal_graph
from repro.gnn.aggregate import masked_mean, masked_softmax, masked_sum
from repro.gnn.layers import hgt_apply, hgt_attention
from repro.gnn.model import (GNN_ZOO, gnn_apply_blocks, init_gnn_model,
                             input_encode, model_meta_from_graph)
from repro.gnn.schema import arrays_of, schema_of

HIDDEN = 16


def _mag_batch():
    g = make_mag_like(n_paper=80, n_author=40, n_inst=8, n_field=4, seed=0)
    sampler = NeighborSampler(g, [3, 3], seed=0)
    mb = sampler.sample({"paper": np.arange(16)})
    feats = fetch_features(g, mb.input_nodes)
    # featureless types get random input features in this test
    rng = np.random.default_rng(0)
    for nt, ids in mb.input_nodes.items():
        if nt not in feats:
            feats[nt] = rng.normal(size=(len(ids), 8)).astype(np.float32)
    return g, mb, feats


@pytest.mark.parametrize("kind", GNN_ZOO)
def test_layer_forward(kind):
    g, mb, feats = _mag_batch()
    extra = {nt: 8 for nt in g.ntypes if not g.has_feat(nt)}
    model = model_meta_from_graph(g, kind, HIDDEN, 2, nheads=4,
                                  extra_feat_dims=extra)
    params = init_gnn_model(jax.random.PRNGKey(0), model)
    schema = schema_of(mb)
    arrays = arrays_of(mb, feats)
    out = gnn_apply_blocks(params, model, schema, arrays)
    assert out["paper"].shape == (16, HIDDEN)
    assert np.isfinite(np.asarray(out["paper"])).all()


def _apply(kind, use_pallas, feats=None):
    g, mb, base = _mag_batch()
    extra = {nt: 8 for nt in g.ntypes if not g.has_feat(nt)}
    model = model_meta_from_graph(g, kind, HIDDEN, 2, nheads=4,
                                  extra_feat_dims=extra,
                                  use_pallas=use_pallas)
    params = init_gnn_model(jax.random.PRNGKey(0), model)
    arrays = arrays_of(mb, base if feats is None else feats)
    return gnn_apply_blocks(params, model, schema_of(mb), arrays)["paper"]


@pytest.mark.parametrize("kind", GNN_ZOO)
def test_pallas_routing_matches_xla(kind):
    """gnn.use_pallas routes the fanout mean/sum through the Pallas
    seg_aggr kernel (interpreted on the CPU); every layer's output is
    the XLA lowering's."""
    np.testing.assert_allclose(np.asarray(_apply(kind, True)),
                               np.asarray(_apply(kind, False)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["gcn", "sage", "rgcn"])
def test_pallas_routing_grad_matches_xla(kind):
    """Input-feature gradients through the routed kernel's VJP match
    the XLA lowering's, for the layers that aggregate by mean or sum."""
    _, _, feats = _mag_batch()
    feats = {nt: jnp.asarray(v) for nt, v in feats.items()}
    w = jnp.asarray(np.random.default_rng(1).normal(size=(16, HIDDEN)),
                    jnp.float32)
    grads = [jax.grad(lambda f: (_apply(kind, up, f) * w).sum())(feats)
             for up in (True, False)]
    for nt in feats:
        np.testing.assert_allclose(np.asarray(grads[0][nt]),
                                   np.asarray(grads[1][nt]),
                                   rtol=1e-5, atol=1e-6)


def test_tgat_uses_time():
    g = make_temporal_graph(n_nodes=60, n_edges=600, seed=0)
    sampler = NeighborSampler(g, [4], seed=0)
    mb = sampler.sample({"user": np.arange(8)})
    feats = fetch_features(g, mb.input_nodes)
    model = model_meta_from_graph(g, "tgat", HIDDEN, 1, nheads=4)
    params = init_gnn_model(jax.random.PRNGKey(0), model)
    schema = schema_of(mb)
    arrays = arrays_of(mb, feats)
    assert arrays["delta_t"][0], "temporal graph must carry delta_t"
    out1 = gnn_apply_blocks(params, model, schema, arrays)
    # zeroing timestamps changes the output (time encoding is active)
    arrays2 = dict(arrays)
    arrays2["delta_t"] = [{k: jnp.zeros_like(v)
                           for k, v in arrays["delta_t"][0].items()}]
    out2 = gnn_apply_blocks(params, model, schema, arrays2)
    assert not np.allclose(np.asarray(out1["user"]), np.asarray(out2["user"]))


def _hgt_layer0(mask_fn=None):
    """HGT's bottom layer over a sampled mag batch: its parameters and
    schema, the masks (``mask_fn`` may replace each) and the encoded
    input rows."""
    g, mb, feats = _mag_batch()
    extra = {nt: 8 for nt in g.ntypes if not g.has_feat(nt)}
    model = model_meta_from_graph(g, "hgt", HIDDEN, 2, nheads=4,
                                  extra_feat_dims=extra)
    params = init_gnn_model(jax.random.PRNGKey(0), model)
    arrays = arrays_of(mb, feats)
    masks = arrays["masks"][0]
    if mask_fn is not None:
        masks = {k: mask_fn(m) for k, m in masks.items()}
    h = input_encode(params, arrays["feats"])
    return params["layers"][0], schema_of(mb).layers[0], {"masks": masks}, h


def test_hgt_attention_is_one_softmax_across_relations():
    """Per destination and head, the weights of all unmasked slots over
    every relation into it sum to 1; masked slots weigh 0."""
    p, lsch, arrays_l, h = _hgt_layer0()
    att, _ = hgt_attention(p, lsch, arrays_l, h)
    dst = [em.dst_t for em in lsch.edges]
    # papers take slots from several relations, so the sum spans them
    assert dst.count("paper") > 1
    for nt in set(dst):
        ems = [em for em in lsch.edges if em.dst_t == nt]
        total = sum(np.asarray(att[em.ekey]).sum(1) for em in ems)
        live = np.any([np.asarray(arrays_l["masks"][em.ekey]).any(1)
                       for em in ems], axis=0)
        assert live.any()
        np.testing.assert_allclose(total[live], 1.0, rtol=1e-5)
        np.testing.assert_allclose(total[~live], 0.0)
        for em in ems:
            a = np.asarray(att[em.ekey])
            m = np.asarray(arrays_l["masks"][em.ekey])
            assert a.shape == m.shape + (4,)
            assert (a[~m] == 0).all()


def test_hgt_destination_without_slots_is_the_gated_residual_norm():
    p, lsch, arrays_l, h = _hgt_layer0(jnp.zeros_like)
    p = dict(p, skip={nt: jnp.full((1,), 0.3) for nt in p["skip"]})
    _, msg = hgt_attention(p, lsch, arrays_l, h)
    out = hgt_apply(p, lsch, arrays_l, h)
    for nt, n in lsch.dst_counts:
        np.testing.assert_allclose(np.asarray(msg[nt]), 0.0)
        off = lsch.self_offset(nt)
        x = (1 - jax.nn.sigmoid(0.3)) * np.asarray(h[nt][off:off + n],
                                                   np.float64)
        mu = x.mean(-1, keepdims=True)
        want = (x - mu) / np.sqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                                  + 1e-5)
        want = want * np.asarray(p["norm"][nt]["scale"]) \
            + np.asarray(p["norm"][nt]["bias"])
        np.testing.assert_allclose(np.asarray(out[nt]), want, rtol=1e-4,
                                   atol=1e-5)


def test_hgt_is_invariant_to_slot_order_within_a_fanout():
    p, lsch, arrays_l, h = _hgt_layer0()
    rng = np.random.default_rng(3)
    h2 = {nt: np.array(v) for nt, v in h.items()}
    masks2 = {}
    for em in lsch.edges:
        perm = rng.permutation(em.fanout)
        a, b = em.src_offset, em.src_offset + em.num_dst * em.fanout
        rows = h2[em.src_t][a:b].reshape(em.num_dst, em.fanout, -1)
        h2[em.src_t][a:b] = rows[:, perm].reshape(b - a, -1)
        masks2[em.ekey] = jnp.asarray(
            np.asarray(arrays_l["masks"][em.ekey])[:, perm])
    want = hgt_apply(p, lsch, arrays_l, h)
    got = hgt_apply(p, lsch, {"masks": masks2},
                    {nt: jnp.asarray(v) for nt, v in h2.items()})
    for nt in want:
        np.testing.assert_allclose(np.asarray(got[nt]),
                                   np.asarray(want[nt]), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("kind", ["rgat", "hgt"])
def test_attention_layer_trains_through_the_gs_runner(kind):
    """The node-classification runner's device step takes two AdamW
    steps of an attention layer with finite losses (no head count in
    the parameter tree for the optimizer to trip on)."""
    from repro.config import GSConfig
    from repro.runner import TASK_REGISTRY, build_graph
    from repro.trainer.epoch_engine import StreamingEpochEngine
    cfg = GSConfig.from_dict({
        "task": "node_classification", "device_features": True,
        "gnn": {"model": kind, "hidden": HIDDEN, "fanout": [2, 2],
                "nheads": 4},
        "hyperparam": {"batch_size": 16, "num_epochs": 1,
                       "sample_on_device": True},
        "input": {"dataset": "mag",
                  "dataset_conf": {"n_paper": 80, "n_author": 40}},
        "node_classification": {}}).resolved()
    runner = TASK_REGISTRY[cfg.task](cfg, build_graph(cfg))
    before = jax.tree_util.tree_map(np.asarray, runner.trainer.params)
    tr_ids, _, _ = runner.data.train_val_test_nodes(
        runner.target_ntype, rng=runner._split_rng())
    engine = StreamingEpochEngine(runner.trainer,
                                  runner._train_loader(tr_ids[:32]))
    engine.run(1)
    losses = engine.step_losses[0]
    assert len(losses) == 2 and np.isfinite(losses).all()
    moved = jax.tree_util.tree_map(
        lambda a, b: not np.allclose(a, np.asarray(b)), before,
        runner.trainer.params)
    assert any(jax.tree_util.tree_leaves(moved))


# ---------------------------------------------------------------------------
@given(st.integers(1, 16), st.integers(1, 8), st.integers(1, 32),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=30, deadline=None)
def test_masked_mean_permutation_invariant(n, f, d, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f, d)).astype(np.float32)
    m = rng.random((n, f)) < 0.6
    perm = rng.permutation(f)
    a = masked_mean(jnp.asarray(x), jnp.asarray(m))
    b = masked_mean(jnp.asarray(x[:, perm]), jnp.asarray(m[:, perm]))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [(16, 4, 8), (130, 7, 96), (64, 10, 256),
                                   (100, 1, 300), (1, 64, 16)])
@pytest.mark.parametrize("fn", [masked_mean, masked_sum],
                         ids=["mean", "sum"])
def test_masked_reduce_matches_float64(shape, fn):
    """The XLA fanout reduction against a float64 numpy sum."""
    n, f, d = shape
    rng = np.random.default_rng(n * f + d)
    x = rng.normal(size=shape).astype(np.float32)
    m = rng.random((n, f)) < 0.7
    want = (x.astype(np.float64) * m[..., None]).sum(axis=1)
    if fn is masked_mean:
        want /= np.maximum(m.sum(axis=1), 1)[:, None]
    np.testing.assert_allclose(np.asarray(fn(jnp.asarray(x), jnp.asarray(m))),
                               want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fn", [masked_mean, masked_sum],
                         ids=["mean", "sum"])
def test_masked_reduce_contracts_at_highest_precision(fn):
    """The fanout sum asks for HIGHEST precision, so a lower default
    matmul precision never rounds it below f32."""
    x = jnp.zeros((4, 3, 8), jnp.float32)
    m = jnp.ones((4, 3), bool)
    dots = [e for e in jax.make_jaxpr(fn)(x, m).jaxpr.eqns
            if e.primitive.name == "dot_general"]
    assert dots and all(
        e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
        for e in dots)


def test_masked_softmax_fully_masked_is_zero():
    s = jnp.asarray(np.random.default_rng(0).normal(size=(4, 6)), jnp.float32)
    m = jnp.zeros((4, 6), bool)
    out = masked_softmax(s, m)
    np.testing.assert_allclose(np.asarray(out), 0.0)


def test_masked_softmax_sums_to_one():
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
    m = jnp.asarray(rng.random((8, 5)) < 0.7)
    out = np.asarray(masked_softmax(s, m))
    rows = np.asarray(m).any(1)
    np.testing.assert_allclose(out[rows].sum(1), 1.0, rtol=1e-5)
    assert (out[~np.asarray(m)] == 0).all()
