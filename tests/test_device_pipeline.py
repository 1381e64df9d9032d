"""Device-resident minibatch pipeline: feature store, prefetch, parity.

The contract under test (docs/pipeline.md): training with
``DeviceFeatureStore`` + ``host_features=False`` loaders must be
numerically identical to the host-gather path — only the *location* of the
raw-feature gather moves (host numpy -> in-jit device gather), not the
math — while the per-batch host->device payload drops to index/mask
blocks.
"""
import numpy as np
import pytest

from repro.core.embedding import SparseEmbedding
from repro.core.feature_store import DeviceFeatureStore
from repro.data import make_mag_like
from repro.gnn.model import model_meta_from_graph
from repro.trainer import (GSgnnAccEvaluator, GSgnnData, GSgnnNodeDataLoader,
                           GSgnnNodeTrainer, PrefetchIterator,
                           host_transfer_bytes)


@pytest.fixture(scope="module")
def mag():
    return make_mag_like(n_paper=120, n_author=60, seed=0)


def _trainer(g, store=None):
    extra = {nt: 16 for nt in g.ntypes if not g.has_feat(nt)}
    model = model_meta_from_graph(g, "rgcn", 32, 2, extra_feat_dims=extra)
    sparse = {nt: SparseEmbedding(g.num_nodes[nt], 16) for nt in extra}
    return GSgnnNodeTrainer(model, "paper", num_classes=8, lr=1e-2,
                            sparse_embeds=sparse,
                            evaluator=GSgnnAccEvaluator(),
                            feature_store=store)


def _loader(g, host_features):
    data = GSgnnData(g)
    tr, _, _ = data.train_val_test_nodes("paper")
    return GSgnnNodeDataLoader(data, "paper", tr, [4, 4], 32, shuffle=False,
                               seed=0, host_features=host_features)


def test_device_path_matches_host_path(mag):
    """Same seeds, same schedule: losses must agree to float tolerance."""
    host_tr = _trainer(mag)
    dev_tr = _trainer(mag, store=DeviceFeatureStore(mag))
    host_losses, dev_losses = [], []
    for batch in _loader(mag, host_features=True):
        host_losses.append(host_tr.fit_batch(batch)[0])
    for batch in _loader(mag, host_features=False):
        dev_losses.append(dev_tr.fit_batch(batch)[0])
    np.testing.assert_allclose(host_losses, dev_losses, rtol=1e-4, atol=1e-5)


def test_device_batches_ship_fewer_bytes(mag):
    store = DeviceFeatureStore(mag)
    host_b = next(iter(_loader(mag, host_features=True)))
    dev_b = next(iter(_loader(mag, host_features=False)))
    host_bytes = host_transfer_bytes(host_b)
    dev_bytes = host_transfer_bytes(dev_b, store_ntypes=store.ntypes)
    assert dev_b["arrays"]["feats"] == {}
    assert dev_bytes < host_bytes / 2, (dev_bytes, host_bytes)


def test_device_eval_matches_host_eval(mag):
    """Eval path (eager store gather) parity after identical training."""
    data = GSgnnData(mag)
    _, va, _ = data.train_val_test_nodes("paper")
    host_tr = _trainer(mag)
    dev_tr = _trainer(mag, store=DeviceFeatureStore(mag))
    for batch in _loader(mag, host_features=True):
        host_tr.fit_batch(batch)
    for batch in _loader(mag, host_features=False):
        dev_tr.fit_batch(batch)
    val_host = GSgnnNodeDataLoader(data, "paper", va, [4, 4], 32,
                                   shuffle=False, host_features=True)
    val_dev = GSgnnNodeDataLoader(data, "paper", va, [4, 4], 32,
                                  shuffle=False, host_features=False)
    assert host_tr.evaluate(val_host) == pytest.approx(
        dev_tr.evaluate(val_dev), abs=1e-6)


def test_missing_feature_source_raises_helpfully(mag):
    """host_features=False loaders without a feature_store must fail with
    guidance, not a bare KeyError deep inside the GNN apply."""
    trainer = _trainer(mag, store=None)
    batch = next(iter(_loader(mag, host_features=False)))
    with pytest.raises(ValueError, match="feature_store"):
        trainer.fit_batch(batch)


def test_device_ids_rejects_int32_overflow():
    with pytest.raises(ValueError, match="int32"):
        DeviceFeatureStore.device_ids(np.array([0, 2 ** 31]))


def test_pallas_toggle_layer_parity(mag):
    """rgcn layer output must be identical with the Pallas seg_aggr
    kernel (interpreted on the CPU backend) and the default XLA
    reduce."""
    from repro.gnn import aggregate
    trainer = _trainer(mag, store=DeviceFeatureStore(mag))
    batch = next(iter(_loader(mag, host_features=False)))
    default = np.asarray(trainer.embed_batch(batch)["paper"])
    aggregate.set_use_pallas(True)
    try:
        fused = np.asarray(trainer.embed_batch(batch)["paper"])
    finally:
        aggregate.set_use_pallas(False)
    np.testing.assert_allclose(default, fused, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# feed mode 3: device-resident sampling (sample -> gather -> step in one jit)
# ---------------------------------------------------------------------------
def _device_setup(g, seed=0):
    from repro.core.sampling import DeviceNeighborSampler
    from repro.trainer import GSgnnNodeDeviceDataLoader
    extra = {nt: 16 for nt in g.ntypes if not g.has_feat(nt)}
    model = model_meta_from_graph(g, "rgcn", 32, 2, extra_feat_dims=extra)
    sparse = {nt: SparseEmbedding(g.num_nodes[nt], 16) for nt in extra}
    sampler = DeviceNeighborSampler(g, [4, 4], seed=seed)
    trainer = GSgnnNodeTrainer(model, "paper", num_classes=8, lr=1e-2,
                               sparse_embeds=sparse,
                               evaluator=GSgnnAccEvaluator(),
                               feature_store=DeviceFeatureStore(g),
                               device_sampler=sampler)
    data = GSgnnData(g)
    tr, _, _ = data.train_val_test_nodes("paper")
    loader = GSgnnNodeDeviceDataLoader(data, "paper", tr, [4, 4], 32,
                                       shuffle=False, seed=seed,
                                       sampler=sampler)
    return trainer, loader


def test_device_sampled_batches_ship_only_seed_ids(mag):
    _, loader = _device_setup(mag)
    b = next(iter(loader))
    dev_bytes = host_transfer_bytes(b)
    # int32 seeds + labels + bool mask, nothing else
    expect = (np.asarray(b["seeds"]).nbytes + np.asarray(b["labels"]).nbytes
              + np.asarray(b["seed_mask"]).nbytes)
    assert dev_bytes == expect
    host_b = next(iter(_loader(mag, host_features=False)))
    store = DeviceFeatureStore(mag)
    assert dev_bytes < host_transfer_bytes(
        host_b, store_ntypes=store.ntypes) / 10


def test_device_sampled_fit_converges(mag):
    trainer, loader = _device_setup(mag)
    hist = trainer.fit(loader, num_epochs=4)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_device_sampled_scan_matches_per_batch(mag):
    """The lax.scan epoch and the per-batch jitted step must walk the
    same counter-based sample stream: identical losses."""
    t1, l1 = _device_setup(mag, seed=0)
    per_batch = [t1.fit_batch(b)[0] for b in l1]
    t2, l2 = _device_setup(mag, seed=0)
    hist = t2.fit(l2, num_epochs=1)
    np.testing.assert_allclose(hist[0]["loss"],
                               np.mean(per_batch), rtol=1e-5)
    # params identical after the epoch, both paths
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(t1.params),
                    jax.tree_util.tree_leaves(t2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_device_sampled_one_compile_per_schema(mag):
    """Recompile-count regression guard: a whole multi-epoch device-
    sampled run must hit exactly one XLA compile of the epoch program
    (one BlockSchema -> one jit cache entry)."""
    trainer, loader = _device_setup(mag)
    trainer.fit(loader, num_epochs=3)
    assert len(trainer._steps) == 1
    fns = next(iter(trainer._steps.values()))
    assert fns["epoch"]._cache_size() == 1
    assert fns["step"]._cache_size() == 0  # per-batch path never traced
    # eval path on the same schema must not add device-step entries
    trainer.fit(loader, num_epochs=1)
    assert len(trainer._steps) == 1
    assert fns["epoch"]._cache_size() == 1


@pytest.mark.parametrize("num_rows", [50, 500])  # dense / sorted lowering
def test_in_jit_sparse_adagrad_matches_host_update(num_rows):
    """Both in-jit lowerings must reproduce apply_sparse_grad exactly:
    duplicate ids summed, one adagrad step per unique row, untouched
    rows untouched."""
    import jax.numpy as jnp
    from repro.trainer.trainers import _sparse_adagrad
    rng = np.random.default_rng(0)
    emb = SparseEmbedding(num_rows, 8, lr=0.05)
    ids = np.array([3, 17, 3, 41, 17, 3, 0] * 4)  # duplicates on purpose
    grads = rng.normal(size=(len(ids), 8)).astype(np.float32)
    before_t, before_g = np.asarray(emb.table), np.asarray(emb.gsum)
    table, gsum = _sparse_adagrad(jnp.asarray(before_t),
                                  jnp.asarray(before_g),
                                  jnp.asarray(ids, jnp.int32),
                                  jnp.asarray(grads), emb.lr)
    emb.apply_sparse_grad(ids, jnp.asarray(grads))
    np.testing.assert_allclose(np.asarray(table), np.asarray(emb.table),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(gsum), np.asarray(emb.gsum),
                               rtol=1e-6, atol=1e-7)
    untouched = np.setdiff1d(np.arange(num_rows), ids)
    np.testing.assert_array_equal(np.asarray(table)[untouched],
                                  before_t[untouched])


def test_device_sampler_mismatch_raises(mag):
    """A loader built around a different sampler than the trainer's must
    fail loudly — the step would silently draw the trainer's stream."""
    from repro.core.sampling import DeviceNeighborSampler
    from repro.trainer import GSgnnNodeDeviceDataLoader
    trainer, _ = _device_setup(mag, seed=0)
    data = GSgnnData(mag)
    tr, _, _ = data.train_val_test_nodes("paper")
    other = GSgnnNodeDeviceDataLoader(
        data, "paper", tr, [4, 4], 32, seed=7,
        sampler=DeviceNeighborSampler(mag, [4, 4], seed=7))
    with pytest.raises(ValueError, match="device_sampler"):
        trainer.fit(other, num_epochs=1)
    with pytest.raises(ValueError, match="device_sampler"):
        trainer.fit_batch(next(iter(other)))


def test_device_sampled_eval_uses_host_structured_loader(mag):
    trainer, loader = _device_setup(mag)
    data = GSgnnData(mag)
    _, va, _ = data.train_val_test_nodes("paper")
    trainer.fit(loader, num_epochs=2)
    val = GSgnnNodeDataLoader(data, "paper", va, [4, 4], 32, shuffle=False,
                              host_features=False)
    acc = trainer.evaluate(val)
    assert 0.0 <= acc <= 1.0


# ---------------------------------------------------------------------------
# PrefetchIterator semantics
# ---------------------------------------------------------------------------
def test_prefetch_preserves_order_and_len():
    items = list(range(57))
    out = list(PrefetchIterator(items, depth=3))
    assert out == items
    assert len(PrefetchIterator(items, depth=3)) == len(items)


def test_prefetch_applies_transfer_in_producer():
    out = list(PrefetchIterator(range(10), depth=2, transfer=lambda x: x * 2))
    assert out == [2 * i for i in range(10)]


def test_prefetch_propagates_producer_errors():
    def gen():
        yield 1
        raise RuntimeError("sampler died")

    it = iter(PrefetchIterator(gen(), depth=2))
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="sampler died"):
        list(it)


def test_prefetch_consumer_can_bail_early():
    """Abandoning iteration must not deadlock the producer thread."""
    it = iter(PrefetchIterator(range(10_000), depth=2))
    for _ in range(3):
        next(it)
    it.close()  # generator close -> stop event -> producer exits


def test_prefetch_early_exit_joins_producer():
    """Bailing early joins the sampler thread — no orphaned producer
    keeps drawing batches into the next epoch's iteration."""
    import threading
    it = iter(PrefetchIterator(range(10_000), depth=2))
    next(it)
    it.close()
    assert not any(t.name == "prefetch-sampler" and t.is_alive()
                   for t in threading.enumerate())


def test_prefetch_detects_dead_producer(monkeypatch):
    """A producer that dies without delivering a batch, an error, or the
    end sentinel must raise at the consumer, not hang it forever (the
    never-started thread stands in for a thread killed mid-flight)."""
    import threading
    monkeypatch.setattr(threading.Thread, "start", lambda self: None)
    it = iter(PrefetchIterator(range(5), depth=2))
    with pytest.raises(RuntimeError, match="died"):
        next(it)


def test_prefetch_with_dataloader_matches_sync(mag):
    loader = _loader(mag, host_features=True)
    sync = [b["seeds"] for b in loader]
    pref = [b["seeds"] for b in PrefetchIterator(loader, depth=2)]
    assert len(sync) == len(pref)
    for a, b in zip(sync, pref):
        np.testing.assert_array_equal(a, b)


def test_fit_with_prefetch_converges(mag):
    trainer = _trainer(mag, store=DeviceFeatureStore(mag))
    loader = _loader(mag, host_features=False)
    hist = trainer.fit(loader, num_epochs=3, prefetch=2)
    assert hist[-1]["loss"] < hist[0]["loss"]


# ---------------------------------------------------------------------------
# feed mode 3 for edge tasks and link prediction (task programs)
# ---------------------------------------------------------------------------
def _lp_device_setup(g, neg_method="joint", k=8, seed=0, loss="contrastive"):
    from repro.core.sampling import DeviceNeighborSampler
    from repro.core.spot_target import split_edges
    from repro.trainer import (GSgnnLinkPredictionDeviceDataLoader,
                               GSgnnLinkPredictionTrainer, GSgnnMrrEvaluator)
    etype = ("paper", "cites", "paper")
    extra = {nt: 16 for nt in g.ntypes if not g.has_feat(nt)}
    model = model_meta_from_graph(g, "rgcn", 32, 2, extra_feat_dims=extra)
    sparse = {nt: SparseEmbedding(g.num_nodes[nt], 16) for nt in extra}
    sampler = DeviceNeighborSampler(g, [4, 4], seed=seed)
    local = (np.arange(g.num_nodes["paper"])
             if neg_method == "local_joint" else None)
    trainer = GSgnnLinkPredictionTrainer(
        model, etype, loss=loss, lr=1e-2, sparse_embeds=sparse,
        evaluator=GSgnnMrrEvaluator(),
        feature_store=DeviceFeatureStore(g), device_sampler=sampler,
        neg_method=neg_method, num_negatives=k, local_nodes=local)
    data = GSgnnData(g)
    tr_e, _, _ = split_edges(np.random.default_rng(0), g, etype)
    loader = GSgnnLinkPredictionDeviceDataLoader(
        data, etype, tr_e, [4, 4], 16, num_negatives=k,
        neg_method=neg_method, shuffle=False, seed=seed, sampler=sampler)
    return trainer, loader


@pytest.mark.parametrize("neg_method,k",
                         [("joint", 8), ("uniform", 4),
                          ("in_batch", 8), ("local_joint", 8)])
def test_lp_device_fit_converges_every_neg_method(mag, neg_method, k):
    trainer, loader = _lp_device_setup(mag, neg_method, k)
    hist = trainer.fit(loader, num_epochs=4)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_lp_device_scan_matches_per_batch(mag):
    """The lax.scan epoch and the per-batch jitted step must walk the
    same counter-based sample AND negative streams."""
    import jax
    t1, l1 = _lp_device_setup(mag, "joint", 8, seed=0)
    per_batch = [t1.fit_batch(b)[0] for b in l1]
    t2, l2 = _lp_device_setup(mag, "joint", 8, seed=0)
    hist = t2.fit(l2, num_epochs=1)
    np.testing.assert_allclose(hist[0]["loss"], np.mean(per_batch),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(t1.params),
                    jax.tree_util.tree_leaves(t2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_lp_device_batches_ship_only_endpoints(mag):
    _, loader = _lp_device_setup(mag, "joint", 8)
    b = next(iter(loader))
    # src + dst int32 + bool mask; negatives never cross host->device
    assert set(b["blocks"]) == {"src", "dst", "seed_mask"}
    assert host_transfer_bytes(b) == 16 * 4 + 16 * 4 + 16


def test_lp_device_one_compile_per_schema(mag):
    trainer, loader = _lp_device_setup(mag, "in_batch", 8)
    trainer.fit(loader, num_epochs=3)
    assert len(trainer._steps) == 1
    fns = next(iter(trainer._steps.values()))
    assert fns["epoch"]._cache_size() == 1
    assert fns["step"]._cache_size() == 0


def test_lp_device_loader_trainer_neg_mismatch_raises(mag):
    """A loader sized for different negatives than the trainer's would
    silently train the wrong layout — the plan/program check fails."""
    trainer, _ = _lp_device_setup(mag, "joint", 8)
    _, other_loader = _lp_device_setup(mag, "uniform", 4)
    other_loader.sampler = trainer.device_sampler  # pass the sampler check
    with pytest.raises(ValueError, match="seed layout|sample plan"):
        trainer.fit(other_loader, num_epochs=1)


def _edge_device_setup(g, etype, task="edge_classification", seed=0):
    from repro.core.sampling import DeviceNeighborSampler
    from repro.core.spot_target import split_edges
    from repro.trainer import GSgnnEdgeDeviceDataLoader, GSgnnEdgeTrainer
    extra = {nt: 16 for nt in g.ntypes if not g.has_feat(nt)}
    model = model_meta_from_graph(g, "rgcn", 32, 2, extra_feat_dims=extra)
    sparse = {nt: SparseEmbedding(g.num_nodes[nt], 16) for nt in extra}
    sampler = DeviceNeighborSampler(g, [4, 4], seed=seed)
    trainer = GSgnnEdgeTrainer(
        model, etype, num_classes=2, task=task, lr=1e-2,
        sparse_embeds=sparse, evaluator=GSgnnAccEvaluator(),
        feature_store=DeviceFeatureStore(g), device_sampler=sampler)
    data = GSgnnData(g)
    tr_e, _, _ = split_edges(np.random.default_rng(0), g, etype)
    src, dst = g.edges[etype]
    lab = (g.node_feats["paper"]["label"][dst]
           % 2).astype(np.int64)
    loader = GSgnnEdgeDeviceDataLoader(
        data, etype, tr_e, [4, 4], 16, labels=lab, shuffle=False,
        seed=seed, sampler=sampler)
    return trainer, loader


@pytest.mark.parametrize("etype", [("paper", "cites", "paper"),
                                   ("author", "writes", "paper")])
def test_edge_device_fit_converges(mag, etype):
    """Edge tasks on the device step, same- and cross-ntype endpoints
    (the cross case exercises the multi-role seed layout)."""
    trainer, loader = _edge_device_setup(mag, etype)
    hist = trainer.fit(loader, num_epochs=4)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_edge_device_ships_endpoints_and_labels(mag):
    _, loader = _edge_device_setup(mag, ("paper", "cites", "paper"))
    b = next(iter(loader))
    assert set(b["blocks"]) == {"src", "dst", "labels", "seed_mask"}
    dev_bytes = host_transfer_bytes(b)
    assert dev_bytes == 16 * 4 * 3 + 16
