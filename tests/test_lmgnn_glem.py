"""GLEM-style EM co-training + perf-knob equivalence tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.embedding import SparseEmbedding
from repro.core.lm_gnn import glem_em
from repro.core.text_encoder import bert_tiny_config
from repro.data import make_mag_like
from repro.gnn.model import model_meta_from_graph
from repro.models.params import init_params
from repro.trainer import (GSgnnAccEvaluator, GSgnnData, GSgnnNodeDataLoader,
                           GSgnnNodeTrainer)


@pytest.mark.slow
def test_glem_em_runs_and_metric_reasonable():
    g = make_mag_like(n_paper=200, n_author=100, n_inst=8, n_field=4, seed=4)
    tokens = g.node_feats["paper"]["text"]
    labels = g.node_feats["paper"]["label"]
    data = GSgnnData(g)
    tr, va, _ = data.train_val_test_nodes("paper")
    cfg = bert_tiny_config(vocab_size=2048 + 1, d_model=32, num_layers=1)
    lm_params = init_params(cfg, jax.random.PRNGKey(0))

    def gnn_train_fn(lm_emb):
        gg = g
        base = gg.node_feats["paper"]["feat"]
        gg.node_feats["paper"] = dict(gg.node_feats["paper"])
        gg.node_feats["paper"]["feat"] = np.concatenate(
            [base, lm_emb], 1).astype(np.float32)
        extra = {nt: 8 for nt in gg.ntypes if not gg.has_feat(nt)}
        model = model_meta_from_graph(gg, "rgcn", 32, 2,
                                      extra_feat_dims=extra)
        sparse = {nt: SparseEmbedding(gg.num_nodes[nt], 8) for nt in extra}
        trainer = GSgnnNodeTrainer(model, "paper", num_classes=8, lr=1e-2,
                                   sparse_embeds=sparse,
                                   evaluator=GSgnnAccEvaluator())
        loader = GSgnnNodeDataLoader(GSgnnData(gg), "paper", tr, [3, 3], 64)
        val = GSgnnNodeDataLoader(GSgnnData(gg), "paper", va, [3, 3], 64,
                                  shuffle=False)
        trainer.fit(loader, val, num_epochs=4)
        # full-graph logits for pseudo-labeling
        all_loader = GSgnnNodeDataLoader(
            GSgnnData(gg), "paper", np.arange(gg.num_nodes["paper"]),
            [3, 3], 64, shuffle=False)
        logits = []
        from repro.gnn.decoders import decoder_apply
        for b in all_loader:
            emb = trainer.embed_batch(b)
            logits.append(np.asarray(decoder_apply(
                trainer.params["dec"], "node_classification", emb,
                target_ntype="paper")))
        logits = np.concatenate(logits)[:gg.num_nodes["paper"]]
        acc = trainer.evaluate(val)
        gg.node_feats["paper"]["feat"] = base
        return logits, acc

    lm_params, history = glem_em(cfg, lm_params, tokens, labels, tr,
                                 num_classes=8, gnn_train_fn=gnn_train_fn,
                                 rounds=2, epochs_lm=1)
    assert len(history) == 2
    assert history[-1] > 0.3  # well above 0.125 chance


def test_perf_knobs_preserve_loss():
    """seq_parallel / shard_activations / vocab_parallel / ce_chunk are
    numerics-preserving (verified on a degenerate (1,1) mesh)."""
    from repro.configs import get_smoke_config
    from repro.launch.specs import concrete_inputs
    from repro.launch.steps import make_loss_fn
    from repro.models.config import InputShape

    cfg = get_smoke_config("granite-3-2b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = concrete_inputs(cfg, InputShape("t", 64, 2, "train"))
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    with mesh:
        base = float(make_loss_fn(cfg)(params, batch)[0])
        for kw in ({"seq_parallel": True}, {"shard_activations": True},
                   {"vocab_parallel_loss": True},
                   {"ce_chunk": 16, "vocab_parallel_loss": True}):
            v = float(make_loss_fn(cfg.replace(**kw))(params, batch)[0])
            np.testing.assert_allclose(v, base, rtol=1e-5), kw
