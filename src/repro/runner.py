"""Task registry + shared run assembly behind `python -m repro.cli.gs`.

One resolved ``GSConfig`` drives the whole pipeline (paper §3.2.1):

  input section  -> graph (built-in synthetic family, or the gconstruct
                    construction pipeline chained in via
                    ``input.gconstruct_conf``)
  gnn section    -> GSgnnModel meta + sparse embedding tables for
                    featureless node types
  task section   -> a registered TaskRunner (node_classification /
                    node_regression / edge_classification /
                    edge_regression / link_prediction / multi_task) that
                    owns loaders, trainer, train loop, checkpointing,
                    and inference

New workloads register with ``@register_task("name")`` and become config
entries — no new CLI.  ``run_config`` is the single programmatic entry
point; the legacy per-task CLIs are thin flag translators on top of it.
"""
from __future__ import annotations

import json
from typing import Dict, Type

import jax
import numpy as np

from repro.checkpoint import (load_multitask_trainer, load_trainer,
                              save_multitask_trainer, save_trainer)
from repro.config import GSConfig, load_config_dict
from repro.core.embedding import SparseEmbedding
from repro.core.feature_store import DeviceFeatureStore
from repro.core.graph import HeteroGraph
from repro.core.sampling import DeviceNeighborSampler
from repro.core.spot_target import exclude_eval_edges, split_edges
from repro.data import (make_amazon_like, make_mag_like, make_scaling_graph,
                        make_temporal_graph)
from repro.gnn.model import model_meta_from_graph
from repro.launch.mesh import make_data_mesh
from repro.trainer import (GSgnnAccEvaluator, GSgnnData,
                           GSgnnEdgeDataLoader, GSgnnEdgeDeviceDataLoader,
                           GSgnnEdgeTrainer,
                           GSgnnLinkPredictionDataLoader,
                           GSgnnLinkPredictionDeviceDataLoader,
                           GSgnnLinkPredictionTrainer, GSgnnMrrEvaluator,
                           GSgnnNodeDataLoader, GSgnnNodeDeviceDataLoader,
                           GSgnnNodeTrainer, GSgnnRegressionEvaluator)
from repro.trainer.multitask import GSgnnMultiTaskTrainer, MultiTaskSpec

TASK_REGISTRY: Dict[str, Type["TaskRunner"]] = {}


def register_task(name: str):
    def deco(cls):
        TASK_REGISTRY[name] = cls
        cls.task_name = name
        return cls
    return deco


# ---------------------------------------------------------------------------
# shared assembly helpers
# ---------------------------------------------------------------------------
_SYNTHETIC = {"mag": make_mag_like, "amazon": make_amazon_like,
              "scaling": make_scaling_graph, "temporal": make_temporal_graph}


def build_graph(cfg: GSConfig) -> HeteroGraph:
    """input section -> HeteroGraph: either a built-in synthetic family or
    a full gconstruct run (transform -> id-map -> partition -> shuffle)."""
    inp = cfg.input
    if inp.gconstruct_conf is not None:
        from repro.gconstruct import construct_graph
        conf = inp.gconstruct_conf
        if isinstance(conf, str):
            conf = load_config_dict(conf)
        graph, _, report = construct_graph(
            conf, num_parts=inp.num_parts, part_method=inp.part_method,
            out_dir=inp.save_graph_path, seed=cfg.hyperparam.seed)
        print(f"gconstruct: nodes={report['num_nodes']} "
              f"edges={report['num_edges']} "
              f"edge_cut={report['edge_cut']:.3f} "
              f"t={report['t_total_s']:.2f}s")
        return graph
    kw = dict(inp.dataset_conf)
    if inp.dataset == "scaling":
        kw.setdefault("n_nodes", 10000)
        kw.setdefault("avg_degree", 20)
    return _SYNTHETIC[inp.dataset](seed=cfg.hyperparam.seed, **kw)


def sparse_embeds_for(graph: HeteroGraph, dim: int,
                      feat_field: str = "feat", seed: int = 0,
                      mesh=None, row_axis: str = None
                      ) -> Dict[str, SparseEmbedding]:
    """One learnable table per featureless node type (§3.3.2) — the single
    construction point for what used to be duplicated `emb_dim = 16`.
    ``seed`` (hyperparam.seed) determines every table's init.  ``mesh``
    places each table on the mesh (rows sharded over ``row_axis``, or
    replicated when it is None) so the data-parallel step can read them."""
    featureless = [nt for nt in graph.ntypes
                   if not graph.has_feat(nt, feat_field)]
    keys = jax.random.split(jax.random.PRNGKey(seed),
                            max(len(featureless), 1))
    return {nt: SparseEmbedding(graph.num_nodes[nt], dim, name=nt, rng=k,
                                mesh=mesh, axis=row_axis)
            for k, nt in zip(keys, featureless)}


def build_model_and_embeds(cfg: GSConfig, graph: HeteroGraph,
                           mesh=None, row_axis: str = None):
    ff = cfg.input.feat_field
    sparse = sparse_embeds_for(graph, cfg.gnn.sparse_embed_dim, ff,
                               seed=cfg.hyperparam.seed,
                               mesh=mesh, row_axis=row_axis)
    model = model_meta_from_graph(
        graph, cfg.gnn.model, hidden=cfg.gnn.hidden,
        num_layers=cfg.gnn.num_layers, nheads=cfg.gnn.nheads,
        extra_feat_dims={nt: cfg.gnn.sparse_embed_dim for nt in sparse},
        feat_field=ff, use_pallas=cfg.gnn.use_pallas)
    return model, sparse


# ---------------------------------------------------------------------------
# task runners
# ---------------------------------------------------------------------------
class TaskRunner:
    """Owns the per-task assembly the two legacy CLIs used to duplicate:
    data facade, model, sparse tables, feature store, loaders, trainer."""

    task_name = "?"

    def __init__(self, cfg: GSConfig, graph: HeteroGraph):
        self.cfg = cfg
        self.graph = graph
        self.data = GSgnnData(graph, label_field=cfg.input.label_field,
                              feat_field=cfg.input.feat_field)
        self.hp = cfg.hyperparam
        # data-parallel mesh (hyperparam.data_parallel): one 1-D ("data",)
        # mesh drives the whole run — batches shard over it, dense params
        # replicate, tables are placed per hyperparam.shard_tables
        self.mesh = make_data_mesh(self.hp.data_parallel) \
            if self.hp.data_parallel != 1 else None
        self._row_axis = "data" if self.hp.shard_tables else None
        row_axis = self._row_axis
        self.model, self.sparse = build_model_and_embeds(
            cfg, graph, mesh=self.mesh, row_axis=row_axis)
        self.store = DeviceFeatureStore(
            graph, feat_field=cfg.input.feat_field,
            mesh=self.mesh, row_axis=row_axis) \
            if cfg.device_features else None
        self.host_features = self.store is None
        # feed mode 3: CSR tables on device, sampling inside the jitted
        # step (validated against the task-program registry: requires
        # device_features + a registered device task program)
        self.device_sampler = self._make_device_sampler(graph)
        # hyperparam.seed determines every host-side stream: splits,
        # shuffling, samplers, negatives, and trainer/embedding init
        self.trainer_rng = jax.random.PRNGKey(self.hp.seed)

    def _make_device_sampler(self, graph):
        """Device CSR tables for feed mode 3, built over the graph the
        task's message passing should see (LP rebuilds on its train
        graph with eval edges excluded)."""
        if not self.hp.sample_on_device:
            return None
        return DeviceNeighborSampler(
            graph, self.cfg.gnn.fanout, seed=self.hp.seed,
            use_pallas=self.cfg.gnn.use_pallas,
            mesh=self.mesh, row_axis=self._row_axis)

    def _split_rng(self):
        """Fresh generator per call so repeated splits (train vs
        inference) reproduce the same partition for one config."""
        return np.random.default_rng(self.hp.seed)

    def _fit_kwargs(self):
        """Streaming-engine knobs for ``trainer.fit`` (docs/pipeline.md
        §3f): the three hyperparam keys, plus a per-epoch atomic
        checkpoint closure when ``output.save_model_path`` is set so
        long runs publish restorable state as they go (the final
        ``save()`` still writes the same path on completion)."""
        kw = {"epoch_chunks": self.hp.epoch_chunks,
              "eval_on_device": self.hp.eval_on_device,
              "async_checkpoint": self.hp.async_checkpoint}
        path = self.cfg.output.save_model_path
        if path:
            cfg_dict = self.cfg.to_dict()
            kw["checkpoint"] = lambda t: save_trainer(t, path,
                                                      config=cfg_dict)
        return kw

    # subclasses implement
    def train(self) -> dict:
        raise NotImplementedError

    def inference(self) -> dict:
        raise NotImplementedError

    def _serve_engine(self, sv):
        """The serving engine a config asks for: one service, or a
        ``ReplicaRouter`` over ``serve.num_replicas`` hash-partitioned
        replicas, always behind an ``AdmissionController`` built from
        the ``serve.*`` admission keys."""
        from repro.serve import (AdmissionController, GSgnnInferenceService,
                                 ReplicaRouter)
        batch = sv.batch_size or self.hp.batch_size
        admission = AdmissionController(
            max_pending_rows=sv.max_pending_rows,
            priorities=sv.priorities)
        if sv.num_replicas > 1:
            return ReplicaRouter.for_trainer(
                self.trainer, sv.num_replicas, batch_size=batch,
                cache_slots=sv.cache_slots,
                max_staleness_steps=sv.max_staleness_steps,
                admission=admission)
        return GSgnnInferenceService(
            self.trainer, batch_size=batch, cache_slots=sv.cache_slots,
            max_staleness_steps=sv.max_staleness_steps,
            admission=admission)

    def serve(self) -> dict:
        """Serve against the (restored) model through the batched
        inference engine (docs/serving.md): with ``serve.port`` set,
        run the asyncio HTTP front end until ``/admin/shutdown``;
        otherwise drain the synthetic seed-request stream.  Returns
        latency percentiles, throughput, and cache/admission counters.
        Every device-capable task serves: node tasks answer with
        logits + embeddings, edge/LP tasks with embeddings.  With
        ``serve.persist_cache`` the embedding cache restores from (and
        snapshots back to) ``<restore_model_path>/serve_cache`` so a
        restarted server comes up warm."""
        import os
        from repro.config import ServeConfig
        from repro.serve import ServeFrontend, request_stream
        sv = self.cfg.serve if self.cfg.serve is not None else ServeConfig()
        engine = self._serve_engine(sv)
        out = {"task": self.task_name, "serve_ntype": engine.ntype,
               "batch_size": engine.batch_size,
               "num_replicas": sv.num_replicas}
        cache_dir = None
        if sv.persist_cache and self.cfg.output.restore_model_path:
            cache_dir = os.path.join(self.cfg.output.restore_model_path,
                                     "serve_cache")
            try:
                out["cache_restored_entries"] = engine.load_cache(cache_dir)
            except ValueError as e:
                # shape mismatch (changed cache_slots / replica count):
                # serve cold rather than load wrong rows
                out["cache_restored_entries"] = 0
                out["cache_restore_note"] = str(e)
        if sv.port is not None:
            front = ServeFrontend(engine, port=sv.port)
            front.start()
            out["url"] = f"http://{front.host}:{front.port}"
            # announce the bound endpoint before blocking so clients
            # (and the CI smoke script) know where to connect
            print(json.dumps({"serving": out["url"]}), flush=True)
            front.wait()
        else:
            reqs = request_stream(
                self.graph.num_nodes[engine.ntype],
                num_requests=sv.requests, request_size=sv.request_size,
                hot_fraction=sv.hot_fraction, hot_set=sv.hot_set,
                seed=self.hp.seed)
            responses = engine.serve(reqs)
            out["row_shapes"] = {
                "emb": list(responses[0]["emb"].shape[1:]),
                "out": list(responses[0]["out"].shape[1:])}
        if cache_dir is not None:
            engine.save_cache(cache_dir)
            out["cache_snapshot_dir"] = cache_dir
        out.update(engine.stats())
        return out

    def restore(self, path: str):
        load_trainer(self.trainer, path)

    def save(self, path: str):
        save_trainer(self.trainer, path, config=self.cfg.to_dict())


@register_task("node_classification")
class NodeClassificationRunner(TaskRunner):
    def __init__(self, cfg, graph):
        super().__init__(cfg, graph)
        nc = cfg.node_classification
        self.target_ntype = nc.target_ntype
        self.trainer = GSgnnNodeTrainer(
            self.model, nc.target_ntype, num_classes=nc.num_classes,
            lr=self.hp.lr, rng=self.trainer_rng, sparse_embeds=self.sparse,
            evaluator=GSgnnAccEvaluator(), feature_store=self.store,
            device_sampler=self.device_sampler, mesh=self.mesh,
            shard_gather=self.hp.shard_gather,
            remote_prefetch=self.hp.remote_prefetch,
            shard_dedup=self.hp.shard_dedup,
            shard_payload_dtype=self.hp.shard_payload_dtype)

    def _loader(self, ids, shuffle=True):
        return GSgnnNodeDataLoader(
            self.data, self.target_ntype, ids, self.cfg.gnn.fanout,
            self.hp.batch_size, shuffle=shuffle, seed=self.hp.seed,
            host_features=self.host_features)

    def _train_loader(self, ids):
        if self.device_sampler is not None:
            return GSgnnNodeDeviceDataLoader(
                self.data, self.target_ntype, ids, self.cfg.gnn.fanout,
                self.hp.batch_size, seed=self.hp.seed,
                sampler=self.device_sampler, mesh=self.mesh)
        return self._loader(ids)

    def train(self) -> dict:
        tr, va, _ = self.data.train_val_test_nodes(self.target_ntype,
                                                   rng=self._split_rng())
        hist = self.trainer.fit(self._train_loader(tr),
                                self._loader(va, False),
                                num_epochs=self.hp.num_epochs, verbose=True,
                                prefetch=self.hp.prefetch,
                                **self._fit_kwargs())
        return {"task": self.task_name, "history": hist}

    def inference(self) -> dict:
        nt = self.target_ntype
        out = {"task": self.task_name}
        if self.cfg.output.save_embed_path:
            loader = self._loader(np.arange(self.graph.num_nodes[nt]), False)
            embs = [np.asarray(self.trainer.embed_batch(b)[nt])
                    for b in loader]
            emb = np.concatenate(embs)[:self.graph.num_nodes[nt]]
            np.save(self.cfg.output.save_embed_path, emb)
            out["embed_shape"] = list(emb.shape)
            out["save_embed_path"] = self.cfg.output.save_embed_path
        _, _, te = self.data.train_val_test_nodes(nt, rng=self._split_rng())
        metric = self.trainer.evaluator.name
        out[metric] = float(self.trainer.evaluate(self._loader(te, False)))
        return out


@register_task("node_regression")
class NodeRegressionRunner(NodeClassificationRunner):
    """Same assembly as node classification with a scalar head and an
    RMSE evaluator; the label field is read as float.  The decoder and
    trainer support existed — this entry makes the task name reachable."""

    def __init__(self, cfg, graph):
        TaskRunner.__init__(self, cfg, graph)
        nr = cfg.node_regression
        self.target_ntype = nr.target_ntype
        self.trainer = GSgnnNodeTrainer(
            self.model, nr.target_ntype, task="node_regression",
            lr=self.hp.lr, rng=self.trainer_rng, sparse_embeds=self.sparse,
            evaluator=GSgnnRegressionEvaluator(), feature_store=self.store,
            device_sampler=self.device_sampler, mesh=self.mesh,
            shard_gather=self.hp.shard_gather,
            remote_prefetch=self.hp.remote_prefetch,
            shard_dedup=self.hp.shard_dedup,
            shard_payload_dtype=self.hp.shard_payload_dtype)


# ---------------------------------------------------------------------------
def _edge_labels(graph: HeteroGraph, etype, label_field, kind: str,
                 node_label_field: str = "label") -> np.ndarray:
    """Per-edge targets: an edge-feature column when ``label_field`` is
    set, else the derived same-label-endpoint indicator (the built-in
    synthetic families carry node labels only)."""
    if label_field is not None:
        col = graph.edge_feats.get(etype, {}).get(label_field)
        if col is None:
            raise ValueError(
                f"edge label_field {label_field!r} not found in "
                f"edge_feats[{etype}]")
        return np.asarray(col)
    src, dst = graph.edges[etype]
    lab_s = graph.node_feats.get(etype[0], {}).get(node_label_field)
    lab_d = graph.node_feats.get(etype[2], {}).get(node_label_field)
    if lab_s is None or lab_d is None:
        raise ValueError(
            f"cannot derive edge labels for {etype}: endpoint node types "
            f"carry no {node_label_field!r} field — set "
            f"edge_*.label_field to an edge label column")
    same = (lab_s[src] == lab_d[dst])
    return (same.astype(np.int64) if kind == "classification"
            else same.astype(np.float32))


class _EdgeTaskRunner(TaskRunner):
    """Shared assembly for edge classification/regression: split the
    target etype's edges, build labeled edge loaders, train/evaluate."""

    kind = "classification"

    def __init__(self, cfg, graph, section, num_classes: int,
                 evaluator):
        super().__init__(cfg, graph)
        self.etype = tuple(section.target_etype)
        self.labels = _edge_labels(graph, self.etype, section.label_field,
                                   self.kind,
                                   node_label_field=cfg.input.label_field)
        self.tr_e, self.va_e, self.te_e = split_edges(self._split_rng(),
                                                      graph, self.etype)
        self.trainer = GSgnnEdgeTrainer(
            self.model, self.etype, num_classes=num_classes,
            task=self.task_name, lr=self.hp.lr, rng=self.trainer_rng,
            sparse_embeds=self.sparse, evaluator=evaluator,
            feature_store=self.store, device_sampler=self.device_sampler,
            mesh=self.mesh,
            shard_gather=self.hp.shard_gather,
            remote_prefetch=self.hp.remote_prefetch,
            shard_dedup=self.hp.shard_dedup,
            shard_payload_dtype=self.hp.shard_payload_dtype)

    def _loader(self, eids, shuffle=True):
        return GSgnnEdgeDataLoader(
            self.data, self.etype, eids, self.cfg.gnn.fanout,
            self.hp.batch_size, labels=self.labels, shuffle=shuffle,
            seed=self.hp.seed, host_features=self.host_features)

    def _train_loader(self, eids):
        if self.device_sampler is not None:
            return GSgnnEdgeDeviceDataLoader(
                self.data, self.etype, eids, self.cfg.gnn.fanout,
                self.hp.batch_size, labels=self.labels, seed=self.hp.seed,
                sampler=self.device_sampler, mesh=self.mesh)
        return self._loader(eids)

    def train(self) -> dict:
        hist = self.trainer.fit(self._train_loader(self.tr_e),
                                self._loader(self.va_e, False),
                                num_epochs=self.hp.num_epochs, verbose=True,
                                prefetch=self.hp.prefetch,
                                **self._fit_kwargs())
        return {"task": self.task_name, "history": hist}

    def inference(self) -> dict:
        metric = self.trainer.evaluator.name
        val = float(self.trainer.evaluate(self._loader(self.te_e, False)))
        return {"task": self.task_name, metric: val}


@register_task("edge_classification")
class EdgeClassificationRunner(_EdgeTaskRunner):
    kind = "classification"

    def __init__(self, cfg, graph):
        ec = cfg.edge_classification
        super().__init__(cfg, graph, ec, ec.num_classes,
                         GSgnnAccEvaluator())


@register_task("edge_regression")
class EdgeRegressionRunner(_EdgeTaskRunner):
    kind = "regression"

    def __init__(self, cfg, graph):
        super().__init__(cfg, graph, cfg.edge_regression, 0,
                         GSgnnRegressionEvaluator())


@register_task("link_prediction")
class LinkPredictionRunner(TaskRunner):
    def __init__(self, cfg, graph):
        super().__init__(cfg, graph)
        lp = cfg.link_prediction
        self.lp = lp
        self.etype = tuple(lp.target_etype)
        self.tr_e, self.va_e, self.te_e = split_edges(self._split_rng(),
                                                      graph, self.etype)
        self.train_graph = exclude_eval_edges(
            graph, self.etype, self.va_e, self.te_e) \
            if lp.exclude_eval_edges else graph
        if self.device_sampler is not None and lp.exclude_eval_edges:
            # the in-jit sampler must not see eval edges either: rebuild
            # the CSR tables over the train graph (the base tables are
            # dropped — a transient double placement at startup)
            self.device_sampler = self._make_device_sampler(self.train_graph)
        # local_joint in a single-partition run degenerates to joint over
        # the full dst node set (a real partition would pass its own set)
        self.local_nodes = np.arange(graph.num_nodes[self.etype[2]]) \
            if lp.neg_method == "local_joint" else None
        self.trainer = GSgnnLinkPredictionTrainer(
            self.model, self.etype, loss=lp.loss, lr=self.hp.lr,
            rng=self.trainer_rng, sparse_embeds=self.sparse,
            evaluator=GSgnnMrrEvaluator(), feature_store=self.store,
            device_sampler=self.device_sampler, mesh=self.mesh,
            shard_gather=self.hp.shard_gather,
            remote_prefetch=self.hp.remote_prefetch,
            shard_dedup=self.hp.shard_dedup,
            shard_payload_dtype=self.hp.shard_payload_dtype,
            neg_method=lp.neg_method, num_negatives=lp.num_negatives,
            local_nodes=self.local_nodes)

    def _loader(self, eids, shuffle=True, restrict=None):
        return GSgnnLinkPredictionDataLoader(
            self.data, self.etype, eids, self.cfg.gnn.fanout,
            self.hp.batch_size, num_negatives=self.lp.num_negatives,
            neg_method=self.lp.neg_method, shuffle=shuffle,
            seed=self.hp.seed, restrict_graph=restrict,
            local_nodes=self.local_nodes,
            host_features=self.host_features)

    def _train_loader(self):
        if self.device_sampler is not None:
            return GSgnnLinkPredictionDeviceDataLoader(
                self.data, self.etype, self.tr_e, self.cfg.gnn.fanout,
                self.hp.batch_size, num_negatives=self.lp.num_negatives,
                neg_method=self.lp.neg_method, seed=self.hp.seed,
                sampler=self.device_sampler,
                restrict_graph=self.train_graph, mesh=self.mesh)
        return self._loader(self.tr_e, restrict=self.train_graph)

    def train(self) -> dict:
        # message passing samples the train graph (eval edges excluded);
        # positives come from the train split of the full edge list
        loader = self._train_loader()
        val_loader = self._loader(self.va_e, shuffle=False)
        hist = self.trainer.fit(loader, val_loader,
                                num_epochs=self.hp.num_epochs, verbose=True,
                                prefetch=self.hp.prefetch,
                                **self._fit_kwargs())
        return {"task": self.task_name, "history": hist}

    def inference(self) -> dict:
        mrr = self.trainer.evaluate(self._loader(self.te_e, shuffle=False))
        return {"task": self.task_name, "mrr": float(mrr)}


@register_task("multi_task")
class MultiTaskRunner(TaskRunner):
    """The multi-task trainer (shared encoder, round-robin heads), reachable
    from config for the first time: each entry of ``multi_task.tasks``
    becomes a MultiTaskSpec with its own trainer/loader/eval split."""

    def __init__(self, cfg, graph):
        super().__init__(cfg, graph)
        specs, self._evals = [], {}
        for t in cfg.multi_task.tasks:
            if t.kind == "node_classification":
                spec, evals = self._build_nc(t)
            else:
                spec, evals = self._build_lp(t)
            specs.append(spec)
            self._evals[t.name] = evals
        self.trainer = GSgnnMultiTaskTrainer(self.model, specs,
                                             sparse_embeds=self.sparse,
                                             rng=self.trainer_rng)

    def _build_nc(self, t):
        nc = t.node_classification
        tr, va, te = self.data.train_val_test_nodes(nc.target_ntype,
                                                    rng=self._split_rng())
        trainer = GSgnnNodeTrainer(
            self.model, nc.target_ntype, num_classes=nc.num_classes,
            lr=self.hp.lr, rng=self.trainer_rng,
            evaluator=GSgnnAccEvaluator(), feature_store=self.store)

        def loader(ids, shuffle=True):
            return GSgnnNodeDataLoader(
                self.data, nc.target_ntype, ids, self.cfg.gnn.fanout,
                self.hp.batch_size, shuffle=shuffle, seed=self.hp.seed,
                host_features=self.host_features)

        spec = MultiTaskSpec(name=t.name, kind=t.kind, trainer=trainer,
                             loader=loader(tr), weight=t.weight)
        return spec, {"metric": "accuracy",
                      "val": loader(va, False), "test": loader(te, False)}

    def _build_lp(self, t):
        lp = t.link_prediction
        etype = tuple(lp.target_etype)
        tr_e, va_e, te_e = split_edges(self._split_rng(), self.graph, etype)
        train_graph = exclude_eval_edges(self.graph, etype, va_e, te_e) \
            if lp.exclude_eval_edges else None
        trainer = GSgnnLinkPredictionTrainer(
            self.model, etype, loss=lp.loss, lr=self.hp.lr,
            rng=self.trainer_rng, evaluator=GSgnnMrrEvaluator(),
            feature_store=self.store)

        def loader(eids, shuffle=True, restrict=None):
            return GSgnnLinkPredictionDataLoader(
                self.data, etype, eids, self.cfg.gnn.fanout,
                self.hp.batch_size, num_negatives=lp.num_negatives,
                neg_method=lp.neg_method, shuffle=shuffle, seed=self.hp.seed,
                restrict_graph=restrict, host_features=self.host_features)

        spec = MultiTaskSpec(name=t.name, kind=t.kind, trainer=trainer,
                             loader=loader(tr_e, restrict=train_graph),
                             weight=t.weight)
        return spec, {"metric": "mrr",
                      "val": loader(va_e, False), "test": loader(te_e, False)}

    def _evaluate(self, split: str) -> dict:
        return {name: {ev["metric"]:
                       float(self.trainer.evaluate(name, ev[split]))}
                for name, ev in self._evals.items()}

    def train(self) -> dict:
        hist = self.trainer.fit(num_epochs=self.hp.num_epochs, verbose=True)
        return {"task": self.task_name, "history": hist,
                "val": self._evaluate("val")}

    def inference(self) -> dict:
        return {"task": self.task_name, "test": self._evaluate("test")}

    def restore(self, path: str):
        load_multitask_trainer(self.trainer, path)

    def save(self, path: str):
        save_multitask_trainer(self.trainer, path,
                               config=self.cfg.to_dict())


# ---------------------------------------------------------------------------
def _serve_ready(cfg: GSConfig) -> GSConfig:
    """Serving always runs the fully-jitted device engine: re-validate
    with sample_on_device/device_features forced on and the mesh
    disabled (serving is single-process here), so an artifact trained on
    the host pipeline serves unchanged — params are feed-mode
    independent.  Tasks without a device program (multi_task) fail the
    capability check with the exact missing feature named."""
    raw = cfg.to_dict()
    hp = raw.setdefault("hyperparam", {})
    hp["sample_on_device"] = True
    hp["data_parallel"] = 1
    hp["shard_tables"] = False
    # an artifact trained with shard_gather: gspmd would fail validation
    # once shard_tables is forced off — the knob is moot without a mesh,
    # as are the wire-format knobs that hang off it
    hp["shard_gather"] = "alltoall"
    hp["shard_dedup"] = False
    hp["shard_payload_dtype"] = "float32"
    raw["device_features"] = True
    return GSConfig.from_dict(raw)


def build_runner(cfg: GSConfig, serve: bool = False) -> TaskRunner:
    """Resolve the config, build the graph, and assemble the registered
    task runner (restored from ``output.restore_model_path`` if set)."""
    if serve:
        cfg = _serve_ready(cfg)
    cfg = cfg.resolved()
    if cfg.task not in TASK_REGISTRY:
        raise KeyError(f"task {cfg.task!r} is not registered; "
                       f"known tasks: {sorted(TASK_REGISTRY)}")
    graph = build_graph(cfg)
    runner = TASK_REGISTRY[cfg.task](cfg, graph)
    if cfg.output.restore_model_path:
        runner.restore(cfg.output.restore_model_path)
    return runner


def run_config(cfg: GSConfig, inference: bool = False,
               serve: bool = False) -> dict:
    """The single programmatic entry point: resolve the config, build the
    graph, dispatch through the registry, train / infer / serve, persist."""
    runner = build_runner(cfg, serve=serve)
    cfg = runner.cfg
    if serve:
        result = runner.serve()
    elif inference:
        result = runner.inference()
    else:
        result = runner.train()
        if cfg.output.save_model_path:
            runner.save(cfg.output.save_model_path)
            result["save_model_path"] = cfg.output.save_model_path
    return result


def run_config_dict(raw: dict, inference: bool = False,
                    serve: bool = False) -> dict:
    return run_config(GSConfig.from_dict(raw), inference=inference,
                      serve=serve)


if __name__ == "__main__":
    import sys
    print(json.dumps(run_config(GSConfig.from_file(sys.argv[1])),
                     default=str))
