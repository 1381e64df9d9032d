"""Typed declarative run configuration (paper §3.2.1).

GraphStorm's headline ease-of-use property is that one YAML file drives
graph construction, training, and inference.  ``GSConfig`` is that file,
typed: a dataclass hierarchy with ``gnn``, ``hyperparam``, ``input``,
``output``, and per-task sections, loaded from YAML or JSON with

  - strict unknown-key rejection (typos fail loudly, with a suggestion),
  - per-field type coercion and defaults,
  - cross-field validation (fanout length vs. num_layers, negative-sampling
    divisibility, task section presence, ...),
  - dotted-path CLI overrides (``--gnn.hidden 128``).

The resolved config serializes back to a plain dict (``to_dict``) so every
checkpoint can carry the exact configuration that produced it; loading that
dict yields an identical ``GSConfig`` (round-trip tested).
"""
from __future__ import annotations

import dataclasses
import difflib
import json
from typing import Any, Dict, List, Optional, Tuple

# Built-in synthetic dataset families and their default prediction targets:
# dataset -> (target ntype, target etype, num classes).  The single source
# of truth for what `input.dataset: mag` means; the legacy CLIs import it
# from here via repro.cli.common.
DATASET_TARGETS = {
    "mag": ("paper", ("paper", "cites", "paper"), 8),
    "amazon": ("item", ("item", "also_buy", "item"), 32),
    "scaling": ("node", ("node", "edge", "node"), 16),
    "temporal": ("user", ("user", "interacts", "user"), 4),
}

TASK_KINDS = ("node_classification", "node_regression",
              "edge_classification", "edge_regression",
              "link_prediction", "multi_task")
MODEL_KINDS = ("gcn", "sage", "gat", "rgcn", "rgat", "hgt", "tgat")
# valid negative-sampling methods mirror core/negative_sampling's
# SAMPLERS registry (host draw functions; every entry also has a device
# twin) — kept as a literal because this module must stay importable
# without pulling in jax (dp tools set XLA_FLAGS before the first jax
# import); tests pin NEG_METHODS == set(SAMPLERS) so they cannot drift
NEG_METHODS = ("uniform", "joint", "local_joint", "in_batch")
LP_LOSSES = ("contrastive", "cross_entropy")
PART_METHODS = ("random", "ldg", "metis")


class ConfigError(ValueError):
    """A configuration problem, with the dotted path of the offending key."""


def _err(path: str, msg: str) -> ConfigError:
    where = f"config key '{path}'" if path else "config"
    return ConfigError(f"{where}: {msg}")


# ---------------------------------------------------------------------------
# generic dict <-> dataclass machinery
# ---------------------------------------------------------------------------
def _coerce(value, field: dataclasses.Field, path: str):
    """Coerce a raw YAML/JSON value to the field's declared type."""
    kind = field.metadata.get("kind", "raw")
    if value is None:
        if field.metadata.get("optional", False):
            return None
        raise _err(path, "must not be null")
    if kind == "int":
        if isinstance(value, bool) or not isinstance(value, int):
            raise _err(path, f"expected an integer, got {value!r}")
        return value
    if kind == "float":
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            raise _err(path, f"expected a number, got {value!r}")
        return float(value)
    if kind == "bool":
        if not isinstance(value, bool):
            raise _err(path, f"expected true/false, got {value!r}")
        return value
    if kind == "str":
        if not isinstance(value, str):
            raise _err(path, f"expected a string, got {value!r}")
        choices = field.metadata.get("choices")
        if choices and value not in choices:
            raise _err(path, f"{value!r} is not one of {list(choices)}")
        return value
    if kind == "int_list":
        if not isinstance(value, (list, tuple)) or not value or \
                any(isinstance(v, bool) or not isinstance(v, int)
                    for v in value):
            raise _err(path, f"expected a non-empty list of integers, "
                             f"got {value!r}")
        return list(value)
    if kind == "etype":
        if not isinstance(value, (list, tuple)) or len(value) != 3 or \
                any(not isinstance(v, str) for v in value):
            raise _err(path, "expected a 3-item [src_type, relation, "
                             f"dst_type] edge type, got {value!r}")
        return tuple(value)
    if kind == "dict":
        if not isinstance(value, dict):
            raise _err(path, f"expected a mapping, got {value!r}")
        return dict(value)
    if kind == "section":
        return _from_dict(field.metadata["cls"], value, path)
    if kind == "section_list":
        if not isinstance(value, (list, tuple)):
            raise _err(path, f"expected a list, got {value!r}")
        return [_from_dict(field.metadata["cls"], v, f"{path}[{i}]")
                for i, v in enumerate(value)]
    return value


def _from_dict(cls, d, path: str = ""):
    if not isinstance(d, dict):
        raise _err(path or cls.__name__, f"expected a mapping, got {d!r}")
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(d) - set(fields)
    if unknown:
        key = sorted(unknown)[0]
        hint = difflib.get_close_matches(key, fields, n=1)
        hint_s = f" (did you mean {hint[0]!r}?)" if hint else ""
        raise _err(f"{path}.{key}" if path else key,
                   f"unknown key in section "
                   f"'{path or 'top level'}'{hint_s}; valid keys: "
                   f"{sorted(fields)}")
    kw = {}
    for name, f in fields.items():
        if name in d:
            kw[name] = _coerce(d[name], f,
                               f"{path}.{name}" if path else name)
        elif f.default is dataclasses.MISSING and \
                f.default_factory is dataclasses.MISSING:
            raise _err(f"{path}.{name}" if path else name,
                       f"required key missing from section "
                       f"'{path or 'top level'}'")
    return cls(**kw)


def _to_plain(obj):
    if dataclasses.is_dataclass(obj):
        return {f.name: _to_plain(getattr(obj, f.name))
                for f in dataclasses.fields(obj)
                if getattr(obj, f.name) is not None}
    if isinstance(obj, dict):
        return {k: _to_plain(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_to_plain(v) for v in obj]
    return obj


def _field(kind: str, default=dataclasses.MISSING, *, optional=False,
           choices=None, cls=None, default_factory=dataclasses.MISSING):
    md: Dict[str, Any] = {"kind": kind, "optional": optional}
    if choices:
        md["choices"] = choices
    if cls is not None:
        md["cls"] = cls
    return dataclasses.field(default=default, default_factory=default_factory,
                             metadata=md)


# ---------------------------------------------------------------------------
# sections
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class GnnConfig:
    """Encoder architecture."""
    model: str = _field("str", "rgcn", choices=MODEL_KINDS)
    hidden: int = _field("int", 64)
    num_layers: int = _field("int", 2)
    fanout: List[int] = _field("int_list", default_factory=lambda: [8, 8])
    nheads: int = _field("int", 4)
    # embedding dim for featureless node types (learnable sparse tables);
    # previously hardcoded to 16 in each CLI
    sparse_embed_dim: int = _field("int", 16)
    # Pallas kernel routing (replaces the old set_use_pallas global):
    # route aggregation/sampling hot loops through the Pallas kernels,
    # compiled on a TPU backend and interpreted on the CPU
    use_pallas: bool = _field("bool", False)


@dataclasses.dataclass
class HyperparamConfig:
    lr: float = _field("float", 1e-2)
    batch_size: int = _field("int", 256)
    num_epochs: int = _field("int", 5)
    seed: int = _field("int", 0)
    # double-buffer depth for the sampler thread (0 = synchronous)
    prefetch: int = _field("int", 2)
    # feed mode 3 (docs/pipeline.md): neighbor sampling runs inside the
    # jitted step against device-resident CSR tables; batches ship only
    # int32 seed ids + labels, epochs run under lax.scan.  Requires
    # device_features: true so raw-featured ntypes are store-served.
    sample_on_device: bool = _field("bool", False)
    # data-parallel shards over a 1-D ("data",) mesh: 1 = single device
    # (no mesh), N = exactly N devices, 0 = every attached device (the
    # paper's "scale without changing code" default).  Each padded batch
    # is sharded over the mesh; gradients mean-all-reduce; requires
    # sample_on_device (the fully-jitted path is the one that scales).
    data_parallel: int = _field("int", 1)
    # table layout under data_parallel: false replicates feature / CSR /
    # sparse-embedding tables on every shard (fastest while they fit);
    # true row-shards them over the data axis (memory scales with
    # devices; gathers become explicit row exchanges, see shard_gather)
    shard_tables: bool = _field("bool", False)
    # gather lowering for row-sharded tables: "alltoall" (default) routes
    # exactly the requested rows between shards through a ragged
    # all-to-all exchange inside shard_map; "gspmd" keeps the legacy
    # sharding-annotated-jit lowering (GSPMD inserts blanket collectives)
    shard_gather: str = _field("str", "alltoall",
                               choices=("alltoall", "gspmd"))
    # remote-row prefetch depth for the alltoall path: 1 (default)
    # issues batch k+1's row exchanges while batch k's model compute
    # runs in the epoch scan (double-buffered remote rows on device);
    # 0 disables the pipeline (each step exchanges synchronously)
    remote_prefetch: int = _field("int", 1)
    # frontier dedup for the alltoall exchanges: collapse duplicate row
    # requests per shard to one wire slot before routing (static 3/4
    # capacity; overflow falls back to the plain exchange in-jit and
    # narrow wire rows skip the compaction statically, so results are
    # always bit-identical — docs/pipeline.md §3e)
    shard_dedup: bool = _field("bool", False)
    # wire dtype for gathered float payloads on the alltoall path:
    # "bfloat16" halves feature/embedding exchange bytes (exact per row
    # on the one-owner reduce-scatter; fp32 restored on arrival, grad
    # scatter-back stays fp32)
    shard_payload_dtype: str = _field("str", "float32",
                                      choices=("float32", "bfloat16"))
    # streaming epoch engine (docs/pipeline.md §3f): split the epoch
    # scan into K chunk dispatches so host work (next-epoch staging,
    # checkpoint enqueue, loss fetch) hides behind device compute.
    # Chunking only splits the scan carry — losses are bit-identical
    # to the unchunked scan for any K.  1 = one dispatch per epoch.
    epoch_chunks: int = _field("int", 1)
    # run validation as a jitted device pass (metric numerator /
    # denominator accumulate in-jit) instead of the per-batch host
    # evaluate() loop; the eval dispatch overlaps end-of-epoch host work
    eval_on_device: bool = _field("bool", False)
    # write per-epoch checkpoints on a background thread (atomic
    # publish; the final save always happens and is always synchronous)
    async_checkpoint: bool = _field("bool", False)


@dataclasses.dataclass
class InputConfig:
    """Where the graph comes from: a built-in synthetic family or a
    gconstruct schema (construct-then-train chaining)."""
    dataset: Optional[str] = _field("str", None, optional=True,
                                    choices=tuple(DATASET_TARGETS))
    dataset_conf: Dict[str, Any] = _field("dict", default_factory=dict)
    # path to a gconstruct schema (JSON/YAML) or the inline schema mapping
    gconstruct_conf: Optional[Any] = _field("raw", None, optional=True)
    num_parts: int = _field("int", 1)
    part_method: str = _field("str", "random", choices=PART_METHODS)
    # where gconstruct writes the partitioned graph (optional)
    save_graph_path: Optional[str] = _field("str", None, optional=True)
    label_field: str = _field("str", "label")
    feat_field: str = _field("str", "feat")


@dataclasses.dataclass
class OutputConfig:
    save_model_path: Optional[str] = _field("str", None, optional=True)
    save_embed_path: Optional[str] = _field("str", None, optional=True)
    restore_model_path: Optional[str] = _field("str", None, optional=True)


@dataclasses.dataclass
class NodeClassificationConfig:
    # both default from DATASET_TARGETS when input.dataset is built-in
    target_ntype: Optional[str] = _field("str", None, optional=True)
    num_classes: Optional[int] = _field("int", None, optional=True)


@dataclasses.dataclass
class NodeRegressionConfig:
    # defaults from DATASET_TARGETS when input.dataset is built-in; the
    # regression target is input.label_field read as float
    target_ntype: Optional[str] = _field("str", None, optional=True)


@dataclasses.dataclass
class EdgeClassificationConfig:
    """Edge classification: predict a class of a (src, rel, dst) edge.

    ``label_field`` names an edge-feature column holding per-edge class
    ids; when unset (the built-in synthetic families carry no edge
    labels) the runner derives a 2-class target — "do the endpoints
    share a node label?" — so the task trains with real signal."""
    target_etype: Optional[Tuple[str, str, str]] = \
        _field("etype", None, optional=True)
    num_classes: Optional[int] = _field("int", None, optional=True)
    label_field: Optional[str] = _field("str", None, optional=True)


@dataclasses.dataclass
class EdgeRegressionConfig:
    """Edge regression: same wiring as edge classification with a float
    target (``label_field`` edge column, or the derived same-label
    indicator as a float when unset)."""
    target_etype: Optional[Tuple[str, str, str]] = \
        _field("etype", None, optional=True)
    label_field: Optional[str] = _field("str", None, optional=True)


@dataclasses.dataclass
class LinkPredictionConfig:
    target_etype: Optional[Tuple[str, str, str]] = \
        _field("etype", None, optional=True)
    loss: str = _field("str", "contrastive", choices=LP_LOSSES)
    neg_method: str = _field("str", "joint", choices=NEG_METHODS)
    # GraphStorm-compatible alias of neg_method (GraphStorm YAML calls
    # the key train_negative_sampler); when set it must name a method in
    # the sampler registry and overrides neg_method at resolve time
    train_negative_sampler: Optional[str] = \
        _field("str", None, optional=True, choices=NEG_METHODS)
    num_negatives: int = _field("int", 32)
    # SpotTarget leakage control: remove val/test edges from the message
    # graph during training
    exclude_eval_edges: bool = _field("bool", True)

    @property
    def effective_neg_method(self) -> str:
        return self.train_negative_sampler or self.neg_method


@dataclasses.dataclass
class ServeConfig:
    """Batched inference serving (``gs --serve``, docs/serving.md):
    continuous batching into the device program's static batch shape
    plus a device-resident, staleness-bounded embedding cache."""
    # serving batch size (the static program shape); defaults to
    # hyperparam.batch_size
    batch_size: Optional[int] = _field("int", None, optional=True)
    # device-resident LRU cache slots; 0 disables the cache (every
    # batch recomputes — the cold-path / parity-reference behavior)
    cache_slots: int = _field("int", 4096)
    # a cached row older than this many program steps is recomputed
    max_staleness_steps: int = _field("int", 64)
    # service replicas behind the ReplicaRouter; seeds hash-partition
    # across them so each replica caches a disjoint shard of the hot
    # set; cache_slots is the TOTAL budget (split evenly)
    num_replicas: int = _field("int", 1)
    # bind the asyncio HTTP front end here instead of running the
    # synthetic request stream (0 = ephemeral port; unset = no HTTP)
    port: Optional[int] = _field("int", None, optional=True)
    # admission control: hard pending-row budget (0 = unlimited) and
    # per-class budget fractions; declaration order is scheduling order
    # (first class drains first)
    max_pending_rows: int = _field("int", 0)
    priorities: Dict[str, float] = \
        _field("dict", default_factory=lambda: {"high": 1.0, "low": 0.5})
    # snapshot the embedding cache next to the checkpoint on exit and
    # restore it on start, so a restarted server comes up warm
    persist_cache: bool = _field("bool", False)
    # synthetic request stream of the CLI path (see serve.request_stream)
    requests: int = _field("int", 64)
    request_size: int = _field("int", 4)
    hot_fraction: float = _field("float", 0.8)
    hot_set: int = _field("int", 64)


@dataclasses.dataclass
class TaskSpecConfig:
    """One task of a multi-task run: a kind, a loss weight, and the
    matching per-task section."""
    name: str = _field("str")
    kind: str = _field("str",
                       choices=("node_classification", "link_prediction"))
    weight: float = _field("float", 1.0)
    node_classification: Optional[NodeClassificationConfig] = \
        _field("section", None, optional=True, cls=NodeClassificationConfig)
    link_prediction: Optional[LinkPredictionConfig] = \
        _field("section", None, optional=True, cls=LinkPredictionConfig)

    def task_section(self):
        return getattr(self, self.kind)


@dataclasses.dataclass
class MultiTaskConfig:
    tasks: List[TaskSpecConfig] = \
        _field("section_list", cls=TaskSpecConfig,
               default_factory=list)


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class GSConfig:
    task: str = _field("str", choices=TASK_KINDS)
    version: str = _field("str", "gsconfig-v1")
    gnn: GnnConfig = _field("section", cls=GnnConfig,
                            default_factory=GnnConfig)
    hyperparam: HyperparamConfig = _field("section", cls=HyperparamConfig,
                                          default_factory=HyperparamConfig)
    input: InputConfig = _field("section", cls=InputConfig,
                                default_factory=InputConfig)
    output: OutputConfig = _field("section", cls=OutputConfig,
                                  default_factory=OutputConfig)
    node_classification: Optional[NodeClassificationConfig] = \
        _field("section", None, optional=True, cls=NodeClassificationConfig)
    node_regression: Optional[NodeRegressionConfig] = \
        _field("section", None, optional=True, cls=NodeRegressionConfig)
    edge_classification: Optional[EdgeClassificationConfig] = \
        _field("section", None, optional=True, cls=EdgeClassificationConfig)
    edge_regression: Optional[EdgeRegressionConfig] = \
        _field("section", None, optional=True, cls=EdgeRegressionConfig)
    link_prediction: Optional[LinkPredictionConfig] = \
        _field("section", None, optional=True, cls=LinkPredictionConfig)
    multi_task: Optional[MultiTaskConfig] = \
        _field("section", None, optional=True, cls=MultiTaskConfig)
    serve: Optional[ServeConfig] = \
        _field("section", None, optional=True, cls=ServeConfig)
    # keep feature tables device-resident; batches ship only index blocks
    device_features: bool = _field("bool", False)

    # ------------------------------------------------------------------
    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GSConfig":
        cfg = _from_dict(cls, d)
        cfg.validate()
        return cfg

    @classmethod
    def from_file(cls, path: str,
                  overrides: Optional[List[str]] = None) -> "GSConfig":
        raw = load_config_dict(path)
        if overrides:
            raw = apply_overrides(raw, overrides)
        return cls.from_dict(raw)

    def to_dict(self) -> Dict[str, Any]:
        return _to_plain(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    # ------------------------------------------------------------------
    def validate(self):
        g, h, inp = self.gnn, self.hyperparam, self.input
        if len(g.fanout) != g.num_layers:
            raise _err("gnn.fanout",
                       f"needs one entry per GNN layer: got {g.fanout} "
                       f"for gnn.num_layers={g.num_layers}")
        if any(f <= 0 for f in g.fanout):
            raise _err("gnn.fanout",
                       f"fanouts must be positive, got {g.fanout}")
        for key in ("hidden", "num_layers", "sparse_embed_dim"):
            if getattr(g, key) <= 0:
                raise _err(f"gnn.{key}", "must be positive")
        for key in ("batch_size", "num_epochs"):
            if getattr(h, key) <= 0:
                raise _err(f"hyperparam.{key}", "must be positive")
        if h.lr <= 0:
            raise _err("hyperparam.lr", "must be positive")
        if h.sample_on_device:
            # capability check against the task-program registry: the
            # error names exactly which feature is missing for this
            # (task, options) combination, not a blanket task list
            from repro.trainer.task_programs import device_capability
            lp = self.link_prediction \
                if self.task == "link_prediction" else None
            missing = device_capability(
                self.task,
                neg_method=lp.effective_neg_method if lp else None,
                num_negatives=lp.num_negatives if lp else 0,
                batch_size=h.batch_size, data_parallel=h.data_parallel)
            if missing:
                raise _err("hyperparam.sample_on_device", missing)
            if not self.device_features:
                raise _err("hyperparam.sample_on_device",
                           "requires device_features: true — in-jit "
                           "sampling can only gather raw features from "
                           "device-resident tables")
        if h.data_parallel < 0:
            raise _err("hyperparam.data_parallel",
                       "must be >= 0 (0 = use every attached device)")
        if h.data_parallel != 1:
            # host-sampled feed modes lower through the same streaming
            # epoch engine and dp machinery since they share BlockSchema;
            # only the per-shard batch divisibility contract remains
            if h.data_parallel > 1 and h.batch_size % h.data_parallel != 0:
                raise _err("hyperparam.data_parallel",
                           f"hyperparam.batch_size ({h.batch_size}) must "
                           f"be divisible by data_parallel "
                           f"({h.data_parallel}) — every shard carries an "
                           f"equal slice of the global batch")
        if h.epoch_chunks < 1:
            raise _err("hyperparam.epoch_chunks",
                       "must be >= 1 (1 = one scan dispatch per epoch; "
                       "K > 1 splits the epoch into K chunk dispatches "
                       "so host work overlaps device compute)")
        if h.remote_prefetch not in (0, 1):
            raise _err("hyperparam.remote_prefetch",
                       "must be 0 (synchronous) or 1 (double-buffered "
                       "remote rows — deeper pipelines would need more "
                       "scan-carry buffers than the exchange keeps)")
        if h.shard_gather != "alltoall" and not h.shard_tables:
            raise _err("hyperparam.shard_gather",
                       "only applies with shard_tables: true (replicated "
                       "tables never exchange rows)")
        if h.shard_dedup and not h.shard_tables:
            raise _err("hyperparam.shard_dedup",
                       "only applies with shard_tables: true (replicated "
                       "tables never exchange rows to deduplicate)")
        if h.shard_dedup and h.shard_gather != "alltoall":
            raise _err("hyperparam.shard_dedup",
                       "needs shard_gather: alltoall (the gspmd lowering "
                       "has no explicit routing to deduplicate)")
        if h.shard_payload_dtype != "float32" and not h.shard_tables:
            raise _err("hyperparam.shard_payload_dtype",
                       "only applies with shard_tables: true (replicated "
                       "tables put nothing on the wire)")
        if h.shard_payload_dtype != "float32" and h.shard_gather != "alltoall":
            raise _err("hyperparam.shard_payload_dtype",
                       "needs shard_gather: alltoall (the gspmd lowering "
                       "does not stage an explicit wire payload)")
        if self.serve is not None:
            sv = self.serve
            if sv.batch_size is not None and sv.batch_size <= 0:
                raise _err("serve.batch_size", "must be positive")
            if sv.cache_slots < 0:
                raise _err("serve.cache_slots",
                           "must be >= 0 (0 disables the cache)")
            if sv.max_staleness_steps < 0:
                raise _err("serve.max_staleness_steps", "must be >= 0")
            for key in ("requests", "request_size", "hot_set"):
                if getattr(sv, key) <= 0:
                    raise _err(f"serve.{key}", "must be positive")
            if not 0.0 <= sv.hot_fraction <= 1.0:
                raise _err("serve.hot_fraction", "must be in [0, 1]")
            if sv.num_replicas < 1:
                raise _err("serve.num_replicas", "must be >= 1")
            if sv.port is not None and not 0 <= sv.port <= 65535:
                raise _err("serve.port",
                           "must be in [0, 65535] (0 = ephemeral)")
            if sv.max_pending_rows < 0:
                raise _err("serve.max_pending_rows",
                           "must be >= 0 (0 = unlimited)")
            if not sv.priorities:
                raise _err("serve.priorities",
                           "needs at least one priority class")
            for name, frac in sv.priorities.items():
                if not isinstance(frac, (int, float)) or \
                        not 0.0 < float(frac) <= 1.0:
                    raise _err(f"serve.priorities.{name}",
                               "budget fraction must be in (0, 1]")
        if (inp.dataset is None) == (inp.gconstruct_conf is None):
            raise _err("input",
                       "exactly one of 'input.dataset' (built-in synthetic "
                       "family) or 'input.gconstruct_conf' (graph "
                       "construction schema) must be set")
        section = getattr(self, self.task)
        if section is None:
            raise _err(self.task,
                       f"task '{self.task}' requires a '{self.task}' "
                       f"section (add one, even if empty, to opt in)")
        if self.task == "link_prediction":
            self._validate_lp(section, "link_prediction")
        if self.task == "multi_task":
            if not section.tasks:
                raise _err("multi_task.tasks",
                           "a multi_task run needs at least one task entry")
            names = [t.name for t in section.tasks]
            if len(set(names)) != len(names):
                raise _err("multi_task.tasks",
                           f"task names must be unique, got {names}")
            for i, t in enumerate(section.tasks):
                if t.task_section() is None:
                    raise _err(f"multi_task.tasks[{i}]",
                               f"task '{t.name}' has kind='{t.kind}' but "
                               f"no '{t.kind}' section")
                if t.kind == "link_prediction":
                    self._validate_lp(t.link_prediction,
                                      f"multi_task.tasks[{i}].link_prediction")

    def _validate_lp(self, lp: LinkPredictionConfig, path: str):
        k, b = lp.num_negatives, self.hyperparam.batch_size
        method = lp.effective_neg_method
        if k <= 0:
            raise _err(f"{path}.num_negatives", "must be positive")
        if method in ("joint", "local_joint") and \
                b % k != 0 and k < b:
            raise _err(f"{path}.num_negatives",
                       f"{method} negative sharing needs "
                       f"hyperparam.batch_size ({b}) divisible by "
                       f"num_negatives ({k}), or num_negatives >= "
                       f"batch_size")

    # ------------------------------------------------------------------
    def resolved(self) -> "GSConfig":
        """Fill task-target defaults from the built-in dataset table
        (e.g. dataset 'mag' -> target_ntype 'paper', 8 classes)."""
        cfg = dataclasses.replace(self)
        target = DATASET_TARGETS.get(cfg.input.dataset or "")

        def _fill_nc(nc):
            if nc is None:
                return None
            nc = dataclasses.replace(nc)
            if target:
                nc.target_ntype = nc.target_ntype or target[0]
                nc.num_classes = nc.num_classes or target[2]
            if nc.target_ntype is None or nc.num_classes is None:
                raise _err("node_classification",
                           "target_ntype/num_classes must be set when "
                           "input.dataset is not a built-in family")
            return nc

        def _fill_lp(lp):
            if lp is None:
                return None
            lp = dataclasses.replace(lp)
            if target and lp.target_etype is None:
                lp.target_etype = target[1]
            if lp.target_etype is None:
                raise _err("link_prediction.target_etype",
                           "must be set when input.dataset is not a "
                           "built-in family")
            if lp.train_negative_sampler is not None:
                # fold the GraphStorm-style alias into neg_method so the
                # rest of the pipeline reads one field
                lp.neg_method = lp.train_negative_sampler
            return lp

        def _fill_nr(nr):
            if nr is None:
                return None
            nr = dataclasses.replace(nr)
            if target:
                nr.target_ntype = nr.target_ntype or target[0]
            if nr.target_ntype is None:
                raise _err("node_regression.target_ntype",
                           "must be set when input.dataset is not a "
                           "built-in family")
            return nr

        def _fill_edge(ec, path, classes=False):
            if ec is None:
                return None
            ec = dataclasses.replace(ec)
            if target and ec.target_etype is None:
                ec.target_etype = target[1]
            if ec.target_etype is None:
                raise _err(f"{path}.target_etype",
                           "must be set when input.dataset is not a "
                           "built-in family")
            if classes and ec.num_classes is None:
                # derived same-label-endpoint target is binary; an edge
                # label_field supplies its own cardinality explicitly
                if ec.label_field is not None:
                    raise _err(f"{path}.num_classes",
                               "must be set when label_field names an "
                               "edge label column")
                ec.num_classes = 2
            return ec

        # only the section(s) the active task will run are resolved (and
        # thereby validated) — an unused extra section stays untouched
        if cfg.task == "node_classification":
            cfg.node_classification = _fill_nc(cfg.node_classification)
        elif cfg.task == "node_regression":
            cfg.node_regression = _fill_nr(cfg.node_regression)
        elif cfg.task == "edge_classification":
            cfg.edge_classification = _fill_edge(
                cfg.edge_classification, "edge_classification", classes=True)
        elif cfg.task == "edge_regression":
            cfg.edge_regression = _fill_edge(
                cfg.edge_regression, "edge_regression")
        elif cfg.task == "link_prediction":
            cfg.link_prediction = _fill_lp(cfg.link_prediction)
        elif cfg.task == "multi_task" and cfg.multi_task is not None:
            tasks = []
            for t in cfg.multi_task.tasks:
                t = dataclasses.replace(
                    t, node_classification=_fill_nc(t.node_classification),
                    link_prediction=_fill_lp(t.link_prediction))
                tasks.append(t)
            cfg.multi_task = MultiTaskConfig(tasks=tasks)
        return cfg


# ---------------------------------------------------------------------------
# file loading + CLI overrides
# ---------------------------------------------------------------------------
def load_config_dict(path: str) -> Dict[str, Any]:
    """Read a YAML or JSON config file into a plain dict."""
    with open(path) as f:
        text = f.read()
    if path.endswith(".json"):
        raw = json.loads(text)
    else:
        import yaml
        raw = yaml.safe_load(text)
    if not isinstance(raw, dict):
        raise ConfigError(f"config file {path!r} must contain a mapping, "
                          f"got {type(raw).__name__}")
    return raw


def _parse_scalar(text: str):
    """Parse an override value the way YAML would ('8,8' -> [8, 8])."""
    import yaml
    if "," in text and not text.strip().startswith(("[", "{")):
        text = f"[{text}]"
    try:
        return yaml.safe_load(text)
    except yaml.YAMLError:
        return text


def apply_overrides(raw: Dict[str, Any],
                    overrides: List[str]) -> Dict[str, Any]:
    """Apply CLI overrides to a raw config dict.

    Accepts ``--gnn.hidden 128`` pairs and ``gnn.hidden=128`` tokens;
    dotted paths address nested sections.  Values are YAML-parsed, so
    ``--gnn.fanout 8,8`` and ``--device_features true`` do what they say.
    Typos surface as unknown-key errors when the dict is loaded.
    """
    raw = json.loads(json.dumps(raw))  # deep copy
    pairs: List[Tuple[str, Any]] = []
    i = 0
    while i < len(overrides):
        tok = overrides[i]
        if "=" in tok:
            key, _, val = tok.lstrip("-").partition("=")
            pairs.append((key, _parse_scalar(val)))
            i += 1
        elif tok.startswith("--"):
            if i + 1 >= len(overrides):
                raise ConfigError(f"override {tok!r} is missing a value")
            pairs.append((tok[2:].replace("-", "_"),
                          _parse_scalar(overrides[i + 1])))
            i += 2
        else:
            raise ConfigError(
                f"cannot parse override {tok!r}: use '--section.key value' "
                f"or 'section.key=value'")
    for key, val in pairs:
        parts = key.split(".")
        node = raw
        for p in parts[:-1]:
            node = node.setdefault(p, {})
            if not isinstance(node, dict):
                raise ConfigError(f"override {key!r}: '{p}' is not a "
                                  f"section")
        node[parts[-1]] = val
    return raw
