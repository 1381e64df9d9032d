"""One run of one cell: build the cell's graph and the program's runner
from the seed, warm up, measure a window of training, check the first
epoch against the plain reference, and return the result line.

The window is one ``StreamingEpochEngine.run(n)`` call over the runner's
trainer and train loader, the path ``python -m repro.cli.gs`` trains
through; ``n`` whole epochs fill ``seconds`` at the warm-up's rate.  The
first epoch of set-up goes through the same engine, program and feed:
its first steps' losses, and the weights and tables it leaves, are what
the reference is compared with.
"""
from __future__ import annotations

import contextlib
import copy
import gc
import math
import os
import tempfile
import time
from typing import Callable, Dict

import numpy as np

from benchmarks.chip import adapter, counts, reference, spec, trace

SPANS = ("graph_build", "runner_build", "warmup", "window", "stage_epoch",
         "dispatch_epoch")
# steps whose losses are compared
REF_STEPS = 3
# a leaf's change is compared where the reference's first gradient of it
# is at least this share of the median leaf's
COUNTED = 1e-3


def data_seed(seed: int) -> int:
    """The 31-bit seed of the graph, its features and the weights, from
    any whole ``--seed``.  The program's own seed (sampling, negatives,
    split, shuffle) is the configuration's ``hyperparam.seed``: the
    program bakes it into the compiled epoch, and a fixed one lets every
    run after a checkout's first find the programs in the cache."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def gs_config(cell: spec.Cell) -> dict:
    raw = copy.deepcopy(cell.config["gs"])
    hp = raw.setdefault("hyperparam", {})
    hp.update(cell.traffic.get("hyperparam", {}))
    hp["batch_size"] = int(cell.traffic["batch_size"])
    return raw


def bench_weights(template, tables: Dict[str, tuple], pseed: int,
                  stds: Dict[str, float]):
    """Dense weights shaped like ``template`` and one embedding table per
    ``tables`` entry ``(rows, dim)``, made on the device in one jitted
    call from the seed.  Each leaf is normal with standard deviation
    ``stds[path]`` where the configuration names its path (``dec/w2``),
    else ``fan_in ** -0.5`` for a matrix and 0.1 for a vector; table
    rows have 0.1."""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    shapes = [tuple(x.shape) for _, x in leaves]
    scale = []
    for path, x in leaves:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        default = x.shape[0] ** -0.5 if len(x.shape) >= 2 else 0.1
        scale.append(float(stds.get(name, default)))
    names = sorted(tables)

    def make(key):
        kw, kt = jax.random.split(key)
        out = [sd * jax.random.normal(jax.random.fold_in(kw, i), s,
                                      jnp.float32)
               for i, (s, sd) in enumerate(zip(shapes, scale))]
        tabs = {nt: 0.1 * jax.random.normal(jax.random.fold_in(kt, i),
                                            tables[nt], jnp.float32)
                for i, nt in enumerate(names)}
        return out, tabs

    dense, tabs = jax.jit(make)(
        jax.random.fold_in(jax.random.PRNGKey(pseed), 0x3E16))
    return jax.tree_util.tree_unflatten(treedef, dense), tabs


class Spans:
    """Host-clock spans around the benchmark's calls into each layer,
    also written into the profiler's trace while it records."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + \
            time.perf_counter() - t


def _annotate(spans: Spans, name: str, fn: Callable) -> Callable:
    def wrapped(*a, **k):
        with spans(name):
            return fn(*a, **k)
    return wrapped


def build_program(cell: spec.Cell, gd, feats, pseed: int):
    """The runner, assembled as ``build_runner`` does, over the cell's
    graph, with the benchmark's weights installed."""
    from repro.config import GSConfig
    from repro.core.graph import HeteroGraph
    from repro.runner import TASK_REGISTRY
    node_feats = {nt: {"feat": feats[nt]} for nt in feats}
    for nt, lab in gd.labels.items():
        node_feats.setdefault(nt, {})["label"] = lab
    graph = HeteroGraph(gd.num_nodes, gd.edges, node_feats)
    cfg = GSConfig.from_dict(gs_config(cell)).resolved()
    runner = TASK_REGISTRY[cfg.task](cfg, graph)
    params, tables = bench_weights(*adapter.weight_shapes(cell, gd), pseed,
                                   cell.config.get("weights", {}))
    adapter.install(runner, cell, gd, params, tables)
    return runner


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def free_device_memory() -> int:
    """Collect what the dropped program state held; returns the bytes
    still live on the devices."""
    import jax
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def run_reference(cell: spec.Cell, gd, pseed: int, blocks, products=None,
                  storage=None, fault=None) -> dict:
    """The reference over ``blocks`` (the first epoch), from weights,
    tables and features it makes again from the seed: its losses, first
    gradients' norms and final state (``Reference.run``), and ``init``,
    the state it started from."""
    import jax.numpy as jnp
    fam = spec.graph_family(cell.config["graph"]["family"])
    ref = reference.Reference(cell.config, gd, int(cell.traffic["batch_size"]),
                              spec.gnn_model(cell), products=products,
                              storage=storage, fault=fault)
    params, tables = bench_weights(*adapter.weight_shapes(cell, gd), pseed,
                                   cell.config.get("weights", {}))
    feats = fam.device_features(gd)
    drop = {}
    lp = cell.config["gs"].get("link_prediction")
    if lp is not None:
        drop = eval_edge_mask(gd, tuple(lp["target_etype"]),
                              cell.config["gs"]["hyperparam"]["seed"],
                              cell.config["reference"]["split"])
    csr = reference.build_csr(gd.edges, gd.num_nodes, drop)
    labels = {nt: jnp.asarray(v) for nt, v in gd.labels.items()}
    steps = len(next(iter(blocks.values())))
    out = ref.run(params, tables, csr, feats, labels, blocks, steps)
    out["init"] = {"params": params, "tables": tables}
    return out


def eval_edge_mask(gd, etype, seed: int, split) -> dict:
    """The validation and test edges (a seeded permutation cut by the
    configuration's split) that message passing may not see, with their
    reverse copies."""
    n = len(gd.edges[etype][0])
    perm = np.random.default_rng(seed).permutation(n)
    mask = np.zeros(n, bool)
    mask[perm[int(split[0] * n):]] = True
    s, r, d = etype
    return {etype: mask, (d, r + "-rev", s): mask}


def step_work(cell: spec.Cell, gd) -> Dict[str, float]:
    """FLOPs and bytes of one step on one chip."""
    import jax
    gs = cell.config["gs"]
    dp = int(cell.traffic.get("hyperparam", {}).get("data_parallel", 1))
    B = int(cell.traffic["batch_size"]) // dp
    H = gs["gnn"]["hidden"]
    dim = gs["gnn"]["sparse_embed_dim"]
    if gs["task"] == "node_classification":
        nt = gs["node_classification"]["target_ntype"]
        seeds = {nt: B}
        head = {"kind": "nc", "batch": B,
                "classes": gs["node_classification"]["num_classes"]}
    else:
        lp = gs["link_prediction"]
        s, _, d = lp["target_etype"]
        k = int(lp["num_negatives"])
        seeds = {s: B}
        seeds[d] = seeds.get(d, 0) + B + (B if k < B else k)
        head = {"kind": "lp", "batch": B, "k": k}
    layers = reference.plan(sorted(gd.edges), gs["gnn"]["fanout"], seeds)
    dims = {nt: gd.feat_dims.get(nt, dim) for nt in gd.num_nodes}
    tables = {nt: dim for nt in gd.num_nodes if nt not in gd.feat_dims}
    n_dense = sum(int(np.prod(x.shape)) for x in
                  jax.tree_util.tree_leaves(
                      adapter.weight_shapes(cell, gd)[0]))
    gnn = spec.gnn_model(cell)
    return counts.step_counts(
        layers, dims, tables, H, head, n_dense,
        lambda layer: gnn.layer_flops(layer, H, gs["gnn"]))


def leaf_changes(state, init) -> Dict[str, float]:
    """The norm of each leaf's change from ``init``, by leaf name
    (``params/<path>``, ``tables/<ntype>``)."""
    import jax
    import jax.numpy as jnp
    out = {}
    for group in ("params", "tables"):
        names = reference.leaf_names(init[group])
        for n, x, x0 in zip(names, jax.tree_util.tree_leaves(state[group]),
                            jax.tree_util.tree_leaves(init[group])):
            d = jnp.asarray(x, jnp.float32) - x0
            out[f"{group}/{n}"] = float(jnp.linalg.norm(d))
    return out


def gaps(program: dict, ref: dict, detail: dict = None) -> dict:
    """The compared numbers, of a run (``losses``, ``params``, ``tables``)
    against the reference's:

    - ``loss_gap.step0``, the first step's relative loss gap, which
      checks the forward pass and the loss; ``loss_gap.steps12``, the
      larger of the next two, which also checks the first updates;
    - ``change_gap.params`` and ``change_gap.tables``: over the first
      epoch, by the worst leaf, the gap between the run's and the
      reference's norms of the leaf's change, over the larger of the
      reference's norm of that leaf and of the median leaf;
      ``change_gap.params_median``, the median dense leaf's gap, which
      swings less where the epoch's later steps are noise.  Leaves
      whose first gradient in the reference is under ``COUNTED`` of the
      median leaf's move by round-off alone and are left out.

    A cell's limits name the numbers it compares.  ``detail``, where
    given, gets each worst gap's leaf."""
    p = np.asarray(program["losses"][:REF_STEPS], np.float64)
    r = np.asarray(ref["losses"][:REF_STEPS], np.float64)
    gap = np.abs(p - r) / np.abs(r)
    out = {"loss_gap.step0": float(gap[0]),
           "loss_gap.steps12": float(gap[1:].max())}
    import jax
    grads = {}
    for group in ("params", "tables"):
        names = reference.leaf_names(ref["init"][group])
        vals = jax.tree_util.tree_leaves(ref["grad_norms"][group])
        grads.update({f"{group}/{n}": v for n, v in zip(names, vals)})
    med = float(np.median(list(grads.values())))
    counted = [k for k, g in grads.items() if g >= COUNTED * med]
    rc = leaf_changes(ref, ref["init"])
    pc = leaf_changes(program, ref["init"])
    med_c = float(np.median([rc[k] for k in counted]))
    for group in ("params", "tables"):
        g = {k: abs(pc[k] - rc[k]) / max(rc[k], med_c)
             for k in counted if k.startswith(group + "/")}
        if g:
            worst = max(g, key=g.get)
            out[f"change_gap.{group}"] = float(g[worst])
            if detail is not None:
                detail[f"change_gap.{group}"] = worst
            if group == "params":
                out["change_gap.params_median"] = float(
                    np.median(list(g.values())))
    if detail is not None:
        detail["left_out"] = sorted(set(grads) - set(counted))
    return out


def check(cell: spec.Cell, numbers: dict) -> dict:
    """Each number the cell's limits name beside its limit.  A number
    that is missing or not finite exceeds any limit."""
    out = {}
    for k, lim in cell.limits.items():
        if isinstance(lim, dict):
            v = numbers.get(k, float("inf"))
            out[k] = {"value": v if np.isfinite(v) else float("inf"),
                      "limit": lim["limit"]}
    return out


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def prepare(cell: spec.Cell, seed: int, spans: Spans):
    """The cell's graph and runner from the seed, with the loader and the
    engine the window uses, and the blocks of epoch 0 (the reference's
    inputs)."""
    from repro.trainer.epoch_engine import StreamingEpochEngine
    pseed = data_seed(seed)
    fam = spec.graph_family(cell.config["graph"]["family"])
    with spans("graph_build"):
        gd = fam.generate(cell.config["graph"], pseed)
        feats = fam.device_features(gd)
    with spans("runner_build"):
        runner = build_program(cell, gd, feats, pseed)
        loader = adapter.train_loader(runner, cell)
        engine = StreamingEpochEngine(runner.trainer, loader,
                                      **runner._fit_kwargs())
    first = {k: np.asarray(v) for k, v in loader.epoch_blocks(epoch=0).items()}
    return gd, pseed, runner, loader, engine, first


def first_epoch(engine, runner) -> dict:
    """Set-up's first epoch through the window's engine and program: its
    losses, its ``seconds``, and a host copy of the weights and tables it
    leaves."""
    t = time.perf_counter()
    engine.run(1)
    out = {"seconds": time.perf_counter() - t}
    out.update(adapter.state(runner))
    out["losses"] = [float(x) for x in engine.step_losses[0]]
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, log=print) -> dict:
    """One whole run; returns the result line's object."""
    import jax
    spans = Spans()
    gd, pseed, runner, loader, engine, first = prepare(cell, seed, spans)
    loader.epoch_blocks = _annotate(spans, "stage_epoch", loader.epoch_blocks)
    adapter.wrap_epoch(runner.trainer,
                       lambda fn: _annotate(spans, "dispatch_epoch", fn))
    with spans("warmup"):
        program = first_epoch(engine, runner)
    t_first = program["seconds"]
    # the first dispatch traces and compiles (or loads from the cache);
    # the rest of the first epoch is what one epoch takes, give or take
    # one-time costs of a few percent, which the 0.9 keeps from cutting
    # the window short of ``seconds``
    t_compile = spans.seconds["dispatch_epoch"]
    t_epoch = t_first - t_compile
    n_epochs = max(1, math.ceil(seconds / (0.9 * t_epoch)))
    steps = n_epochs * int(loader.num_batches)
    setup_s = time.time() - t_start
    tmp = tempfile.TemporaryDirectory() if traced else None
    try:
        if traced:
            jax.profiler.start_trace(tmp.name)
        with spans("window"):
            t = time.perf_counter()
            engine.run(n_epochs)
            window_s = time.perf_counter() - t
        if traced:
            jax.profiler.stop_trace()
        window_losses = np.concatenate(engine.step_losses[1:])
        device = device_info(cell.chips)
        metrics = {}
        if traced:
            # readers see the program too, for counters of their own
            run = {"cell": cell, "steps": steps, "window_s": window_s,
                   "t_compile_s": t_compile, "t_epoch_s": t_epoch,
                   "spans": dict(spans.seconds), "chips": cell.chips,
                   "work": step_work(cell, gd),
                   "peaks": spec.peaks(device["kind"]),
                   "runner": runner, "loader": loader,
                   "trace": trace.summarize(
                       trace.load(_xplane(tmp.name), SPANS), "window",
                       sorted(d.id for d in jax.devices()[:cell.chips]))}
            for m in cell.per_layer:
                v = spec.metric_reader(m.name).read(run)
                if v is not None:
                    metrics[m.name] = {"value": v, "unit": m.unit}
            summary = run.pop("trace")
            del run
    finally:
        if tmp is not None:
            tmp.cleanup()
    log({"phase": "window", "epochs": n_epochs, "steps": steps,
         "window_s": window_s, "first_epoch_s": t_first,
         "first_dispatch_s": t_compile, "setup_s": setup_s,
         "spans": spans.seconds,
         "program_losses": program["losses"][:REF_STEPS]})
    del runner, loader, engine
    live = free_device_memory()
    t = time.perf_counter()
    ref = run_reference(cell, gd, pseed, first)
    detail = {}
    checks = check(cell, gaps(program, ref, detail))
    log({"phase": "reference", "reference_losses": ref["losses"][:REF_STEPS],
         "worst_leaves": detail, "seconds": time.perf_counter() - t,
         "device_bytes_live_before": live})
    del ref
    failed = int((~np.isfinite(window_losses)).sum())
    correct = failed == 0 and passes(checks)
    out = {"correct": bool(correct), "attempted": steps, "failed": failed}
    if traced:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["metrics"] = metrics
    else:
        e2e = {"train_step_ms": window_s / steps * 1e3,
               "peak_hbm_gb": device["memory_peak_bytes"] / 1e9,
               "setup_s": setup_s}
        out["metrics"] = {m.name: {"value": e2e[m.name], "unit": m.unit}
                          for m in cell.end_to_end}
    out["device"] = device
    if traced:
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = checks
    return out


def _xplane(d: str) -> str:
    for root, _, files in os.walk(d):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no .xplane.pb under {d}")
