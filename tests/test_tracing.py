"""Device scopes and host spans of the training program
(``repro.trainer.tracing``): every scope reaches the lowered epoch
program on each lowering, scopes change no loss, and the streaming
epoch engine writes its spans into a profiler trace in order."""
import contextlib
import re

import numpy as np
import pytest

from repro.trainer import tracing

# the lowerings of the device epoch: one device; the dp shard_map over
# replicated tables; the all-to-all path over row-sharded tables (both
# on a data mesh of every device, one here)
LOWERINGS = {"single": {"data_parallel": 1},
             "dp": {"data_parallel": 0},
             "alltoall": {"data_parallel": 0, "shard_tables": True}}
TASKS = ("node_classification", "link_prediction")
NAME = re.compile(r'loc\("([^"]*)"')
WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
HLO_OP = re.compile(r"= \S+ (\w[\w-]*)\(.*op_name=\"([^\"]*)\"")


def _raw(task, **hp):
    raw = {"task": task, "gnn": {"hidden": 16, "fanout": [2, 2]},
           "hyperparam": {"batch_size": 16, "num_epochs": 1, "seed": 0,
                          "sample_on_device": True, **hp},
           "input": {"dataset": "mag",
                     "dataset_conf": {"n_paper": 96, "n_author": 48}},
           "device_features": True}
    if task == "link_prediction":
        raw["link_prediction"] = {"neg_method": "joint", "num_negatives": 4}
    else:
        raw["node_classification"] = {}
    return raw


def _runner(task, **hp):
    from repro.config import GSConfig
    from repro.runner import TASK_REGISTRY, build_graph
    cfg = GSConfig.from_dict(_raw(task, **hp)).resolved()
    runner = TASK_REGISTRY[cfg.task](cfg, build_graph(cfg))
    if task == "link_prediction":
        return runner, runner._train_loader()
    ids, _, _ = runner.data.train_val_test_nodes(runner.target_ntype,
                                                 rng=runner._split_rng())
    return runner, runner._train_loader(ids)


def _scopes_in(names):
    """The program scopes named anywhere in these name stacks."""
    found = set()
    for name in names:
        for part in name.split("/"):
            m = WRAPPED.match(part)
            while m:
                part = m.group(1)
                m = WRAPPED.match(part)
            if part.rstrip("0123456789") in tracing.SCOPES:
                found.add(part)
    return found


def _lowered_epoch(task, **hp):
    runner, loader = _runner(task, **hp)
    tr = runner.trainer
    xs = loader.epoch_blocks(epoch=0)
    fns = tr._engine_fns_for(loader, xs)
    tables = tr.feature_store.tables if tr.feature_store is not None else {}
    return fns["epoch"].lower(tr.params, tr.opt_state, tr.stepno,
                              tr._sparse_pack(), tables,
                              tr.device_sampler.tables, fns["put"](xs))


def _lowered_names(task, **hp):
    low = _lowered_epoch(task, **hp)
    return NAME.findall(low.as_text(debug_info=True))


def _expected(task):
    every = {s for s in tracing.SCOPES if s != "gnn.layer"} | \
        {"gnn.layer0", "gnn.layer1"}
    if task == "node_classification":
        # one seed role (its layout is a no-op) and no target edges
        return every - {"expand", "spot_target"}
    return every


@pytest.mark.parametrize("lowering", LOWERINGS)
@pytest.mark.parametrize("task", TASKS)
def test_every_scope_reaches_the_lowered_epoch(task, lowering):
    found = _scopes_in(_lowered_names(task, **LOWERINGS[lowering]))
    assert found == _expected(task)


def test_backward_ops_carry_their_forward_scope():
    names = _lowered_names("node_classification")
    for s in ("gnn.layer0", "gnn.layer1", "encode", "head"):
        assert any(f"transpose(jvp({s}))" in n for n in names), s


@pytest.mark.parametrize("lowering", ["single", "dp"])
def test_spot_target_lowers_without_gather_or_sort(lowering):
    """SpotTarget's membership test is compares and reductions only: a
    gather or sort under its scope is the searched form coming back,
    one scalar gather per query per search step."""
    hlo = _lowered_epoch("link_prediction",
                         **LOWERINGS[lowering]).as_text(dialect="hlo",
                                                        debug_info=True)
    ops = [m.group(1) for line in hlo.splitlines()
           if (m := HLO_OP.search(line))
           and "spot_target" in m.group(2).split("/")]
    assert "compare" in ops and "reduce" in ops
    assert not {"gather", "sort", "dynamic-slice"} & set(ops), ops


def _first_epoch_losses(task, **hp):
    from repro.trainer.epoch_engine import StreamingEpochEngine
    runner, loader = _runner(task, **hp)
    engine = StreamingEpochEngine(runner.trainer, loader)
    engine.run(1)
    return engine.step_losses[0]


@pytest.mark.parametrize("lowering", ["single", "dp"])
@pytest.mark.parametrize("task", TASKS)
def test_scopes_leave_losses_bit_identical(task, lowering, monkeypatch):
    hp = LOWERINGS[lowering]
    scoped = _first_epoch_losses(task, **hp)
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    assert _scopes_in(_lowered_names(task, **hp)) == set()
    plain = _first_epoch_losses(task, **hp)
    assert scoped.dtype == plain.dtype and len(scoped) > 1
    np.testing.assert_array_equal(scoped, plain)


def test_engine_spans_are_in_the_host_trace_in_order(tmp_path):
    import jax
    from jax.profiler import ProfileData
    from repro.trainer.epoch_engine import StreamingEpochEngine
    runner, loader = _runner("node_classification")
    engine = StreamingEpochEngine(runner.trainer, loader)
    engine.run(1)                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(2)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = sorted((e.start_ns, e.name)
                   for p in ProfileData.from_file(str(path)).planes
                   if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name in tracing.SPANS)
    # epoch 1 is staged while epoch 0 runs; each epoch ends in its fetch
    assert [n for _, n in spans] == [
        "engine.run", "stage_epoch",
        "slice_chunk", "dispatch_epoch", "stage_epoch", "fetch_losses",
        "slice_chunk", "dispatch_epoch", "fetch_losses"]


def test_cached_programs_keep_their_own_scopes(monkeypatch, tmp_path):
    """An executable from the persistent cache carries the metadata it
    was compiled with, so the program keys the cache by metadata too."""
    import jax
    from repro.common.compile_cache import enable_compile_cache
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        enable_compile_cache()
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before)


@pytest.mark.parametrize("bad", ["backward", "gnn", "dispatch_epoch"])
def test_unknown_scope_is_refused(bad):
    with pytest.raises(ValueError):
        tracing.scope(bad)


@pytest.mark.parametrize("bad", ["window", "sample", "engine"])
def test_unknown_span_is_refused(bad):
    with pytest.raises(ValueError):
        tracing.span(bad)
