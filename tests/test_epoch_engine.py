"""Streaming epoch engine (docs/pipeline.md §3f): chunked-scan parity,
device-resident eval, atomic + async checkpointing, and the
``(seed, epoch)``-keyed resume determinism contract.

The multi-device runs (host-sampled dp1-vs-dp8 through the shard_map
lowering, streaming-vs-blocking under dp) execute in a subprocess
because ``--xla_force_host_platform_device_count`` must be set before
the first jax import.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointWriter, load_trainer,
                              save_trainer)
from repro.core.embedding import SparseEmbedding
from repro.data import make_mag_like
from repro.gnn.model import model_meta_from_graph
from repro.trainer import (GSgnnAccEvaluator, GSgnnData, GSgnnNodeDataLoader,
                           GSgnnNodeTrainer)
from repro.trainer.epoch_engine import StreamingEpochEngine, _chunk_bounds

_ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# chunk arithmetic
# ---------------------------------------------------------------------------
def test_chunk_bounds():
    assert _chunk_bounds(10, 1) == [(0, 10)]
    assert _chunk_bounds(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
    assert _chunk_bounds(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
    for nb, k in [(7, 3), (16, 5), (5, 5)]:
        bounds = _chunk_bounds(nb, k)
        assert bounds[0][0] == 0 and bounds[-1][1] == nb
        assert all(a2 == b1 for (_, b1), (a2, _) in zip(bounds, bounds[1:]))
        # at most two distinct chunk lengths -> at most two jit entries
        assert len({b - a for a, b in bounds}) <= 2


# ---------------------------------------------------------------------------
# host-sampled engine: parity with the unchunked scan and with the
# legacy per-batch loop
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mag():
    return make_mag_like(n_paper=96, n_author=48, seed=0)


def _nc_trainer(g):
    extra = {nt: 16 for nt in g.ntypes if not g.has_feat(nt)}
    model = model_meta_from_graph(g, "rgcn", 16, 2, extra_feat_dims=extra)
    sparse = {nt: SparseEmbedding(g.num_nodes[nt], 16, name=nt)
              for nt in extra}
    return GSgnnNodeTrainer(model, "paper", num_classes=8, lr=1e-2,
                            sparse_embeds=sparse,
                            evaluator=GSgnnAccEvaluator())


def _nc_loader(g, shuffle=True, n=64, batch=16):
    return GSgnnNodeDataLoader(GSgnnData(g), "paper", np.arange(n), [2, 2],
                               batch, shuffle=shuffle, seed=0)


def _losses(hist):
    return np.array([h["loss"] for h in hist])


def test_host_chunked_losses_bitwise_match_blocking(mag):
    def run(chunks):
        trainer = _nc_trainer(mag)
        hist = trainer.fit(_nc_loader(mag), num_epochs=2,
                           epoch_chunks=chunks)
        return _losses(hist)

    blocking = run(1)
    # chunking only splits the scan carry: bit-identical, any K —
    # including K=3 over 4 batches (two distinct chunk lengths)
    np.testing.assert_array_equal(blocking, run(2))
    np.testing.assert_array_equal(blocking, run(3))


def test_host_engine_matches_legacy_per_batch_loop(mag):
    engine_tr = _nc_trainer(mag)
    hist = engine_tr.fit(_nc_loader(mag), num_epochs=2)

    legacy_tr = _nc_trainer(mag)
    loader = _nc_loader(mag)
    legacy = []
    for _ in range(2):
        losses = [legacy_tr.fit_batch(b)[0] for b in loader]
        legacy.append(float(np.mean(losses)))
    # identical (seed, epoch)-keyed draws; only XLA fusion differs
    # between the scanned epoch program and the per-batch step
    np.testing.assert_allclose(_losses(hist), legacy, rtol=1e-4)


@pytest.mark.parametrize("chunks", [1, 3])
def test_engine_step_losses_match_legacy_per_batch_loop(mag, chunks):
    """The engine keeps each step's loss, in step order across chunks;
    they average to the epoch loss and follow the per-batch loop."""
    engine = StreamingEpochEngine(_nc_trainer(mag), _nc_loader(mag),
                                  epoch_chunks=chunks)
    hist = engine.run(2)
    legacy_tr = _nc_trainer(mag)
    loader = _nc_loader(mag)
    legacy = [[legacy_tr.fit_batch(b)[0] for b in loader] for _ in range(2)]
    assert [len(s) for s in engine.step_losses] == [4, 4]
    np.testing.assert_allclose([s.mean() for s in engine.step_losses],
                               _losses(hist), rtol=1e-6)
    np.testing.assert_allclose(np.stack(engine.step_losses), legacy,
                               rtol=1e-4)


def test_engine_second_fit_continues_epoch_stream(mag):
    one_shot = _nc_trainer(mag)
    full = _losses(one_shot.fit(_nc_loader(mag), num_epochs=4))

    resumed = _nc_trainer(mag)
    loader = _nc_loader(mag)
    resumed.fit(loader, num_epochs=2)
    # epochs are keyed by len(history): the second call replays the
    # original run's epochs 2..3 batch stream exactly
    np.testing.assert_array_equal(
        full, _losses(resumed.fit(loader, num_epochs=2)))


def test_checkpoint_resume_replays_batch_stream(mag, tmp_path):
    path = str(tmp_path / "ckpt")
    full = _losses(_nc_trainer(mag).fit(_nc_loader(mag), num_epochs=4))

    first = _nc_trainer(mag)
    first.fit(_nc_loader(mag), num_epochs=2)
    save_trainer(first, path)

    restored = load_trainer(_nc_trainer(mag), path)
    hist = restored.fit(_nc_loader(mag), num_epochs=2)
    assert [h["epoch"] for h in hist] == [0, 1, 2, 3]
    np.testing.assert_array_equal(full, _losses(hist))


def test_eval_on_device_matches_host_eval(mag):
    def run(on_device):
        trainer = _nc_trainer(mag)
        hist = trainer.fit(_nc_loader(mag),
                           _nc_loader(mag, shuffle=False),
                           num_epochs=2, eval_on_device=on_device)
        return _losses(hist), [h["accuracy"] for h in hist]

    host_l, host_a = run(False)
    dev_l, dev_a = run(True)
    # eval never perturbs training state
    np.testing.assert_array_equal(host_l, dev_l)
    # same (num, den) metric contract; fused in-jit logits may flip an
    # argmax only on float ties
    np.testing.assert_allclose(host_a, dev_a, atol=0.05)


def test_async_checkpoint_publishes_each_epoch(mag, tmp_path):
    path = str(tmp_path / "ckpt")
    trainer = _nc_trainer(mag)
    trainer.fit(_nc_loader(mag), num_epochs=2,
                checkpoint=lambda t: save_trainer(t, path),
                async_checkpoint=True)
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    assert meta["stepno"] == int(trainer.stepno)
    assert len(meta["history"]) == 2
    # the published checkpoint restores into a fresh trainer
    restored = load_trainer(_nc_trainer(mag), path)
    np.testing.assert_array_equal(
        np.asarray(restored.stepno), np.asarray(trainer.stepno))


# ---------------------------------------------------------------------------
# AsyncCheckpointWriter unit behavior
# ---------------------------------------------------------------------------
def test_async_writer_latest_wins():
    w = AsyncCheckpointWriter()
    done, gate = [], threading.Event()
    w.submit(lambda: (gate.wait(10), done.append("a")))
    deadline = time.time() + 5          # wait for the thread to take job a
    while w._job is not None and time.time() < deadline:
        time.sleep(0.01)
    w.submit(lambda: done.append("b"))
    w.submit(lambda: done.append("c"))  # replaces the pending "b"
    gate.set()
    w.drain()
    assert done == ["a", "c"]
    assert w.written == 2
    w.close()


def test_async_writer_reraises_on_training_thread():
    w = AsyncCheckpointWriter()
    def boom():
        raise ValueError("disk full")
    w.submit(boom)
    with pytest.raises(ValueError, match="disk full"):
        w.drain()
    w.close()
    with pytest.raises(RuntimeError, match="closed"):
        w.submit(lambda: None)


# ---------------------------------------------------------------------------
# atomic checkpoint writes: SIGKILL mid-write must leave the previous
# complete checkpoint untouched (temp file + os.replace publish)
# ---------------------------------------------------------------------------
_KILL_SCRIPT = r"""
import os, signal, sys, threading
sys.path.insert(0, os.path.join(%(root)r, "src"))
import numpy as np
from repro.checkpoint import save_trainer

class FakeTrainer:
    params = {"w": np.arange(4.0, dtype=np.float32)}
    opt_state = {"m": np.zeros(4, np.float32)}
    stepno = 7
    task = "node_classification"
    history = [{"epoch": 0, "loss": 1.0}]
    sparse_embeds = {}

path = sys.argv[1]
t = FakeTrainer()
save_trainer(t, path, config={"seed": 0})
print("SAVED1", flush=True)
t.params = {"w": np.full(4, 9.0, np.float32)}
t.stepno = 99
# widen the mid-write window, then SIGKILL while the new params.npz is
# still a temp file — the publish (os.replace) must never have happened
os.environ["REPRO_CKPT_WRITE_DELAY_S"] = "30"
threading.Timer(1.0, lambda: os.kill(os.getpid(), signal.SIGKILL)).start()
save_trainer(t, path)
print("UNREACHABLE", flush=True)
"""


def test_kill_mid_write_preserves_previous_checkpoint(tmp_path):
    path = str(tmp_path / "ckpt")
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_SCRIPT % {"root": _ROOT}, path],
        capture_output=True, text=True, timeout=120, cwd=_ROOT)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode,
                                                proc.stderr[-2000:])
    assert "SAVED1" in proc.stdout and "UNREACHABLE" not in proc.stdout
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    assert meta["stepno"] == 7          # the kill never published step 99
    with np.load(os.path.join(path, "params.npz")) as z:
        np.testing.assert_array_equal(z["w"], np.arange(4.0,
                                                        dtype=np.float32))


# ---------------------------------------------------------------------------
# 8 fake devices (subprocess): host-sampled dp1 vs dp8 through the
# engine's shard_map lowering, and streaming-vs-blocking parity under dp
# ---------------------------------------------------------------------------
_DP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import sys
sys.path.insert(0, os.path.join(%(root)r, "src"))
from repro.config import GSConfig
from repro.runner import TASK_REGISTRY, build_graph

def run(raw):
    cfg = GSConfig.from_dict(raw).resolved()
    runner = TASK_REGISTRY[cfg.task](cfg, build_graph(cfg))
    hist = runner.train()["history"]
    out = {"loss": [h["loss"] for h in hist],
           "acc": [h["accuracy"] for h in hist]}
    path = raw.get("output", {}).get("save_model_path")
    if path:
        out["ckpt_meta"] = json.load(open(os.path.join(path, "meta.json")))
    return out

confs = json.loads(sys.argv[1])
print("DPRESULT:" + json.dumps({k: run(v) for k, v in confs.items()}))
"""


def _host_conf(dp, epoch_chunks=1, eval_on_device=False,
               async_checkpoint=False, save_path=None):
    raw = {
        "task": "node_classification",
        "gnn": {"hidden": 16, "fanout": [2, 2]},
        "hyperparam": {"batch_size": 32, "num_epochs": 2, "seed": 0,
                       "sample_on_device": False, "data_parallel": dp,
                       "epoch_chunks": epoch_chunks,
                       "eval_on_device": eval_on_device,
                       "async_checkpoint": async_checkpoint},
        "input": {"dataset": "mag",
                  "dataset_conf": {"n_paper": 96, "n_author": 48}},
        "device_features": True,
        "node_classification": {},
    }
    if save_path:
        raw["output"] = {"save_model_path": save_path}
    return raw


@pytest.fixture(scope="module")
def host_dp_results(tmp_path_factory):
    ckpt = str(tmp_path_factory.mktemp("engine_dp") / "ckpt")
    confs = {
        "dp1": _host_conf(1),
        "dp8": _host_conf(8),
        "dp8_stream": _host_conf(8, epoch_chunks=2, eval_on_device=True,
                                 async_checkpoint=True, save_path=ckpt),
    }
    proc = subprocess.run(
        [sys.executable, "-c", _DP_SCRIPT % {"root": _ROOT},
         json.dumps(confs)],
        capture_output=True, text=True, timeout=900, cwd=_ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("DPRESULT:")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[0][len("DPRESULT:"):])


def test_host_dp8_loss_curve_matches_dp1(host_dp_results):
    r = host_dp_results
    # the shard_map lowering samples the GLOBAL batch once and permutes
    # it shard-major: same draws, same global masked mean, only the
    # all-reduce float summation order differs
    np.testing.assert_allclose(r["dp1"]["loss"], r["dp8"]["loss"],
                               rtol=1e-4)
    np.testing.assert_allclose(r["dp1"]["acc"], r["dp8"]["acc"], atol=0.05)


def test_host_dp8_streaming_matches_blocking(host_dp_results):
    r = host_dp_results
    # chunking + device eval + async checkpoint change nothing about the
    # training math: bit-identical to the blocking dp8 run
    np.testing.assert_array_equal(r["dp8"]["loss"], r["dp8_stream"]["loss"])
    meta = r["dp8_stream"]["ckpt_meta"]
    assert len(meta["history"]) == 2    # per-epoch checkpoint published
