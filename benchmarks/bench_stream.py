"""Streaming epoch engine (docs/pipeline.md §3f): epoch wall-clock at
equal work on the 8-fake-device rig.

Three rows, identical training/eval/checkpoint workload (host-sampled
feed mode 2, dp=8 through the shard_map lowering, validation every
epoch, a checkpoint published every epoch), differing only in how much
of the engine's overlap machinery is on:

- ``stream/blocking`` — ``epoch_chunks=1``, host per-batch validation,
  synchronous checkpoint write on the training thread.
- ``stream/chunked``  — ``epoch_chunks=4``: the epoch scan is split into
  4 dispatches (bit-identical losses), freeing the host earlier between
  segments.
- ``stream/overlap``  — chunked + ``eval_on_device`` (validation is a
  jitted (num, den) scan over a once-staged val epoch instead of a
  host re-sample + per-batch loop every epoch) + ``async_checkpoint``
  (fetch + atomic write on the background writer thread).

Each measurement warms up with ``runner.train()`` (compiles every
program), then times ``--timed-epochs`` full epochs end to end —
staging + train + eval + checkpoint (``benchmarks/dp_child.py``).  The
derived ``overlap_efficiency`` column on ``stream/overlap`` is
``blocking_wall / overlap_wall``; the acceptance bar is overlap epoch
wall-clock <= 0.9x blocking (efficiency >= 1.11) at equal work.
"""
from __future__ import annotations

import os
import tempfile

from benchmarks.common import Bench
from benchmarks.dp_child import run as _child


def _stream_rows(bench: Bench, n_nodes: int, batch: int, warm: int,
                 timed: int):
    base = dict(dp=8, epochs=warm, timed_epochs=timed, n_nodes=n_nodes,
                batch_size=batch)
    with tempfile.TemporaryDirectory() as td:
        blocking = _child(flags=("host_sampling",),
                          save_model_path=os.path.join(td, "blk"), **base)
        chunked = _child(flags=("host_sampling",), epoch_chunks=4,
                         save_model_path=os.path.join(td, "chk"), **base)
        overlap = _child(flags=("host_sampling", "eval_on_device",
                                "async_checkpoint"), epoch_chunks=4,
                         save_model_path=os.path.join(td, "ovl"), **base)
    t_blk = blocking["epoch_wall_us"]
    t_chk = chunked["epoch_wall_us"]
    t_ovl = overlap["epoch_wall_us"]
    bench.add("stream/blocking", t_blk,
              f"loss={blocking['loss']:.4f} global_batch={batch} "
              f"dp=8 ckpt=sync eval=host")
    bench.add("stream/chunked", t_chk,
              f"ratio_vs_blocking={t_chk / t_blk:.2f}x "
              f"loss={chunked['loss']:.4f} epoch_chunks=4")
    bench.add("stream/overlap", t_ovl,
              f"overlap_efficiency={t_blk / t_ovl:.2f} "
              f"ratio_vs_blocking={t_ovl / t_blk:.2f}x "
              f"loss={overlap['loss']:.4f} "
              f"epoch_chunks=4 eval=device ckpt=async")


def run_smoke(bench: Bench):
    """CI smoke: all three engine configurations train + eval +
    checkpoint end to end at tiny size on 8 fake devices (the <= 0.9x
    wall-clock claim is the full bench's job — tiny epochs are noise)."""
    _stream_rows(bench, n_nodes=2048, batch=512, warm=2, timed=2)


def run(bench: Bench, fast: bool = True):
    if fast:
        _stream_rows(bench, n_nodes=8192, batch=512, warm=2, timed=4)
    else:
        _stream_rows(bench, n_nodes=32768, batch=1024, warm=2, timed=6)
