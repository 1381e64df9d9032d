"""Streaming epoch engine: one async train/eval/checkpoint pipeline for
every feed mode (docs/pipeline.md §3f).

``StreamingEpochEngine`` runs an epoch as K chunked dispatches of the
trainer's scanned epoch program (``epoch_chunks``; chunking only splits
the scan *carry*, so losses are bit-identical to the unchunked scan for
any K) and uses JAX's async dispatch to hide every piece of host work
behind device compute:

- **next-epoch staging**: after the first chunk of epoch e is dispatched
  the host immediately samples/shuffles epoch e+1's blocks and stages
  them on the device(s), double-buffered behind the running epoch;
- **device-resident validation** (``eval_on_device``): a jitted eval
  scan accumulates the evaluator's (num, den) metric state in-jit and is
  dispatched right behind the last chunk — the host fetches two scalars
  per epoch instead of running a per-batch ``evaluate()`` loop;
- **async checkpointing** (``async_checkpoint``): a jitted device *copy*
  of the new trainer state is dispatched before the next epoch's
  donation can invalidate the live buffers, and a background
  ``AsyncCheckpointWriter`` thread performs the blocking fetch and the
  atomic ``checkpoint.io`` publish off the training thread.

The engine is feed-mode agnostic: device-sampled loaders (feed mode 3)
reuse the trainer's device epoch program verbatim; host-sampled loaders
(feed modes 1-2) are lowered through ``Trainer._host_fns_for`` — the
same scanned step / donation / data-parallel machinery over the stacked
``epoch_blocks`` pytree their loader builds.

Determinism contract: every epoch's randomness is keyed by
``(seed, epoch)`` with ``epoch = len(trainer.history)`` at entry, so a
run restored from an epoch-k checkpoint replays the original run's
batch stream from epoch k onward.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional

import jax
import numpy as np

from repro.checkpoint import AsyncCheckpointWriter
from repro.trainer import tracing


def _chunk_bounds(nb: int, k: int) -> List[tuple]:
    """Split ``nb`` scan iterations into ``k`` contiguous chunks: the
    first ``nb % k`` chunks get one extra batch, so at most two distinct
    chunk lengths exist (at most two jit cache entries of the epoch
    program; exactly one when ``k`` divides ``nb``)."""
    q, r = divmod(nb, k)
    bounds, a = [], 0
    for i in range(k):
        b = a + q + (1 if i < r else 0)
        bounds.append((a, b))
        a = b
    return bounds


class _SnapshotEmbedding:
    """state_dict()-compatible view over a snapshot's (table, gsum) pair
    so ``checkpoint.io.save_trainer`` serializes it like a live
    ``SparseEmbedding`` (pad rows stripped the same way)."""

    def __init__(self, table, gsum, num_nodes: int):
        self._table, self._gsum, self._n = table, gsum, int(num_nodes)

    def state_dict(self):
        return {"table": np.asarray(self._table)[:self._n],
                "gsum": np.asarray(self._gsum)[:self._n]}


class _TrainerSnapshot:
    """Immutable trainer view over a jitted device copy of the state:
    everything ``checkpoint.io.save_trainer`` reads, detached from the
    live (donation-recycled) training buffers so the background writer
    can fetch it while the next epoch runs."""

    def __init__(self, trainer, carry, history: List[dict]):
        self.params, self.opt_state, self.stepno, sparse = carry
        self.task = trainer.task
        self.history = history
        self.sparse_embeds = {
            nt: _SnapshotEmbedding(t, g, trainer.sparse_embeds[nt].num_nodes)
            for nt, (t, g) in sparse.items()}


class StreamingEpochEngine:
    """One streaming train/eval/checkpoint pipeline over any loader that
    exposes stacked epochs (``epoch_blocks(epoch)``).

    ``checkpoint`` is a callable taking a trainer-like snapshot (e.g.
    ``lambda t: save_trainer(t, path, cfg)``), invoked once per epoch;
    with ``async_checkpoint`` it runs on a background writer thread
    (latest-wins if epochs outrun the disk; the atomic publish in
    ``checkpoint.io`` keeps readers safe at every instant).
    """

    def __init__(self, trainer, loader, val_loader=None, *,
                 epoch_chunks: int = 1, eval_on_device: bool = False,
                 checkpoint: Optional[Callable] = None,
                 async_checkpoint: bool = False, verbose: bool = False):
        if epoch_chunks < 1:
            raise ValueError(
                f"epoch_chunks must be >= 1, got {epoch_chunks}")
        self.trainer = trainer
        self.loader = loader
        self.val_loader = val_loader
        self.epoch_chunks = int(epoch_chunks)
        self.eval_on_device = bool(eval_on_device)
        self.checkpoint = checkpoint
        self.async_checkpoint = bool(async_checkpoint)
        self.verbose = bool(verbose)
        self._fns = None
        self._eval_fns = None
        self._val_staged = None
        # one array per epoch run: the train loss of each step, in order
        self.step_losses: List[np.ndarray] = []

    # ------------------------------------------------------------------
    def _stage(self, epoch: int):
        """Build + place epoch ``epoch``'s blocks.  Pure host + transfer
        work — called right after a chunk dispatch so it overlaps the
        device running the current epoch."""
        with tracing.span("stage_epoch"):
            xs = self.loader.epoch_blocks(epoch=epoch)
            if self._fns is None:
                self._fns = self.trainer._engine_fns_for(self.loader, xs)
            if self._fns.get("prepare") is not None:
                xs = self._fns["prepare"](xs)
            return self._fns["put"](xs)

    def _stage_val(self):
        """Stage the validation epoch once (epoch-0 keyed: the val
        stream is fixed across training epochs — metrics are order- and
        batching-invariant by the evaluators' num/den contract)."""
        tr = self.trainer
        vl = self.val_loader
        if getattr(vl, "sample_on_device", False):
            tr._check_device_sampler(getattr(vl, "sampler", None))
        xs = vl.epoch_blocks(epoch=0)
        self._eval_fns = tr._eval_fns_for(vl, xs)
        self._val_staged = self._eval_fns["put"](xs)

    def _do_device_eval(self) -> bool:
        return (self.eval_on_device and self.val_loader is not None
                and self.trainer.evaluator is not None)

    def _submit_checkpoint(self, snap, writer):
        tr = self.trainer
        view = _TrainerSnapshot(tr, snap, list(tr.history))
        fn = self.checkpoint
        if writer is not None:
            writer.submit(lambda: fn(view))
        else:
            fn(view)

    # ------------------------------------------------------------------
    def run(self, num_epochs: int = 1) -> List[dict]:
        """Train ``num_epochs`` epochs; returns the trainer's history.
        Each piece of host work is a ``tracing`` span inside
        ``engine.run``."""
        with tracing.span("engine.run"):
            return self._run(num_epochs)

    def _run(self, num_epochs: int) -> List[dict]:
        tr = self.trainer
        loader = self.loader
        if getattr(loader, "sample_on_device", False):
            tr._check_device_sampler(getattr(loader, "sampler", None))
        tables = (tr.feature_store.tables
                  if tr.feature_store is not None else {})
        csr = (tr.device_sampler.tables
               if tr.device_sampler is not None else {})
        base = len(tr.history)
        writer = (AsyncCheckpointWriter()
                  if self.checkpoint is not None and self.async_checkpoint
                  else None)
        tm = jax.tree_util.tree_map
        try:
            staged = self._stage(base) if num_epochs > 0 else None
            for e in range(num_epochs):
                eidx = base + e
                fns = self._fns
                t0 = time.time()
                nb = int(loader.num_batches)
                k = min(self.epoch_chunks, nb)
                carry = (tr.params, tr.opt_state, tr.stepno,
                         tr._sparse_pack())
                parts = []
                next_staged = None
                for ci, (a, b) in enumerate(_chunk_bounds(nb, k)):
                    with tracing.span("slice_chunk"):
                        xs = tm(lambda v: v[a:b], staged)
                    with tracing.span("dispatch_epoch"):
                        out = fns["epoch"](*carry, tables, csr, xs)
                    carry, losses = tuple(out[:4]), out[4]
                    parts.append(losses)
                    if ci == 0 and e + 1 < num_epochs:
                        # dispatch returned immediately (async): sample +
                        # stage the NEXT epoch while the device runs this one
                        next_staged = self._stage(eidx + 1)
                ev = None
                if self._do_device_eval():
                    with tracing.span("eval_epoch"):
                        if self._val_staged is None:
                            self._stage_val()
                        # reads the post-epoch params (no donation):
                        # queued behind the last chunk, fetched as two
                        # scalars below
                        ev = self._eval_fns["epoch"](carry[0], carry[3],
                                                     tables, csr,
                                                     self._val_staged)
                snap = None
                if self.checkpoint is not None:
                    # jitted device copy, dispatched BEFORE the next
                    # epoch's donation can recycle the live buffers
                    with tracing.span("checkpoint"):
                        snap = tr._snapshot_fn()(carry)
                tr.params, tr.opt_state, tr.stepno, state = carry
                tr._sparse_unpack(state)
                with tracing.span("fetch_losses"):
                    losses = np.concatenate(
                        [np.asarray(p).reshape(-1) for p in parts])
                self.step_losses.append(losses)
                rec = {"epoch": eidx, "loss": float(losses.mean()),
                       "epoch_time_s": time.time() - t0}
                if ev is not None:
                    with tracing.span("eval_epoch"):
                        evaluator = tr.evaluator
                        evaluator.reset()
                        evaluator.merge(np.asarray(ev[0]),
                                        np.asarray(ev[1]))
                        rec[evaluator.name] = evaluator.value()
                elif self.val_loader is not None and tr.evaluator is not None:
                    with tracing.span("eval_epoch"):
                        rec[tr.evaluator.name] = tr.evaluate(self.val_loader)
                tr.history.append(rec)
                if self.checkpoint is not None:
                    with tracing.span("checkpoint"):
                        self._submit_checkpoint(snap, writer)
                if self.verbose:
                    print(rec)
                staged = next_staged
        finally:
            if writer is not None:
                writer.close()
        return tr.history
