"""Names the training program gives its work in a profiler trace.

Device scopes (``scope``) wrap the work of the device step where that
work lives, so every lowering of the step (single device, the dp
``shard_map``, the row-sharded all-to-all path, the host-fed step) gets
them.  They are trace-time metadata only: each op of the compiled
program carries the innermost scope of the code that emitted it in its
``op_name`` (``jit(epoch)/while/body/sample/spot_target/...``), and the
backward ops of differentiated code carry their forward scope inside
``jvp(...)`` / ``transpose(...)``.  They change no computation.

Host spans (``span``) mark what the streaming epoch engine's host thread
is doing, on the profiler's own clock; with no profiler recording, one
costs a Python call.
"""
from __future__ import annotations

import jax

#: device scopes, in step order; ``gnn.layer`` is followed by the layer's
#: index (``gnn.layer0``)
SCOPES = ("expand", "sample", "spot_target", "gather.features",
          "gather.embeddings", "encode", "gnn.layer", "head", "adamw",
          "sparse_adagrad")

#: host spans of ``StreamingEpochEngine.run``; ``engine.run`` encloses
#: the others
SPANS = ("engine.run", "stage_epoch", "dispatch_epoch", "slice_chunk",
         "eval_epoch", "checkpoint", "fetch_losses")


def scope(name: str):
    """``jax.named_scope`` for one of ``SCOPES``."""
    if name.rstrip("0123456789") not in SCOPES:
        raise ValueError(f"{name!r} is not a device scope: {SCOPES}")
    return jax.named_scope(name)


def span(name: str):
    """A host span, one of ``SPANS``, written into the profiler's trace
    while it records."""
    if name not in SPANS:
        raise ValueError(f"{name!r} is not a host span: {SPANS}")
    return jax.profiler.TraceAnnotation(name)
