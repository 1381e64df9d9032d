"""Neighbor aggregation over padded fixed-fanout blocks.

The (num_dst, fanout, dim) masked reduction is the message-passing
hot-spot; ``repro.kernels.seg_aggr`` provides the Pallas TPU kernel and
these jnp forms are its oracle (and the CPU execution path).

Kernel routing is config-driven: ``GSConfig``'s ``gnn.use_pallas``
flows into ``GSgnnModel`` and ``gnn_apply_blocks`` scopes it around the
layer stack via ``routing(...)``.  The legacy mutable global survives
only as the *default* routing behind ``set_use_pallas`` (back-compat
shim for code that predates the config key).  Whether a routed kernel
is compiled or interpreted follows the backend
(``repro.kernels.backend``).
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

# routing stack: [-1] is active; [0] is the process default (the old
# set_use_pallas global)
_ROUTING = [False]


@contextlib.contextmanager
def routing(use_pallas: Optional[bool] = None):
    """Scope kernel routing for a model apply; ``None`` inherits the
    enclosing scope (so hand-built models keep the process default)."""
    _ROUTING.append(_ROUTING[-1] if use_pallas is None else bool(use_pallas))
    try:
        yield
    finally:
        _ROUTING.pop()


def set_use_pallas(flag: bool):
    """Back-compat shim: set the *default* routing.  New code should set
    ``gnn.use_pallas`` in GSConfig (routing then scopes per model apply)
    instead of flipping process state."""
    _ROUTING[0] = bool(flag)


def pallas_enabled() -> bool:
    return _ROUTING[-1]


def _fanout_sum(nbr_h, m):
    """Contract the fanout axis as a batched matvec (einsum) instead of
    materializing the masked (n, f, d) product — ~6x faster on CPU XLA,
    same math.  HIGHEST pins the sum to f32 whatever the process's
    default matmul precision (on TPU v5e the default form trains to
    bitwise-identical losses, so the pin costs nothing there)."""
    return jnp.einsum("nfd,nf->nd", nbr_h, m,
                      precision=jax.lax.Precision.HIGHEST)


def masked_mean(nbr_h, mask):
    """nbr_h: (n, f, d), mask: (n, f) -> (n, d)."""
    if pallas_enabled():
        from repro.kernels.seg_aggr.ops import seg_aggr
        return seg_aggr(nbr_h, mask, reduce="mean")
    m = mask.astype(nbr_h.dtype)
    return _fanout_sum(nbr_h, m) / jnp.maximum(m.sum(axis=1), 1.0)[:, None]


def masked_sum(nbr_h, mask):
    if pallas_enabled():
        from repro.kernels.seg_aggr.ops import seg_aggr
        return seg_aggr(nbr_h, mask, reduce="sum")
    return _fanout_sum(nbr_h, mask.astype(nbr_h.dtype))


def masked_max(nbr_h, mask):
    neg = jnp.full_like(nbr_h, -1e30)
    s = jnp.where(mask[..., None], nbr_h, neg).max(axis=1)
    return jnp.where(mask.any(axis=1, keepdims=True), s, 0.0)


def masked_softmax(scores, mask):
    """scores: (n, f) attention logits -> masked softmax over fanout."""
    scores = jnp.where(mask, scores, -1e30)
    att = jax.nn.softmax(scores, axis=1)
    return jnp.where(mask.any(axis=1, keepdims=True), att, 0.0)
