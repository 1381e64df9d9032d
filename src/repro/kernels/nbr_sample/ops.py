"""jit'd public wrapper for nbr_sample.

The random stream is counter-based: callers derive a fresh
``jax.random`` key per (step, layer, edge-block) with ``fold_in``, the
wrapper turns it into one uniform 32-bit word per (dst, fanout) slot, and
the kernel/oracle map words onto CSR segments.  A config seed therefore
fully determines the sample stream, on any backend, inside or outside
jit.

With ``use_pallas`` the kernel compiles on a TPU backend and runs the
Pallas interpreter on the CPU (``repro.kernels.backend``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.nbr_sample.kernel import nbr_sample_pallas
from repro.kernels.nbr_sample.ref import nbr_sample_ref, segment_bounds_ref


@functools.partial(jax.jit,
                   static_argnames=("fanout", "use_pallas", "interpret"))
def nbr_sample(row_ptr, col_idx, edge_id, dst_ids, key, *, fanout: int,
               use_pallas: bool = False, interpret: Optional[bool] = None,
               bits=None):
    """Draw ``fanout`` in-neighbors per dst id from a device CSR.

    row_ptr: (num_dst+1,) int32; col_idx/edge_id: (E,) int32 padded
    tables; dst_ids: (n,) int; key: jax PRNG key ->
    (nbr (n, fanout) int32, eid (n, fanout) int32, mask (n, fanout) bool).
    Rows with degree 0 are fully masked (and gather row 0, discarded).

    ``bits`` overrides the uniform words (one per (dst, fanout) slot).
    Data-parallel shards pass the rows of the *global* batch's bit
    array that belong to them, so the union of all shards' draws is
    bit-identical to the single-device draw of the global batch.
    """
    starts, degs = segment_bounds_ref(row_ptr, dst_ids)
    if bits is None:
        bits = jax.random.bits(key, (dst_ids.shape[0], fanout), jnp.uint32)
    if use_pallas:
        return nbr_sample_pallas(bits, starts, degs, col_idx, edge_id,
                                 interpret=interpret)
    return nbr_sample_ref(bits, starts, degs, col_idx, edge_id)
