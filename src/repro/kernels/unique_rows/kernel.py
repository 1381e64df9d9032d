"""Pallas TPU kernel: distinct ranks of a sorted id vector.

The sort stays an XLA prologue and the compaction an XLA epilogue
(``ops.unique_rows``); the kernel computes, for each sorted element, its
rank among the distinct values: a run start (``s[i] != s[i-1]``) and an
inclusive prefix sum of the run starts, minus one.

The vector is laid out as ``(rows, 128)`` and walked in blocks of
``RANK_ROWS`` rows over a sequential grid.  Within a block the previous
element comes from a lane roll (and a sublane roll for lane 0), and the
prefix sum is log-step: seven lane roll-and-add passes, then the same
over the row totals along sublanes.  Two SMEM scalars carry the last
value and the running distinct count from one block to the next.  The
pad past ``n`` sorts after every real id, so it never changes a real
element's rank.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

LANES = 128
RANK_ROWS = 256


def _prefix_sum(x, axis: int):
    """Inclusive prefix sum along ``axis`` by log-step roll-and-add."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < x.shape[axis]:
        x = x + jnp.where(pos >= shift, pltpu.roll(x, shift, axis), 0)
        shift *= 2
    return x


def _rank_kernel(s_ref, rank_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        carry_ref[0] = jnp.int32(-1)      # previous value: ids are >= 0
        carry_ref[1] = jnp.int32(0)       # distinct values so far

    x = s_ref[...]                        # (rows, 128) sorted int32
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    left = pltpu.roll(x, 1, 1)            # x[r, l-1]; lane 0 wraps
    up = pltpu.roll(left, 1, 0)           # lane 0: x[r-1, 127]
    prev = jnp.where(lane == 0, up, left)
    prev = jnp.where((lane == 0) & (row == 0), carry_ref[0], prev)
    firsts = (x != prev).astype(jnp.int32)
    in_row = _prefix_sum(firsts, 1)
    totals = jnp.broadcast_to(in_row[:, LANES - 1:], x.shape)
    before = _prefix_sum(totals, 0) - totals
    rank_ref[...] = in_row + before + carry_ref[1] - 1
    carry_ref[1] = carry_ref[1] + jnp.sum(firsts)
    carry_ref[0] = jnp.max(x)


def sorted_ranks_pallas(s, *, interpret: Optional[bool] = None):
    """s: (n,) int32 non-negative ids sorted ascending -> (n,) int32 rank
    of each element among the distinct values (0-based)."""
    n = s.shape[0]
    rows = -(-n // LANES)
    blk = min(RANK_ROWS, -(-rows // 8) * 8)
    rows_pad = -(-rows // blk) * blk
    pad = rows_pad * LANES - n
    s2 = jnp.pad(s.astype(jnp.int32), (0, pad),
                 constant_values=jnp.iinfo(jnp.int32).max)
    rank = pl.pallas_call(
        _rank_kernel,
        grid=(rows_pad // blk,),
        in_specs=[pl.BlockSpec((blk, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.int32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(s2.reshape(rows_pad, LANES))
    return rank.reshape(-1)[:n]
