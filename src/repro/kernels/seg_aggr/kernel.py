"""Pallas TPU kernel: masked fixed-fanout neighbor aggregation.

``seg_aggr`` reduces an already-gathered ``(n, fanout, d)`` block.  The
grid runs over ``(n / BLK_N, d / BLK_D)``; the full fanout axis stays in
the block, so one block's working set is ``BLK_N * fanout * BLK_D * 4B``
(128 * 32 * 128 * 4 = 2 MiB) and the fanout reduction is one VPU pass.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

BLK_N = 128
BLK_D = 128


def _seg_aggr_kernel(nbr_ref, mask_ref, out_ref, *, reduce: str):
    x = nbr_ref[...].astype(jnp.float32)       # (BLK_N, F, BLK_D)
    m = mask_ref[...].astype(jnp.float32)      # (BLK_N, F)
    s = jnp.sum(x * m[:, :, None], axis=1)     # (BLK_N, BLK_D)
    if reduce == "mean":
        cnt = jnp.maximum(jnp.sum(m, axis=1), 1.0)
        s = s / cnt[:, None]
    out_ref[...] = s.astype(out_ref.dtype)


def seg_aggr_pallas(nbr, mask, reduce: str = "mean", *,
                    interpret: Optional[bool] = None):
    n, f, d = nbr.shape
    blk_n = min(BLK_N, n)
    blk_d = min(BLK_D, d)
    grid = (pl.cdiv(n, blk_n), pl.cdiv(d, blk_d))
    return pl.pallas_call(
        functools.partial(_seg_aggr_kernel, reduce=reduce),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_n, f, blk_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((blk_n, f), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((blk_n, blk_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), nbr.dtype),
        interpret=resolve_interpret(interpret),
    )(nbr, mask)
