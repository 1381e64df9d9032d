"""Pallas TPU kernel: segmented random-gather for neighbor sampling.

Layout: each dst row owns a CSR segment ``[starts[i], starts[i]+degs[i])``
of the per-etype ``col_idx``/``edge_id`` tables and draws ``fanout``
entries with replacement from pre-generated uniform bits.

The tables stay in HBM: at ogbn-mag scale one etype holds millions of
edges, far more than VMEM.  Each grid step takes ``SAMPLE_ROWS`` dst rows
whose bits and segment bounds arrive as SMEM blocks.  The draw is scalar
work: ``pos = start + bits % deg`` per slot.  The tables are viewed as
``(E / 128, 1, 128)``, a free reshape of the padded 1-D array whose rows
are single 128-entry lines, so one DMA per table moves the line holding
``pos`` into SMEM and the entry is read from there.  Groups of
``SAMPLE_GROUP`` rows are double-buffered: one group's DMAs are in flight
while the previous group's entries are read out.  Rows past ``n`` are
padding (degree 0) and are sliced off.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

SAMPLE_ROWS = 128
SAMPLE_GROUP = 8
LINE = 128


def _nbr_sample_kernel(bits_ref, seg_ref, cols_hbm, eids_hbm, nbr_ref,
                       eid_ref, cbuf, ebuf, sem, *, group: int,
                       num_edges: int):
    rows, f = bits_ref.shape
    n_groups = rows // group

    def pos_of(r, k):
        deg = jnp.maximum(seg_ref[r, 1], 1).astype(jnp.uint32)
        draw = (bits_ref[r, k] % deg).astype(jnp.int32)
        return jnp.clip(seg_ref[r, 0] + draw, 0, num_edges - 1)

    def line_copies(slot, j, pos):
        line = pos // LINE
        return (pltpu.make_async_copy(cols_hbm.at[pl.ds(line, 1)],
                                      cbuf.at[slot, pl.ds(j, 1)],
                                      sem.at[slot]),
                pltpu.make_async_copy(eids_hbm.at[pl.ds(line, 1)],
                                      ebuf.at[slot, pl.ds(j, 1)],
                                      sem.at[slot]))

    def issue(g, slot):
        def one(j, carry):
            for cp in line_copies(slot, j, pos_of(g * group + j // f, j % f)):
                cp.start()
            return carry
        jax.lax.fori_loop(0, group * f, one, 0)

    def drain(slot):
        def one(j, carry):
            for cp in line_copies(slot, j, 0):
                cp.wait()
            return carry
        jax.lax.fori_loop(0, group * f, one, 0)

    def read_out(g, slot):
        def one(j, carry):
            r, k = g * group + j // f, j % f
            lane = pos_of(r, k) % LINE
            nbr_ref[r, k] = cbuf[slot, j, 0, lane]
            eid_ref[r, k] = ebuf[slot, j, 0, lane]
            return carry
        jax.lax.fori_loop(0, group * f, one, 0)

    issue(0, 0)

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _():
            issue(g + 1, 1 - slot)

        drain(slot)
        read_out(g, slot)
        return carry

    jax.lax.fori_loop(0, n_groups, body, 0)


def nbr_sample_pallas(bits, starts, degs, col_idx, edge_id, *,
                      interpret: Optional[bool] = None):
    """bits: (n, f) uint32; starts/degs: (n,) int32; col_idx/edge_id: (E,)
    -> (nbr (n,f) int32, eid (n,f) int32, mask (n,f) bool)."""
    n, f = bits.shape
    num_edges = col_idx.shape[0]
    e_pad = -(-num_edges // LINE) * LINE

    def lines(t):
        t = t.astype(jnp.int32)
        if e_pad != num_edges:
            t = jnp.pad(t, (0, e_pad - num_edges))
        return t.reshape(e_pad // LINE, 1, LINE)

    rows = min(SAMPLE_ROWS, -(-n // SAMPLE_GROUP) * SAMPLE_GROUP)
    n_pad = -(-n // rows) * rows
    seg = jnp.stack([starts.astype(jnp.int32), degs.astype(jnp.int32)],
                    axis=1)
    seg = jnp.pad(seg, ((0, n_pad - n), (0, 0)))
    bits = jnp.pad(bits, ((0, n_pad - n), (0, 0)))

    def smem(width):
        return pl.BlockSpec((rows, width), lambda i: (i, 0),
                            memory_space=pltpu.SMEM)

    line_buf = pltpu.SMEM((2, SAMPLE_GROUP * f, 1, LINE), jnp.int32)
    nbr, eid = pl.pallas_call(
        functools.partial(_nbr_sample_kernel, group=SAMPLE_GROUP,
                          num_edges=num_edges),
        grid=(n_pad // rows,),
        in_specs=[smem(f), smem(2),
                  pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=[smem(f), smem(f)],
        out_shape=[jax.ShapeDtypeStruct((n_pad, f), jnp.int32),
                   jax.ShapeDtypeStruct((n_pad, f), jnp.int32)],
        scratch_shapes=[line_buf, line_buf, pltpu.SemaphoreType.DMA((2,))],
        interpret=resolve_interpret(interpret),
    )(bits, seg, lines(col_idx), lines(edge_id))
    mask = jnp.broadcast_to((degs > 0)[:, None], (n, f))
    return nbr[:n], eid[:n], mask
