"""Pallas TPU kernel: blocked causal flash attention (forward).

Grid: (B, H, Sq/BQ, Sk/BK) with the KV axis innermost; the running
softmax state (m, l, acc) lives in VMEM scratch and carries across KV
steps, so HBM traffic is one pass over Q/K/V and one write of O.

Tiling: BQ x Dh and BK x Dh tiles are MXU-aligned (block sizes are
multiples of 128 when the dims allow); VMEM working set is
BQ*Dh + BK*Dh + BQ*BK + BQ*Dh(acc) floats ≈ 0.5 MiB at 128/128/128.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, bq: int, bk: int, scale: float,
                  kv_steps: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale      # (BQ, Dh)
    k = k_ref[0, 0].astype(jnp.float32)              # (BK, Dh)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (BQ, BK)

    if causal:
        q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == kv_steps - 1)
    def _final():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                           interpret: Optional[bool] = None):
    B, H, S, Dh = q.shape
    Sk = k.shape[2]
    bq = min(bq, S)
    bk = min(bk, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, Sk, bq, bk)
    kv_steps = Sk // bk
    grid = (B, H, S // bq, kv_steps)
    scale = Dh ** -0.5
    return pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, bq=bq, bk=bk,
                          scale=scale, kv_steps=kv_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
