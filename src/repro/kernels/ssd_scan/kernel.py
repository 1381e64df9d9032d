"""Pallas TPU kernel: Mamba2 SSD intra-chunk block.

Per grid cell (one batch·chunk element × one head) the kernel computes
the quadratic intra-chunk output and the chunk's outgoing state:

    L      = exp(segsum(dt*A))          (Q, Q) lower-triangular decay
    y_diag = ((C Bᵀ) ∘ L ∘ dt) x        (Q, P)
    state  = (exp(dA_last - dA_cs) ∘ dt ∘ x)ᵀ B   (P, N)

VMEM working set at Q=256, P=64, N=128:
    x (Q,P) + B/C (Q,N) + CB/L (Q,Q) + state (P,N) ≈ 0.6 MiB.
The (Q,Q) and (Q,P)/(P,N) contractions are MXU matmuls; the cumulative
decay is a VPU cumsum.  The inter-chunk recurrence (tiny, O(chunks))
stays in jnp — see ops.ssd_forward.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref):
    x = x_ref[0, 0].astype(jnp.float32)     # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)   # (Q,)
    A = a_ref[0].astype(jnp.float32)        # scalar
    Bm = b_ref[0, 0].astype(jnp.float32)    # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)    # (Q, N)
    Q = x.shape[0]

    dA = dt * A                              # (Q,) negative
    cs = jnp.cumsum(dA)                      # (Q,)
    seg = cs[:, None] - cs[None, :]          # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(ii >= jj, jnp.exp(seg), 0.0)

    CB = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)  # (Q, Q)
    M = CB * L * dt[None, :]
    y = jnp.dot(M, x, preferred_element_type=jnp.float32)       # (Q, P)

    w = jnp.exp(cs[-1] - cs) * dt                               # (Q,)
    st = jnp.dot((w[:, None] * x).T, Bm,
                 preferred_element_type=jnp.float32)            # (P, N)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = st


def ssd_chunk_pallas(x, dt, A, Bh, Ch, *, interpret: Optional[bool] = None):
    """x: (BN,H,Q,P) dt: (BN,H,Q) A: (H,) Bh/Ch: (BN,H,Q,N)
    -> y_diag (BN,H,Q,P), states (BN,H,P,N)."""
    BN, H, Q, P = x.shape
    N = Bh.shape[-1]
    grid = (BN, H)
    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, h: (b, h, 0)),
            pl.BlockSpec((1,), lambda b, h: (h,)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, H, Q, P), x.dtype),
            jax.ShapeDtypeStruct((BN, H, P, N), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, dt, A, Bh, Ch)
