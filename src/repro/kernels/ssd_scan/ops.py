"""jit'd SSD forward composed from the intra-chunk Pallas kernel plus the
(tiny) inter-chunk recurrence and off-diagonal correction in jnp."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_chunk_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_forward(x, dt, A, Bm, Cm, D=None, chunk: int = 64,
                interpret: Optional[bool] = None):
    """x: (B,S,H,P) dt: (B,S,H) A: (H,) Bm/Cm: (B,S,G,N).
    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    Bz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert S % chunk == 0
    nc = S // chunk
    rep = H // G
    gid = jnp.arange(H) // rep
    Bh = Bm[:, :, gid]  # (B,S,H,N)
    Ch = Cm[:, :, gid]

    # (B*nc, H, Q, ...) layout for the kernel grid
    xk = x.reshape(Bz, nc, chunk, H, P).transpose(0, 1, 3, 2, 4) \
          .reshape(Bz * nc, H, chunk, P)
    dtk = dt.reshape(Bz, nc, chunk, H).transpose(0, 1, 3, 2) \
            .reshape(Bz * nc, H, chunk)
    Bk = Bh.reshape(Bz, nc, chunk, H, N).transpose(0, 1, 3, 2, 4) \
           .reshape(Bz * nc, H, chunk, N)
    Ck = Ch.reshape(Bz, nc, chunk, H, N).transpose(0, 1, 3, 2, 4) \
           .reshape(Bz * nc, H, chunk, N)

    y_diag, states = ssd_chunk_pallas(xk, dtk, A, Bk, Ck,
                                      interpret=interpret)
    y_diag = y_diag.reshape(Bz, nc, H, chunk, P).transpose(0, 1, 3, 2, 4)
    states = states.reshape(Bz, nc, H, P, N)

    # ---- inter-chunk recurrence (jnp; O(nc) small tensors) -----------
    dA = (dt.astype(jnp.float32)
          * A[None, None, :]).reshape(Bz, nc, chunk, H)
    dA_cs = jnp.cumsum(dA, axis=2)
    chunk_decay = jnp.exp(dA_cs[:, :, -1])  # (B,nc,H)

    def scan_fn(carry, inp):
        st, dec = inp
        new = carry * dec[:, :, None, None] + st
        return new, carry

    s0 = jnp.zeros((Bz, H, P, N), jnp.float32)
    final, prev = jax.lax.scan(
        scan_fn, s0, (states.transpose(1, 0, 2, 3, 4),
                      chunk_decay.transpose(1, 0, 2)))
    prev = prev.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N) state entering chunk

    in_decay = jnp.exp(dA_cs)  # (B,nc,Q,H)
    Ckq = Ch.reshape(Bz, nc, chunk, H, N)
    y_off = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp",
                       Ckq.astype(jnp.float32), prev, in_decay)
    y = (y_diag.astype(jnp.float32) + y_off).reshape(Bz, S, H, P)
    if D is not None:
        y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), final
