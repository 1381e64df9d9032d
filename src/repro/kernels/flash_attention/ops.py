"""jit'd public wrapper for flash attention (GQA-aware)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: Optional[bool] = None):
    """q: (B, H, S, Dh); k, v: (B, KV, S, Dh) with H % KV == 0."""
    H, KV = q.shape[1], k.shape[1]
    if KV != H:  # broadcast kv heads to query heads (GQA)
        rep = H // KV
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk,
                                  interpret=interpret)
