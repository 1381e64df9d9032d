"""Pure-jnp oracle for the seg_aggr kernel."""
from __future__ import annotations

import jax.numpy as jnp


def seg_aggr_ref(nbr, mask, reduce: str = "mean"):
    """nbr: (n, f, d); mask: (n, f) -> (n, d)."""
    m = mask[..., None].astype(nbr.dtype)
    s = (nbr * m).sum(axis=1)
    if reduce == "sum":
        return s
    if reduce == "mean":
        return s / jnp.maximum(m.sum(axis=1), 1.0)
    raise ValueError(reduce)
