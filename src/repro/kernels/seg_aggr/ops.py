"""jit'd public wrapper for seg_aggr.

The kernel compiles on a TPU backend and runs the Pallas interpreter on
the CPU (``repro.kernels.backend``).  It is differentiable: the forward
pass is the kernel, and the backward pass is the VJP of the jnp oracle
in ``ref.py`` (the masked, count-scaled broadcast of the output
cotangent; XLA drops the oracle's unused forward).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.seg_aggr.kernel import seg_aggr_pallas
from repro.kernels.seg_aggr.ref import seg_aggr_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _seg_aggr(nbr, mask, reduce, interpret):
    return seg_aggr_pallas(nbr, mask, reduce=reduce, interpret=interpret)


def _seg_aggr_fwd(nbr, mask, reduce, interpret):
    return _seg_aggr(nbr, mask, reduce, interpret), (nbr, mask)


def _seg_aggr_bwd(reduce, interpret, res, g):
    nbr, mask = res
    _, vjp = jax.vjp(lambda x: seg_aggr_ref(x, mask, reduce), nbr)
    return vjp(g)[0], None


_seg_aggr.defvjp(_seg_aggr_fwd, _seg_aggr_bwd)


@functools.partial(jax.jit, static_argnames=("reduce", "interpret"))
def seg_aggr(nbr, mask, reduce: str = "mean",
             interpret: Optional[bool] = None):
    return _seg_aggr(nbr, mask, reduce, interpret)
