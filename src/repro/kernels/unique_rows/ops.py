"""jit'd public wrapper for unique_rows.

The dedup primitive behind the sharded-table exchange
(``hyperparam.shard_dedup`` — docs/pipeline.md §3e): collapse a
duplicate-heavy request vector to ``capacity`` fixed slots before the
:class:`~repro.common.sharding.RaggedExchange` routing, fan the gathered
rows back out with the inverse permutation after.  ``count`` signals
overflow (more distinct values than slots); callers branch to the
un-deduplicated exchange in that case, so results stay bit-identical
for every input.

The Pallas path compiles on a TPU backend and runs the interpreter on
the CPU (``repro.kernels.backend``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.unique_rows.kernel import sorted_ranks_pallas
from repro.kernels.unique_rows.ref import unique_rows_ref


# crossover between the two formulations on CPU: the dense path's
# prefix sum is O(universe), the sort path O(n log n) — past ~half a
# million universe slots the cumsum loses to the sort at exchange-sized
# request vectors, so bounded-but-huge universes (CSR position draws
# against the full edge array) fall back to the sort
DENSE_UNIVERSE_MAX = 1 << 19


def _unique_rows_dense(ids, capacity: int, universe: int):
    """Sort-free formulation for bounded ids: ``ids`` all lie in
    ``[0, universe)`` (table row ids against a known row count), so a
    presence scatter + prefix sum over the universe replaces the
    comparator sort — on CPU that is ~6x cheaper than ``argsort`` at the
    exchange's request sizes.  Bit-identical to :func:`unique_rows_ref`
    (both emit the distinct values sorted ascending with first-of-run
    rank semantics), overflow included."""
    n = ids.shape[0]
    hit = jnp.zeros((universe,), jnp.int32).at[ids].set(1)
    # associative_scan's blocked schedule beats the cumsum lowering by
    # ~30% on CPU at this size; integer adds, so the association order
    # cannot change the result
    csum = jax.lax.associative_scan(jnp.add, hit)  # rank+1 at each id
    count = csum[universe - 1]
    # k-th distinct value == first universe position whose prefix count
    # reaches k+1 (binary search; positions past count mask to the 0 pad)
    uniq = jnp.searchsorted(
        csum, jnp.arange(1, capacity + 1, dtype=jnp.int32),
        side="left").astype(jnp.int32)
    uniq = jnp.where(jnp.arange(capacity) < count, uniq, 0)
    inv = jnp.minimum(jnp.take(csum, ids) - 1, capacity - 1)
    return uniq, inv, count


@functools.partial(jax.jit,
                   static_argnames=("capacity", "universe", "use_pallas",
                                    "interpret"))
def unique_rows(ids, *, capacity: int, universe=None,
                use_pallas: bool = False, interpret: Optional[bool] = None):
    """Static-capacity unique.

    ids: (n,) non-negative int row ids ->
    (uniq (capacity,) int32, inv (n,) int32, count () int32) with
    ``uniq[inv[i]] == ids[i]`` whenever ``count <= capacity``; slots at
    and past ``count`` pad with 0 (in-bounds, dropped by ``inv``).
    ``count > capacity`` means the capacity overflowed — fall back to
    the un-deduplicated path (see ``sharding.dedup_gather``).

    ``universe`` (static): when the ids are known to lie in
    ``[0, universe)`` — always true for table row requests — the
    sort-free dense formulation runs instead of the sort-based one
    (unless the universe is so large the prefix sum would cost more
    than the sort; see ``DENSE_UNIVERSE_MAX``); results are
    bit-identical either way.

    ``use_pallas``: the distinct ranks of the sorted ids come from the
    Pallas kernel; the sort before it and the compaction after it stay
    XLA.  Bit-identical to the oracle.
    """
    ids = ids.astype(jnp.int32)
    if use_pallas:
        n = ids.shape[0]
        order = jnp.argsort(ids)               # XLA prologue (the sort)
        s = jnp.take(ids, order)
        rank = sorted_ranks_pallas(s, interpret=interpret)
        count = rank[n - 1] + 1
        slot = jnp.minimum(rank, capacity - 1)
        inv = jnp.zeros((n,), jnp.int32).at[order].set(slot)
        # first value of each slot's run, as in the oracle (overflow
        # included): min-scatter, then pad the slots past count with 0
        uniq = jnp.full((capacity,), jnp.iinfo(jnp.int32).max,
                        jnp.int32).at[slot].min(s)
        uniq = jnp.where(jnp.arange(capacity) < count, uniq, 0)
        return uniq, inv, count
    if universe is not None and int(universe) <= DENSE_UNIVERSE_MAX:
        return _unique_rows_dense(ids, capacity, int(universe))
    return unique_rows_ref(ids, capacity)
