"""The static-capacity unique primitive behind shard_dedup
(``kernels/unique_rows`` — docs/pipeline.md §3e): jnp oracle semantics,
oracle-vs-Pallas-kernel bitwise parity (interpreted on the CPU), and the
overflow contract the in-jit exchange fallback relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.unique_rows import unique_rows, unique_rows_ref


def _check_contract(ids, capacity):
    uniq, inv, count = unique_rows(jnp.asarray(ids, jnp.int32),
                                   capacity=capacity)
    uniq, inv, count = map(np.asarray, (uniq, inv, count))
    expect = np.unique(np.asarray(ids))
    assert count == len(expect)
    if count <= capacity:
        # distinct values sorted ascending, compacted to the front
        np.testing.assert_array_equal(uniq[:count], expect)
        # pad slots hold 0 (always a legal row id to gather)
        np.testing.assert_array_equal(uniq[count:], 0)
        # the fan-out mapping reconstructs the request vector exactly
        np.testing.assert_array_equal(uniq[inv], np.asarray(ids))
    return uniq, inv, count


def test_basic_dedup():
    uniq, inv, count = _check_contract([7, 3, 7, 7, 3, 9, 0, 9], capacity=8)
    assert count == 4


def test_all_duplicates():
    uniq, inv, count = _check_contract([5] * 16, capacity=2)
    assert count == 1
    np.testing.assert_array_equal(np.asarray(inv), 0)


def test_all_distinct_exact_fit():
    _check_contract(np.arange(31, -1, -1), capacity=32)


def test_overflow_reports_count():
    # more distinct values than slots: count signals the overflow so the
    # caller can fall back; uniq/inv need not reconstruct
    _, _, count = unique_rows(jnp.arange(16, dtype=jnp.int32), capacity=8)
    assert int(count) == 16 > 8


@pytest.mark.parametrize("n,capacity,hi", [
    (64, 64, 16),      # duplicate-heavy, fits
    (64, 56, 1 << 20), # sparse ids, overflows
    (128, 96, 40),     # borderline either way per draw
    (1, 1, 4),
])
def test_oracle_vs_kernel_bitwise(n, capacity, hi):
    rng = np.random.default_rng(n * 31 + capacity)
    for trial in range(8):
        ids = jnp.asarray(rng.integers(0, hi, size=n), jnp.int32)
        ref = unique_rows(ids, capacity=capacity, use_pallas=False)
        ker = unique_rows(ids, capacity=capacity, use_pallas=True,
                          interpret=True)
        for a, b in zip(ref, ker):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n,capacity,universe", [
    (64, 64, 16),       # duplicate-heavy, fits
    (64, 56, 4096),     # sparse ids, overflows
    (128, 96, 160),     # borderline either way per draw
    (1, 1, 4),
])
def test_sort_vs_dense_universe_bitwise(n, capacity, universe):
    # the sort-free dense formulation (what dedup_gather runs: ids
    # bounded by the padded row count) must match the sort-based oracle
    # bit for bit, overflow included
    rng = np.random.default_rng(n * 17 + capacity)
    for trial in range(8):
        ids = jnp.asarray(rng.integers(0, universe, size=n), jnp.int32)
        ref = unique_rows(ids, capacity=capacity)
        dense = unique_rows(ids, capacity=capacity, universe=universe)
        for a, b in zip(ref, dense):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ref_matches_public_wrapper():
    ids = jnp.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], jnp.int32)
    for a, b in zip(unique_rows_ref(ids, 8), unique_rows(ids, capacity=8)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_jit_and_grad_free_shapes():
    # scan-safety: the op jits with static capacity and fixed shapes
    f = jax.jit(lambda x: unique_rows(x, capacity=4))
    uniq, inv, count = f(jnp.asarray([2, 2, 2, 8], jnp.int32))
    assert uniq.shape == (4,) and inv.shape == (4,) and count.shape == ()
