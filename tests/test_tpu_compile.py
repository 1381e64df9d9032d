"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode on the CPU checks what a kernel computes; only the TPU
compiler checks that it can run on the chip (tile-aligned slices, VMEM
and SMEM budgets, supported lowerings).  Each test compiles one kernel
with ``interpret=False`` for one chip of a v5e topology that is
described, not attached, at the widths of ``chip_smoke.py``'s training
run: RGCN hidden 256, fanout [10, 10], batch 1024 on an ogbn-mag-shaped
graph.  Nothing runs.

At that batch the widest edge block has 21,504 destination rows; the
largest CSR (paper-cites-paper, 6 citations per paper) holds 4,418,432
padded entries; a four-way sharded run deduplicates 164,096 paper
requests per shard.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HIDDEN = 256
FANOUT = 10
DST_ROWS = 21_504
CSR_ENTRIES = 4_418_432
SHARD_REQUESTS = 164_096


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a TPU executable cached from a CPU process cannot be read
    back here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_seg_aggr_compiles(one_chip):
    from repro.kernels.seg_aggr.kernel import seg_aggr_pallas
    _compile(lambda x, m: seg_aggr_pallas(x, m, "mean", interpret=False),
             one_chip,
             ((DST_ROWS, FANOUT, HIDDEN), jnp.float32),
             ((DST_ROWS, FANOUT), jnp.bool_))


@pytest.mark.parametrize("reduce,width", [("sum", HIDDEN), ("mean", 128),
                                          ("sum", 128)])
def test_seg_aggr_compiles_at_layer_widths(one_chip, reduce, width):
    """The other reductions and the 128-wide first layer (paper
    features and learnable embeddings)."""
    from repro.kernels.seg_aggr.kernel import seg_aggr_pallas
    _compile(lambda x, m: seg_aggr_pallas(x, m, reduce, interpret=False),
             one_chip,
             ((DST_ROWS, FANOUT, width), jnp.float32),
             ((DST_ROWS, FANOUT), jnp.bool_))


def test_nbr_sample_compiles(one_chip):
    from repro.kernels.nbr_sample.kernel import nbr_sample_pallas
    _compile(lambda b, s, d, c, e: nbr_sample_pallas(b, s, d, c, e,
                                                     interpret=False),
             one_chip,
             ((DST_ROWS, FANOUT), jnp.uint32),
             ((DST_ROWS,), jnp.int32), ((DST_ROWS,), jnp.int32),
             ((CSR_ENTRIES,), jnp.int32), ((CSR_ENTRIES,), jnp.int32))


def test_unique_rows_compiles(one_chip):
    from repro.kernels.unique_rows.kernel import sorted_ranks_pallas
    _compile(lambda s: sorted_ranks_pallas(s, interpret=False), one_chip,
             ((SHARD_REQUESTS,), jnp.int32))
