"""Pallas TPU kernels for the compute hot-spots.

  seg_aggr        — masked neighbor aggregation over padded fanout blocks
                    (GNN message passing; GraphStorm's per-layer hot loop)
  nbr_sample      — segmented random draw from device CSR tables
  unique_rows     — static-capacity unique (sharded-table dedup)
  flash_attention — blocked online-softmax causal attention (LM encoders)
  ssd_scan        — Mamba2 SSD intra-chunk kernel

Each kernel ships with ops.py (jit'd wrapper) and ref.py (pure-jnp
oracle used by the allclose test sweeps).  ``backend.resolve_interpret``
compiles every kernel on a TPU backend and interprets it on the CPU.
"""
