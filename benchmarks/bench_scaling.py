"""Table 3 analogue: pipeline scalability on synthetic degree-100 graphs.

Phase timings (pre-process / partition / training) across graph sizes
scaled to CPU (the paper's 1B/10B/100B become 1e5/1e6/1e7 edges); the
derived column reports the cost growth vs the previous size — the paper's
headline is that cost grows sub-quadratically with size.

``dp/`` rows: data-parallel device-pipeline step time at 1/2/4/8 fake
CPU devices with the *global* batch held fixed (the shard_map path of
docs/pipeline.md §Data-parallel).  On the CPU each measurement runs in
a subprocess because the fake-device flag must be set before jax
imports; on real devices it runs in this process, which holds the
chips (see ``benchmarks/dp_child.py``).  On real multi-chip hardware the
speedup column is the near-linear scaling claim; on a CI box it
saturates at the physical core count — the acceptance bar is that every
sharded row is no slower than the 1-device baseline.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import dp_child
from benchmarks.common import Bench
from repro.core.dist_graph import PartitionedGraph
from repro.data import make_scaling_graph
from repro.core.embedding import SparseEmbedding
from repro.gconstruct.partition import random_partition
from repro.gnn.model import model_meta_from_graph
from repro.trainer import (GSgnnAccEvaluator, GSgnnData, GSgnnNodeDataLoader,
                           GSgnnNodeTrainer)


def _dp_child(dp: int, epochs: int, flags=(), **kw) -> dict:
    return dp_child.run(flags, dp=dp, epochs=epochs, **kw)


def _bench_data_parallel(bench: Bench, fast: bool = True):
    epochs = 6 if fast else 10   # median over epochs-1 steady epochs
    base = None
    for dp in (1, 2, 4, 8):
        r = _dp_child(dp, epochs)
        if base is None:
            base = r["step_us"]
        bench.add(f"dp/{dp}dev", r["step_us"],
                  f"speedup={base / r['step_us']:.2f}x "
                  f"loss={r['loss']:.4f} global_batch=1024")


def _bench_sharded(bench: Bench, fast: bool = True):
    """``shard/`` rows: the sharded-table step at equal global batch on
    8 fake devices, on a graph whose feature table (262k x 64 f32) is
    large enough that sharding it is the point.  ``replicated`` keeps
    every table on every shard (the memory-hungry baseline), ``gspmd``
    row-shards them and lets the compiler lower the gathers (all-gather
    fallbacks that scale with *table* size), ``alltoall`` is the explicit
    ragged-exchange fast path (traffic scales with the *frontier*, not
    the table), ``alltoall_dedup`` adds the wire-format reductions
    (in-jit frontier dedup + bf16 payloads — docs/pipeline.md §3e).
    ``exchanged_bytes_step`` and ``dedup_ratio`` are *measured* by the
    child off the actual routing (``trainer.exchange_report``: unique
    requested rows counted per shard, wire slots x wire bytes), not
    modelled from shapes.  Acceptance: alltoall beats gspmd, and
    alltoall_dedup closes the gap to replicated."""
    epochs = 4 if fast else 8
    kw = dict(n_nodes=262144, avg_degree=10)
    repl = _dp_child(8, epochs, **kw)
    bench.add("shard/replicated", repl["step_us"],
              f"loss={repl['loss']:.4f} global_batch=1024 tables=replicated")
    gspmd = _dp_child(8, epochs, flags=("shard_tables",),
                      shard_gather="gspmd", **kw)
    bench.add("shard/gspmd", gspmd["step_us"],
              f"slowdown_vs_replicated="
              f"{gspmd['step_us'] / repl['step_us']:.2f}x "
              f"loss={gspmd['loss']:.4f}")
    a2a = _dp_child(8, epochs, flags=("shard_tables",), **kw)
    bench.add("shard/alltoall", a2a["step_us"],
              f"speedup_vs_gspmd={gspmd['step_us'] / a2a['step_us']:.2f}x "
              f"gap_vs_replicated={a2a['step_us'] / repl['step_us']:.2f}x "
              f"loss={a2a['loss']:.4f} "
              f"exchanged_bytes_step={a2a['exchanged_bytes_step']} "
              f"dedup_ratio={a2a['dedup_ratio']}")
    ded = _dp_child(8, epochs, flags=("shard_tables", "shard_dedup"),
                    shard_payload_dtype="bfloat16", **kw)
    bench.add("shard/alltoall_dedup", ded["step_us"],
              f"gap_vs_replicated={ded['step_us'] / repl['step_us']:.2f}x "
              f"bytes_vs_alltoall={ded['exchanged_bytes_step'] / a2a['exchanged_bytes_step']:.2f}x "
              f"loss={ded['loss']:.4f} "
              f"exchanged_bytes_step={ded['exchanged_bytes_step']} "
              f"dedup_ratio={ded['dedup_ratio']} payload=bf16")


def _bench_link_prediction(bench: Bench, fast: bool = True):
    """``lp_host`` vs ``lp_device`` isolates the sampling location for
    the industrial LP workload (in-batch negatives): both keep features
    device-resident; lp_host draws neighborhoods + negatives in host
    numpy behind the prefetch thread, lp_device runs the fully-jitted
    task-program step (in-jit negatives, scanned epochs).  ``lp_dp/``
    rows shard that device step over 1/4/8 fake devices at equal global
    batch — the acceptance bar mirrors the node dp/ rows (no sharded row
    slower than 1 device; lp_device faster than lp_host)."""
    epochs = 4 if fast else 8
    kw = dict(task="link_prediction", n_nodes=4096, batch_size=1024,
              neg_method="joint", num_negatives=8)
    host = _dp_child(1, epochs, flags=("host_sampling",), **kw)
    bench.add("lp_host", host["step_us"],
              f"loss={host['loss']:.4f} mrr={host.get('mrr', 0):.4f} "
              f"neg=joint global_batch=1024")
    dev = _dp_child(1, epochs, **kw)
    bench.add("lp_device", dev["step_us"],
              f"speedup={host['step_us'] / dev['step_us']:.2f}x_vs_host "
              f"loss={dev['loss']:.4f} mrr={dev.get('mrr', 0):.4f}")
    base = dev["step_us"]
    bench.add("lp_dp/1dev", dev["step_us"],
              f"speedup=1.00x loss={dev['loss']:.4f} global_batch=1024")
    for dp in (4, 8):
        r = _dp_child(dp, epochs, **kw)
        bench.add(f"lp_dp/{dp}dev", r["step_us"],
                  f"speedup={base / r['step_us']:.2f}x "
                  f"loss={r['loss']:.4f} global_batch=1024")


def run_smoke(bench: Bench):
    """CI smoke: the 1-vs-8-device data-parallel rows at tiny size —
    proves the sharded step trains end to end and keeps the dp/ rows
    exercised on every push (loss parity is the tier-1 tests' job).
    The lp_dp/ pair does the same for the link-prediction device step
    (in-jit negatives + the sharded in-batch score matrix)."""
    base = None
    for dp in (1, 8):
        r = _dp_child(dp, epochs=2, n_nodes=2048, batch_size=512)
        if base is None:
            base = r["step_us"]
        bench.add(f"dp/{dp}dev", r["step_us"],
                  f"speedup={base / r['step_us']:.2f}x "
                  f"loss={r['loss']:.4f} global_batch=512")
    base = None
    for dp in (1, 8):
        r = _dp_child(dp, epochs=2, task="link_prediction",
                      n_nodes=2048, batch_size=512)
        if base is None:
            base = r["step_us"]
        bench.add(f"lp_dp/{dp}dev", r["step_us"],
                  f"speedup={base / r['step_us']:.2f}x "
                  f"loss={r['loss']:.4f} mrr={r.get('mrr', 0):.4f} "
                  f"global_batch=512")
    # sharded-table lane: both gather strategies train end to end at 8
    # devices (the alltoall-vs-gspmd timing claim is the full bench's job)
    g = _dp_child(8, epochs=2, n_nodes=2048, batch_size=512,
                  flags=("shard_tables",), shard_gather="gspmd")
    bench.add("shard/gspmd", g["step_us"],
              f"loss={g['loss']:.4f} global_batch=512")
    a = _dp_child(8, epochs=2, n_nodes=2048, batch_size=512,
                  flags=("shard_tables",))
    bench.add("shard/alltoall", a["step_us"],
              f"speedup_vs_gspmd={g['step_us'] / a['step_us']:.2f}x "
              f"loss={a['loss']:.4f} global_batch=512")
    # wire-format lane: dedup + bf16 payloads train end to end and the
    # measured probe sees actual duplicate collapse (CI asserts the
    # printed dedup_ratio < 1.0)
    d = _dp_child(8, epochs=2, n_nodes=2048, batch_size=512,
                  flags=("shard_tables", "shard_dedup"),
                  shard_payload_dtype="bfloat16")
    bench.add("shard/alltoall_dedup", d["step_us"],
              f"loss={d['loss']:.4f} "
              f"exchanged_bytes_step={d['exchanged_bytes_step']} "
              f"dedup_ratio={d['dedup_ratio']} payload=bf16 "
              f"global_batch=512")


def run(bench: Bench, fast: bool = True):
    _bench_data_parallel(bench, fast)
    _bench_sharded(bench, fast)
    _bench_link_prediction(bench, fast)
    sizes = [(1_000, 100), (10_000, 100)] if fast else \
        [(1_000, 100), (10_000, 100), (100_000, 100)]
    prev = {}
    for n_nodes, deg in sizes:
        tag = f"{n_nodes * deg // 1000}k-edges"
        t0 = time.time()
        g = make_scaling_graph(n_nodes, avg_degree=deg, seed=0)
        t_pre = time.time() - t0

        t0 = time.time()
        assign = random_partition(g, 8, seed=0)
        pg = PartitionedGraph(g, assign, 8)
        t_part = time.time() - t0

        data = GSgnnData(g)
        tr = np.arange(int(0.8 * n_nodes))
        model = model_meta_from_graph(g, "gcn", 64, 1)
        trainer = GSgnnNodeTrainer(model, "node", num_classes=16, lr=1e-2,
                                   evaluator=GSgnnAccEvaluator())
        loader = GSgnnNodeDataLoader(data, "node", tr, [5], 1024)
        t0 = time.time()
        n_batches = 0
        for batch in loader:
            trainer.fit_batch(batch)
            n_batches += 1
            if n_batches >= 20:
                break
        t_train = time.time() - t0

        for phase, t in (("preprocess", t_pre), ("partition", t_part),
                         ("train20b", t_train)):
            growth = ""
            if phase in prev:
                growth = f"growth_x={t / max(prev[phase], 1e-9):.1f}"
            prev[phase] = t
            bench.add(f"t3/{tag}/{phase}", t * 1e6, growth)
