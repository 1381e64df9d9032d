"""One training measurement for the data-parallel / LP / stream rows of
``bench_scaling`` and ``bench_stream``.

``run(flags, **kw)`` takes the measurement where the devices are.  On
real accelerators it runs in the calling process: a chip belongs to one
process, and the bench parent already holds it.  On the CPU it starts
``python -m benchmarks.dp_child`` in a fresh process, because
``--xla_force_host_platform_device_count`` (the fake devices the dp rows
need) must be set before the first jax import.  The child prints one
``DPRESULT:{json}`` line: median steady-state seconds per step (epoch 0
compiles and is discarded) and the final loss, so the parent can assert
loss parity across shard counts as well as timing.

``--task link_prediction`` measures the LP device step (negatives drawn
in-jit, in-batch ``B x B`` scoring per shard against the all-gathered
global dst set); ``--host-sampling`` instead runs the host-sampled
baseline (feed mode 2: device-resident features, numpy neighbor +
negative sampling behind the prefetch thread) for the
``lp_host``-vs-``lp_device`` comparison.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, required=True)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--n-nodes", type=int, default=8192)
    ap.add_argument("--avg-degree", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--shard-tables", action="store_true")
    ap.add_argument("--shard-gather", default="alltoall",
                    choices=["alltoall", "gspmd"],
                    help="sharded-table gather strategy (shard/ rows "
                         "compare the two at equal global batch)")
    ap.add_argument("--remote-prefetch", type=int, default=1)
    ap.add_argument("--shard-dedup", action="store_true",
                    help="collapse duplicate row requests per shard "
                         "before the alltoall routing (in-jit unique_rows "
                         "+ overflow fallback — bit-identical results)")
    ap.add_argument("--shard-payload-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="wire dtype for gathered float payloads on the "
                         "alltoall path (bf16 halves exchange bytes)")
    ap.add_argument("--task", default="node_classification",
                    choices=["node_classification", "link_prediction"])
    ap.add_argument("--host-sampling", action="store_true",
                    help="host-sampled baseline (feed mode 2) instead of "
                         "the fully-jitted device step")
    ap.add_argument("--neg-method", default="in_batch")
    ap.add_argument("--num-negatives", type=int, default=8)
    # streaming epoch engine knobs (docs/pipeline.md §3f)
    ap.add_argument("--epoch-chunks", type=int, default=1)
    ap.add_argument("--eval-on-device", action="store_true")
    ap.add_argument("--async-checkpoint", action="store_true")
    ap.add_argument("--save-model-path", default=None,
                    help="checkpoint dir: enables the per-epoch engine "
                         "checkpoint (sync unless --async-checkpoint)")
    ap.add_argument("--timed-epochs", type=int, default=0,
                    help="after the warm-up train() (compiles every "
                         "program), time this many additional epochs "
                         "end to end — train + eval + checkpoint wall "
                         "clock per epoch goes out as epoch_wall_us")
    return ap.parse_args(argv)


def measure(args) -> dict:
    """Train one config and return its DPRESULT record."""
    import numpy as np

    from repro.config import GSConfig
    from repro.runner import TASK_REGISTRY, build_graph

    raw = {
        "task": args.task,
        "device_features": True,
        "gnn": {"model": "gcn", "hidden": args.hidden, "num_layers": 2,
                "fanout": [5, 5]},
        "hyperparam": {"batch_size": args.batch_size,
                       "num_epochs": args.epochs, "seed": 0,
                       "sample_on_device": not args.host_sampling,
                       "data_parallel": args.dp,
                       "shard_tables": args.shard_tables,
                       "shard_gather": args.shard_gather,
                       "remote_prefetch": args.remote_prefetch,
                       "shard_dedup": args.shard_dedup,
                       "shard_payload_dtype": args.shard_payload_dtype,
                       "epoch_chunks": args.epoch_chunks,
                       "eval_on_device": args.eval_on_device,
                       "async_checkpoint": args.async_checkpoint},
        "input": {"dataset": "scaling",
                  "dataset_conf": {"n_nodes": args.n_nodes,
                                   "avg_degree": args.avg_degree}},
    }
    if args.task == "link_prediction":
        raw["link_prediction"] = {"neg_method": args.neg_method,
                                  "num_negatives": args.num_negatives}
    else:
        raw["node_classification"] = {}
    if args.save_model_path:
        raw["output"] = {"save_model_path": args.save_model_path}
    cfg = GSConfig.from_dict(raw).resolved()
    runner = TASK_REGISTRY[cfg.task](cfg, build_graph(cfg))
    hist = runner.train()["history"]
    epoch_wall_us = None
    if args.timed_epochs:
        # every program is now compiled (same schemas -> trainer._steps
        # cache hits); time full epochs end to end — staging + train +
        # eval + checkpoint — through the same fit path train() used
        import time
        ids, va, _ = runner.data.train_val_test_nodes(
            runner.target_ntype, rng=runner._split_rng())
        t0 = time.time()
        runner.trainer.fit(runner._train_loader(ids),
                           runner._loader(va, False),
                           num_epochs=args.timed_epochs,
                           **runner._fit_kwargs())
        epoch_wall_us = (time.time() - t0) / args.timed_epochs * 1e6
    if args.task == "link_prediction":
        n_items = len(runner.tr_e)
        n_batches = n_items // args.batch_size   # LP drops the ragged tail
    else:
        n_batches = -(-int(0.8 * args.n_nodes) // args.batch_size)
    # epoch_time_s covers only the training epoch (eval excluded);
    # min over steady epochs: robust to contention spikes on shared CI
    # boxes (epoch 0 compiles and is discarded)
    step_s = float(np.min([h["epoch_time_s"] for h in hist[1:]])
                   ) / n_batches
    out = {"dp": args.dp, "step_us": step_s * 1e6,
           "loss": hist[-1]["loss"], "n_batches": n_batches}
    if (args.shard_tables and args.shard_gather == "alltoall"
            and not args.host_sampling
            and args.task == "node_classification"):
        # measured wire stats of one training batch (replaces the old
        # analytic byte model): unique requested rows counted per shard
        # straight off the routing — see trainers.exchange_report
        ids, _, _ = runner.data.train_val_test_nodes(
            runner.target_ntype, rng=runner._split_rng())
        rep = runner.trainer.exchange_report(runner._train_loader(ids))
        out["exchanged_bytes_step"] = rep["exchanged_bytes_step"]
        out["dedup_ratio"] = round(rep["dedup_ratio"], 4)
    if epoch_wall_us is not None:
        out["epoch_wall_us"] = epoch_wall_us
    metric = runner.trainer.evaluator.name
    if metric in hist[-1]:
        out[metric] = hist[-1][metric]
    return out


def run(flags=(), **kw) -> dict:
    """Measure one config (``flags``: store-true options, ``kw``: valued
    options, both by their argument names)."""
    argv = [f"--{f.replace('_', '-')}" for f in flags]
    for k, v in kw.items():
        argv += [f"--{k.replace('_', '-')}", str(v)]
    import jax
    if jax.default_backend() != "cpu":
        return measure(parse_args(argv))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-m", "benchmarks.dp_child"]
                         + argv, capture_output=True, text=True,
                         timeout=1200, env=env)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("DPRESULT:")]
    assert lines, (out.returncode, out.stderr[-2000:])
    return json.loads(lines[0][len("DPRESULT:"):])


def main():
    args = parse_args()
    # CPU only: fake devices for the dp rows (set before jax is imported)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.devices}")
    print("DPRESULT:" + json.dumps(measure(args)))


if __name__ == "__main__":
    sys.exit(main())
