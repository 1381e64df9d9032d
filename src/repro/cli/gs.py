"""The single GraphStorm command (paper §3.2.1): one YAML config drives
graph construction, training, and inference for every registered task.

  # train (construct->train; persists the resolved config with the model)
  PYTHONPATH=src python -m repro.cli.gs --cf examples/configs/nc_mag.yaml

  # override any config key from the command line
  PYTHONPATH=src python -m repro.cli.gs --cf nc_mag.yaml \
      --gnn.hidden 128 --hyperparam.num_epochs 2

  # inference from the saved artifact alone: hyperparameters, task, and
  # dataset all come from the persisted config — no flags to re-specify
  PYTHONPATH=src python -m repro.cli.gs --inference \
      --restore-model-path out/nc_mag

  # batched inference serving from the same artifact (docs/serving.md):
  # continuous batching + device-resident embedding cache; prints
  # p50/p99 latency, req/s, and cache hit counters
  PYTHONPATH=src python -m repro.cli.gs --serve \
      --restore-model-path out/nc_mag --serve.requests 256

  # or serve over HTTP (asyncio front end; POST /v1/infer, GET /stats)
  # with multi-replica routing and admission control
  PYTHONPATH=src python -m repro.cli.gs --serve --port 8080 \
      --restore-model-path out/nc_mag --serve.num_replicas 2 \
      --serve.max_pending_rows 256

Tasks are registry entries (repro.runner.TASK_REGISTRY):
node_classification, node_regression, edge_classification,
edge_regression, link_prediction, multi_task.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.checkpoint import load_run_config
from repro.common.compile_cache import enable_compile_cache
from repro.config import GSConfig, apply_overrides, load_config_dict
from repro.runner import TASK_REGISTRY, run_config


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        prog="python -m repro.cli.gs",
        description="single-command GraphStorm runner; any config key can "
                    "be overridden with --section.key value",
        epilog=f"registered tasks: {sorted(TASK_REGISTRY)}")
    ap.add_argument("--cf", "--yaml-config-file", dest="cf", default=None,
                    help="YAML/JSON GSConfig file")
    ap.add_argument("--inference", action="store_true",
                    help="run inference instead of training")
    ap.add_argument("--serve", action="store_true",
                    help="serve a batched inference request stream from "
                         "the restored model (serve.* config keys set the "
                         "traffic shape; docs/serving.md)")
    ap.add_argument("--port", type=int, default=None,
                    help="with --serve: bind the asyncio HTTP front end "
                         "here (0 = ephemeral) instead of running the "
                         "synthetic request stream; shorthand for "
                         "--serve.port")
    ap.add_argument("--restore-model-path", default=None,
                    help="checkpoint dir; without --cf, the config "
                         "persisted next to the model is used")
    args, overrides = ap.parse_known_args(argv)
    if args.inference and args.serve:
        ap.error("--inference and --serve are mutually exclusive")

    if args.cf:
        raw = load_config_dict(args.cf)
    elif args.restore_model_path:
        raw = load_run_config(args.restore_model_path)
    else:
        ap.error("pass --cf <config.yaml>, or --restore-model-path "
                 "<dir> to reuse the config persisted with a checkpoint")
    if args.restore_model_path:
        raw.setdefault("output", {})["restore_model_path"] = \
            args.restore_model_path
    if args.port is not None:
        if not args.serve:
            ap.error("--port requires --serve")
        raw.setdefault("serve", {})["port"] = args.port
    if overrides:
        raw = apply_overrides(raw, overrides)

    cfg = GSConfig.from_dict(raw)
    result = run_config(cfg, inference=args.inference, serve=args.serve)
    print(json.dumps(result, indent=2, default=str))
    return result


if __name__ == "__main__":
    main(sys.argv[1:])
