#!/usr/bin/env python3
"""Chip benchmark of GNN training through the ``gs`` runner.

    python3 benchmarks/chip/run.py --workload mag-nc.train --seed 7 \
        --seconds 10 --trace 0

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` the per-layer metrics and ``breakdown``), then ``checks``:
each number compared with the reference beside its limit, which also
ends standard error.  Without a TPU, or with fewer chips than the cell
asks for, it exits 1 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# modules here are imported as ``benchmarks.chip.*``; the script's own
# directory would let them shadow top-level modules (``trace``)
sys.path[:] = [os.path.join(ROOT, "src"), ROOT] + [
    p for p in sys.path if os.path.abspath(p or ".") != HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.chip import spec
    cell = spec.find_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program a run compiles, however quick, comes from the cache
    # in the checkout's later runs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchmarks.chip import harness

    def log(obj):
        print(json.dumps(obj), flush=True)

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, log=log)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
