#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 benchmarks/chip/control.py --workload mag-nc.train \\
        --seeds 101 102 103 --variant-seeds 101 102 103 \\
        --out chiprun_out/mag-nc.train.readings.jsonl

For each seed: the cell's graph, weights and runner as a benchmark run
builds them, and the first epoch through the same engine and program
(``harness.first_epoch``).  The program's numbers (``harness.gaps``)
against the reference at the configuration's precision are the sound
readings.  On each of ``--variant-seeds`` the reference also runs in the
program's place: with its products one precision lower (the control),
with its weights, tables and features stored in bfloat16 (storage one
step below), and carrying each planted fault (``reference.FAULTS``).
Each reading goes through ``harness.check`` against the cell's limits,
so each line says whether that run would be ``correct``.  Prints one
JSON line per seed (also appended to ``--out``) and a summary: the
largest sound reading and the smallest reading of each variant.  The
benchmark's own runs do not run this.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:] = [os.path.join(ROOT, "src"), ROOT] + [
    p for p in sys.path if os.path.abspath(p or ".") != HERE]


def variants(cell) -> list:
    """``(name, run_reference keywords)`` of each run in the program's
    place."""
    from benchmarks.chip import reference
    out = [("control", {"products": cell.config["reference"]["control"]}),
           ("storage_bfloat16", {"storage": "bfloat16"})]
    has_tables = any(nt not in cell.config["graph"]["features"]
                     for nt in cell.config["graph"]["num_nodes"])
    for f in reference.FAULTS:
        if f and (f != "frozen_tables" or has_tables):
            out.append((f, {"fault": f}))
    return out


def readings(cell, seed: int, with_variants=True) -> dict:
    """Every reading of one seed: the program's and each variant's
    compared numbers, each with ``correct`` by the cell's limits."""
    from benchmarks.chip import harness
    spans = harness.Spans()
    gd, pseed, runner, loader, engine, first = harness.prepare(
        cell, seed, spans)
    program = harness.first_epoch(engine, runner)
    del runner, loader, engine
    harness.free_device_memory()
    ref = harness.run_reference(cell, gd, pseed, first)

    def judged(run):
        detail = {}
        numbers = harness.gaps(run, ref, detail)
        checks = harness.check(cell, numbers)
        return {"numbers": numbers, "correct": harness.passes(checks),
                "worst_leaves": detail,
                "losses": [run["losses"][0], run["losses"][-1]]}
    out = {"seed": seed, "reference_losses": [ref["losses"][0],
                                              ref["losses"][-1]],
           "sound": judged(program)}
    del program
    for name, kw in variants(cell) if with_variants else ():
        run = harness.run_reference(cell, gd, pseed, first, **kw)
        out[name] = judged(run)
        del run
    del ref
    harness.free_device_memory()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variant-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmarks.chip import spec
    from repro.common.compile_cache import enable_compile_cache
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.find_cell(args.workload)
    rows = []
    for seed in args.seeds:
        t = time.time()
        row = readings(cell, seed, seed in args.variant_seeds)
        row["seconds"] = time.time() - t
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    summary = {}
    for k in rows[0]["sound"]["numbers"]:
        summary[k] = {"lower": max(r["sound"]["numbers"][k] for r in rows)}
        for name, _ in variants(cell):
            vals = [r[name]["numbers"][k] for r in rows if name in r]
            if vals:
                summary[k][name] = min(vals)
    correct = {name: [r[name]["correct"] for r in rows if name in r]
               for name in ["sound"] + [n for n, _ in variants(cell)]}
    print(json.dumps({"summary": summary, "correct": correct,
                      "seeds": args.seeds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
