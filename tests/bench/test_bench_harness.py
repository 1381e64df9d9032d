"""Cells, configurations, traffic and metrics are found by name from
files; names and units outside the allowed characters are refused; the
command fails without a chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import reference, spec

ROOT = spec.CHECKOUT
BENCH = spec.load_benchmark()
E2E = {m["name"] for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_found_from_its_files(name):
    c = spec.find_cell(name)
    assert c.config["name"] == c.config_name
    entry = {x["name"]: x for x in BENCH["configs"]}[c.config_name]
    assert entry["file"] == f"benchmarks/chip/configs/{c.config_name}.json"
    assert entry["source"] == c.config["source"]
    assert entry["reduced"] == c.config["reduced"]
    assert int(c.traffic["batch_size"]) % c.chips == 0
    numbers = {k for k, v in c.limits.items() if isinstance(v, dict)}
    assert {"loss_gap.step0", "loss_gap.steps12"} <= numbers
    assert any(k.startswith("change_gap.") for k in numbers)
    for k in numbers:
        lim = c.limits[k]
        assert lim["lower"] < lim["limit"] < lim["upper"]
        assert lim["upper"] >= 3 * lim["lower"]
    assert {m.name for m in c.end_to_end} == E2E


@pytest.mark.parametrize("entry", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_its_reader(entry):
    mod = spec.metric_reader(entry["name"])
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == \
        (entry["layer"], entry["unit"], entry["moves"])
    assert entry["moves"] in E2E
    for cell in entry.get("workloads", CELLS):
        assert cell in CELLS


@pytest.mark.parametrize("bad", ["a b", "a,b", "a/b", "-lead", "µs", "",
                                 "x" * 65])
def test_bad_names_are_refused(bad):
    with pytest.raises(ValueError):
        spec.check_name(bad)


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "",
                                 "x" * 17, "a,b"])
def test_bad_units_are_refused(bad):
    with pytest.raises(ValueError):
        spec.check_unit(bad)


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        for n in names:
            spec.check_name(n)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        spec.check_unit(m["unit"])
    assert "setup_s" in E2E
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell, a traffic mix, a configuration and a metric added as files
    and entries are found without a change to any file already there."""
    here = spec.HERE
    for sub in ("configs", "traffic", "limits", "metrics", "graphs"):
        shutil.copytree(here / sub, tmp_path / sub)
    cfg = json.loads((here / "configs" / "rgcn-mag-nc.json").read_text())
    cfg["name"] = "rgcn-mag-nc.wide"
    (tmp_path / "configs" / "rgcn-mag-nc.wide.json").write_text(
        json.dumps(cfg))
    (tmp_path / "traffic" / "train.e16.json").write_text(
        json.dumps({"batch_size": 2048, "batches_per_epoch": 16}))
    (tmp_path / "limits" / "mag-nc.wide.json").write_text(
        (here / "limits" / "mag-nc.train.json").read_text())
    (tmp_path / "metrics" / "window_steps.py").write_text(
        'LAYER = "device step"\nUNIT = "1"\nMOVES = "train_step_ms"\n\n'
        'def read(run):\n    return run["steps"]\n')
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "rgcn-mag-nc.wide", "source": "s",
                             "file": "x", "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "mag-nc.wide",
                               "config": "rgcn-mag-nc.wide",
                               "traffic": "train.e16", "chips": 1,
                               "why": "w"})
    bench["per_layer"].append({"name": "window_steps", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "device step",
                               "moves": "train_step_ms",
                               "workloads": ["mag-nc.wide"]})
    c = spec.find_cell("mag-nc.wide", bench, here=tmp_path)
    assert c.traffic["batch_size"] == 2048
    assert c.config["name"] == "rgcn-mag-nc.wide"
    assert "window_steps" in [m.name for m in c.per_layer]
    assert "window_steps" not in [
        m.name for m in spec.find_cell("mag-nc.train", bench,
                                       here=tmp_path).per_layer]
    assert spec.metric_reader("window_steps", here=tmp_path).read(
        {"steps": 7}) == 7


TOY_GNN = '''"""A sum over each relation's unmasked draws, one weight per
relation and no self weight."""
import jax
import jax.numpy as jnp

from benchmarks.chip.reference import ekey


def layer_shapes(ntypes, ekeys, hidden, gnn):
    return {"w": {e: jax.ShapeDtypeStruct((hidden, hidden), jnp.float32)
                  for e in ekeys}}


def layer(p, plan_layer, masks, h, mm, last):
    out = {}
    for et, nd, f, off in plan_layer.edges:
        rows = h[et[0]][off:off + nd * f].reshape(nd, f, -1)
        agg = (rows * masks[ekey(et)][..., None]).sum(1)
        out[et[2]] = out.get(et[2], 0.0) + mm(agg, p["w"][ekey(et)])
    return out


def layer_flops(plan_layer, hidden, gnn):
    fwd = bwd = 0.0
    for _, n, f, _ in plan_layer.edges:
        agg, mm = 2.0 * n * f * hidden, 2.0 * n * hidden * hidden
        fwd += agg + mm
        bwd += agg + 2 * mm
    return fwd, bwd
'''


def test_a_new_gnn_needs_only_new_files(tmp_path):
    """A configuration whose ``gs.gnn.model`` names a new file under
    ``models/`` is weighted, referenced and counted with no change to a
    file already there."""
    from bench_small import graph_of_counts, reference_run, small_cell

    from benchmarks.chip import adapter, harness
    here = spec.HERE
    for sub in ("configs", "traffic", "limits", "metrics", "graphs",
                "models"):
        shutil.copytree(here / sub, tmp_path / sub)
    (tmp_path / "models" / "toysum.py").write_text(TOY_GNN)
    cfg = json.loads((here / "configs" / "rgcn-mag-nc.json").read_text())
    cfg["name"] = "toysum-mag-nc"
    cfg["gs"]["gnn"]["model"] = "toysum"
    (tmp_path / "configs" / "toysum-mag-nc.json").write_text(json.dumps(cfg))
    (tmp_path / "limits" / "mag-nc.toysum.json").write_text(
        (here / "limits" / "mag-nc.train.json").read_text())
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "toysum-mag-nc", "source": "s",
                             "file": "x", "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "mag-nc.toysum",
                               "config": "toysum-mag-nc",
                               "traffic": "train.e64", "chips": 1,
                               "why": "w"})
    cell = small_cell("mag-nc.toysum", bench=bench, here=tmp_path)
    rgcn = small_cell("mag-nc.train")
    gd = graph_of_counts(cell)
    ekeys = sorted(reference.ekey(e) for e in gd.edges)
    H = cell.config["gs"]["gnn"]["hidden"]

    dense, tables = adapter.weight_shapes(cell, gd)
    assert [set(lay) for lay in dense["gnn"]["layers"]] == [{"w"}, {"w"}]
    assert sorted(dense["gnn"]["layers"][0]["w"]) == ekeys
    assert {tuple(x.shape) for x in dense["gnn"]["layers"][1]["w"].values()} \
        == {(H, H)}
    assert tables == adapter.weight_shapes(rgcn, gd)[1]

    out = reference_run(cell)
    assert np.isfinite(out["losses"][0])
    g = out["grad_norms"]["params"]["gnn"]["layers"][0]["w"]
    assert sorted(g) == ekeys and all(np.isfinite(list(g.values())))

    # the toy is RGCN without its self products (6 n H^2 a destination
    # row, forward and backward) and the self weights and biases
    seeds = {cell.config["gs"]["node_classification"]["target_ntype"]:
             int(cell.traffic["batch_size"])}
    layers = reference.plan(sorted(gd.edges), cell.config["gs"]["gnn"]
                            ["fanout"], seeds)
    toy, full = harness.step_work(cell, gd), harness.step_work(rgcn, gd)
    assert full["flops"] - toy["flops"] == sum(
        6.0 * n * H * H for lay in layers for _, n in lay.dst)
    per_layer = len(gd.num_nodes) * (H * H + H)
    assert full["bytes"] - toy["bytes"] == 7 * 4.0 * 2 * per_layer


def test_reference_imports_nothing_of_the_program():
    """The reference and every GNN layer it runs."""
    files = [Path(reference.__file__)] + sorted(
        (spec.HERE / "models").glob("*.py"))
    assert len(files) > 1
    for f in files:
        assert "repro" not in f.read_text(), f


def test_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mag-nc.train", "--seed", str(2 ** 31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "needs 1 TPU chip" in p.stderr
