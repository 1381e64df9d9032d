"""Cells, configurations, traffic and metrics are found by name from
files; names and units outside the allowed characters are refused; the
command fails without a chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

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


def test_reference_imports_nothing_of_the_program():
    src = Path(reference.__file__).read_text()
    assert "repro" not in src


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
