"""chip_smoke.py on the CPU: its phases at a tiny size (kernels
interpreted), and its refusal to report a result without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = {"n_paper": 400, "n_author": 200, "n_inst": 16, "n_field": 16,
        "feat_dim": 128}


def test_one_chip_phases_tiny():
    pallas, xla = chip_smoke.one_chip_phases(
        dataset_conf=TINY, kernel_sizes={"n": 40, "rows": 300}, hidden=32,
        fanout=(4, 4), batch_size=64, steps=2)
    assert pallas["phase"] == "pallas" and xla["phase"] == "xla"
    assert len(pallas["epoch_losses"]) == chip_smoke.EPOCHS
    assert [len(e) for e in xla["step_losses"]] == [2] * chip_smoke.EPOCHS


def _run(name, curve):
    return {"phase": name, "step_losses": curve}


REF = [[2.5, 2.0, 1.5, 1.0], [0.9, 0.8, 0.7, 0.6]]


@pytest.mark.parametrize("fault,passes", [
    ("none", True),           # rounding-sized differences
    ("late_drift", True),     # later epochs are printed, not gated
    ("step0", False),         # the first step already differs
    ("curve", False),         # a first-epoch step drifts past the limit
    ("fewer_steps", False),   # a run that dropped batches
])
def test_check_agree_gates_on_first_step_and_first_epoch(fault, passes):
    rtol = {"step0": 1e-4, "curve": 1e-2}
    curve = [[x * (1 + 1e-6) for x in e] for e in REF]
    if fault == "late_drift":
        curve[1] = [x * 1.3 for x in curve[1]]
    elif fault == "step0":
        curve[0][0] *= 1 + 1e-3
    elif fault == "curve":
        curve[0][2] *= 1 + 2e-2
    elif fault == "fewer_steps":
        curve = [e[:3] for e in curve]
    a, b = _run("a", curve), _run("b", REF)
    if passes:
        gaps = chip_smoke.check_agree(a, b, rtol)
        assert gaps["step0"] == pytest.approx(1e-6, rel=1e-3)
        assert len(gaps["per_epoch_max"]) == len(REF)
    else:
        with pytest.raises(AssertionError):
            chip_smoke.check_agree(a, b, rtol)


def test_smoke_config_is_the_main_path():
    raw = chip_smoke.smoke_config(use_pallas=True)
    assert raw["input"]["dataset_conf"]["n_paper"] == 736_389
    assert raw["gnn"]["hidden"] == 256 and raw["gnn"]["fanout"] == [10, 10]
    hp = raw["hyperparam"]
    assert hp["batch_size"] == 1024 and hp["sample_on_device"]
    assert raw["device_features"]
    sharded = chip_smoke.smoke_config(use_pallas=False, data_parallel=4,
                                      sharded=True)["hyperparam"]
    assert sharded["shard_tables"] and sharded["shard_dedup"]
    assert sharded["shard_payload_dtype"] == "bfloat16"


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


@pytest.mark.parametrize("alone", [False, True])
def test_script_exits_nonzero_without_tpu(tmp_path, alone):
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise one fixed
    directory in the checkout."""
    import jax
    from repro.common import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
