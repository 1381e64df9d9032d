"""``correct`` at a size the CPU holds: a sound run passes; the controls
(the reference in the program's place, with products in the
configuration's control precision or with bfloat16 storage), the faults
the reference can carry, and each fault planted in the timed path fail.
The limits are the chip-derived ones in ``limits/<cell>.json``."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench_small import small_cell
from benchmarks.chip import harness
from benchmarks.chip.control import readings

CELLS = ["mag-nc.train", "citation2-lp.train"]
SEED = 2 ** 31 + 77


def run(cell):
    return harness.run_cell(cell, SEED, 0.05, False, time.time(),
                            log=lambda *_: None)


def state_unchanged(mp):
    from repro.optim import adamw as real
    from repro.optim.adamw import Optimizer
    from repro.trainer import trainers

    def frozen(**kw):
        opt = real(**kw)
        return Optimizer(init=opt.init, update=lambda g, s, p, *a: (p, s))
    mp.setattr(trainers, "adamw", frozen)
    mp.setattr(trainers, "_sparse_adagrad", lambda t, g, *a: (t, g))


def half_batch(mp):
    from repro.trainer import task_programs, trainers
    expand = task_programs.NodeTaskProgram.expand
    lp_loss = trainers.GSgnnLinkPredictionTrainer._lp_loss

    def nc_half(self, blocks, step, dp=None):
        seeds, aux, ex = expand(self, blocks, step, dp)
        m = aux["mask"]
        return seeds, dict(aux, mask=m & (jnp.arange(m.shape[0])
                                          < m.shape[0] // 2)), ex

    def lp_half(self, pos, nsc, neg_mask):
        h = pos.shape[0] // 2
        return lp_loss(self, pos[:h], nsc[:h], neg_mask[:h])
    mp.setattr(task_programs.NodeTaskProgram, "expand", nc_half)
    mp.setattr(trainers.GSgnnLinkPredictionTrainer, "_lp_loss", lp_half)


def altered_rows(mp):
    from repro.core.sampling import DeviceNeighborSampler
    sample = DeviceNeighborSampler.sample

    def shifted(self, *a, **k):
        masks, dts, frontier = sample(self, *a, **k)
        return masks, dts, {nt: jnp.roll(v, 1) for nt, v in frontier.items()}
    mp.setattr(DeviceNeighborSampler, "sample", shifted)


def frozen_tables(mp):
    from repro.trainer import trainers
    mp.setattr(trainers, "_sparse_adagrad", lambda t, g, *a: (t, g))


def bfloat16_storage(mp):
    """Features, weights and tables kept in bfloat16: rounded where the
    program gets them and after every update."""
    from benchmarks.chip import adapter
    from repro.optim import adamw as real
    from repro.optim.adamw import Optimizer
    from repro.trainer import trainers
    adagrad = trainers._sparse_adagrad
    build, install = harness.build_program, adapter.install

    def rnd(x):
        return jnp.asarray(x).astype(jnp.bfloat16).astype(x.dtype)

    def build_rounded(cell, gd, feats, pseed):
        return build(cell, gd, {k: rnd(v) for k, v in feats.items()}, pseed)

    def install_rounded(runner, cell, gd, params, tables):
        install(runner, cell, gd, jax.tree_util.tree_map(rnd, params),
                {k: rnd(v) for k, v in tables.items()})

    def rounded(**kw):
        opt = real(**kw)

        def update(*a):
            p, s = opt.update(*a)
            return jax.tree_util.tree_map(rnd, p), s
        return Optimizer(init=opt.init, update=update)

    def adagrad_rounded(*a):
        t, g = adagrad(*a)
        return rnd(t), g
    mp.setattr(trainers, "adamw", rounded)
    mp.setattr(trainers, "_sparse_adagrad", adagrad_rounded)
    mp.setattr(harness, "build_program", build_rounded)
    mp.setattr(adapter, "install", install_rounded)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "altered_rows": altered_rows}
# faults of the state a cell keeps: only mag-nc.train has embedding tables
STATE_FAULTS = [("mag-nc.train", "frozen_tables"),
                ("mag-nc.train", "bfloat16_storage"),
                ("citation2-lp.train", "bfloat16_storage")]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = run(small_cell(name))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_step_ms", "peak_hbm_gb",
                                   "setup_s"}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_fault_in_the_timed_path_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(small_cell(name))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name,fault", STATE_FAULTS)
def test_state_fault_in_the_timed_path_is_not_correct(name, fault,
                                                      monkeypatch):
    {"frozen_tables": frozen_tables,
     "bfloat16_storage": bfloat16_storage}[fault](monkeypatch)
    out = run(small_cell(name))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The program's readings are correct; the reference in its place,
    as the control, with bfloat16 storage or with each fault, is not."""
    r = readings(small_cell(name), SEED)
    assert r["sound"]["correct"], r["sound"]
    wrong = [k for k, v in r.items() if isinstance(v, dict) and k != "sound"]
    assert {"control", "storage_bfloat16", "unchanged", "half_batch",
            "altered"} <= set(wrong)
    assert ("frozen_tables" in wrong) == (name == "mag-nc.train")
    for k in wrong:
        assert not r[k]["correct"], (k, r[k])
