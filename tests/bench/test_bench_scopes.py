"""The reduction of a recording to per-scope device time and named idle
gaps (``scopes.py``): on a hand-made recording, on a scoped toy scan
recorded on a v5e chip, and through a traced run at a size the CPU
holds."""
import json
import sys
import time
from pathlib import Path

import pytest

from bench_small import small_cell
from benchmarks.chip import harness, scopes, spec
from repro.trainer import tracing

SCOPED = Path(scopes.__file__).parent / "testdata" / "scoped.xplane.pb"
X = 500_000.0      # the device clock runs this far ahead of the host's
LEAD = 50.0        # a launch starts this long after its dispatch


def handmade():
    """Two launches of an epoch program, on a device clock ``X`` ahead;
    host spans on the host clock (the inner ``dispatch_epoch`` is a
    caller's span around the same call)."""
    spans = [("engine.run", 0, 10000), ("stage_epoch", 100, 2000),
             ("dispatch_epoch", 2000, 2100), ("dispatch_epoch", 2010, 2090),
             ("fetch_losses", 2200, 6000), ("dispatch_epoch", 7000, 7100),
             ("fetch_losses", 7200, 9000)]
    modules = [("jit_slice", 1990 + X, 1995 + X),
               ("jit_epoch", 2000 + LEAD + X, 5900 + X),
               ("jit_epoch", 7000 + LEAD + X, 8900 + X)]
    body = "jit(epoch)/while/body/"
    ops = [("fusion.1", body + "sample/spot_target/jit(searchsorted)/lt",
            2100 + X, 3100 + X),
           ("fusion.2", body + "transpose(jvp(gnn.layer0))/dot_general",
            3000 + X, 4000 + X),
           ("copy.3", body + "dynamic_update_slice", 4000 + X, 4500 + X),
           ("fusion.4", body + "jvp(head)/mul", 7100 + X, 8100 + X)]
    return scopes.Recording(ops={0: ops}, modules={0: modules}, spans=spans)


@pytest.mark.parametrize("name,scope", [
    ("jit(epoch)/while/body/closed_call/sample/spot_target/"
     "jit(searchsorted)/while/body/lt", "spot_target"),
    ("jit(epoch)/while/body/transpose(jvp(gnn.layer1))/dot_general",
     "gnn.layer1"),
    ("jit(epoch)/while/body/jvp(gather.features)/gather",
     "gather.features"),
    ("jit(epoch)/while/body/closed_call/expand/jit(_threefry_fold_in)/"
     "DeviceNeighborSampler.sample/add", "expand"),
    ("jit(epoch)/while/body/dynamic_update_slice", None),
    ("", None)])
def test_an_op_goes_to_its_innermost_scope(name, scope):
    assert scopes.scope_of(name, tracing.SCOPES) == scope


def test_every_program_scope_is_in_one_group():
    members = [m for g in scopes.GROUPS.values() for m in g]
    assert sorted(members) == sorted(tracing.SCOPES)


def test_nested_span_of_the_same_name_is_dropped():
    out = scopes.outermost(handmade().spans)
    assert ("dispatch_epoch", 2010, 2090) not in out
    assert len(out) == len(handmade().spans) - 1


def test_offset_is_the_least_launch_lead():
    rec = handmade()
    assert scopes.clock_offset(rec.spans, rec.modules[0]) == X + LEAD


def test_handmade_recording_reduces_to_scopes_and_named_gaps():
    s = scopes.reduce(handmade(), tracing.SCOPES, [0], steps=2)
    ns = 1e-6
    assert s["offset_ms"] == {0: (X + LEAD) * ns}
    assert s["window_ms"] == pytest.approx(10000 * ns)
    assert s["busy_ms"] == pytest.approx(3400 * ns)
    # fusion.2 starts under fusion.1: only its own 900 ns count
    assert s["scope_ms"] == pytest.approx(
        {"spot_target": 1000 * ns, "gnn.layer0": 900 * ns,
         "head": 1000 * ns, "unscoped": 500 * ns})
    assert s["idle_ms"] == pytest.approx(
        {"stage_epoch": 2050 * ns, "fetch_losses": 2600 * ns,
         "unnamed": 1950 * ns})
    assert scopes.step_ms(s, "spot_target") == pytest.approx(500 * ns)
    assert scopes.step_ms(s, "gnn") == pytest.approx(950 * ns)
    assert scopes.step_ms(s, "sample") == 0
    assert scopes.idle_ms(s, "unnamed") == pytest.approx(1950 * ns)
    total = sum(scopes.step_ms(s, g) for g in
                list(scopes.GROUPS) + [scopes.UNSCOPED])
    assert total * 2 == pytest.approx(s["busy_ms"])


def test_recorded_chip_trace():
    """A scoped toy scan recorded on one v5e chip (``record_scoped.py``):
    three launches of a 4-step epoch under the engine's spans.  The
    head's and the update's few ops fused into the layer's, whose
    scope they take; ops XLA made (copies, a hoisted convert) have no
    name stack and count as unscoped."""
    rec = scopes.load(str(SCOPED), tracing.SPANS)
    assert sorted(rec.ops) == sorted(rec.modules) == [0]
    s = scopes.reduce(rec, tracing.SCOPES, [0], steps=12)
    assert sum(s["scope_ms"].values()) == pytest.approx(s["busy_ms"],
                                                        rel=1e-9)
    assert set(s["scope_ms"]) == {"gather.features", "gnn.layer0",
                                  "unscoped"}
    backward = [tf for _, tf, _, _ in rec.ops[0] if "transpose(" in tf]
    assert backward and {scopes.scope_of(tf, tracing.SCOPES)
                         for tf in backward} == {"gnn.layer0"}
    # the host clock runs ahead here: each launch starts, on the host's
    # clock, before the call that made it
    off = s["offset_ms"][0] * 1e6
    assert -2e6 < off < 0
    dispatch = [a for n, a, _ in rec.spans if n == "dispatch_epoch"]
    leads = [m - d - off for d, (_, m, _) in zip(dispatch, rec.modules[0])]
    assert min(leads) == pytest.approx(0, abs=1e-6)
    assert all(x >= -1e-6 for x in leads)
    assert s["idle_ms"]["stage_epoch"] > 15       # the 20 ms stage
    assert s["idle_ms"]["unnamed"] > 25           # three 10 ms sleeps
    assert s["busy_ms"] + sum(s["idle_ms"].values()) == \
        pytest.approx(s["window_ms"])


def test_program_without_tracing_reads_nothing(monkeypatch):
    import repro.trainer
    monkeypatch.delattr(repro.trainer, "tracing")
    monkeypatch.setitem(sys.modules, "repro.trainer.tracing", None)
    run = {}
    assert scopes.step_ms(scopes.summary(run), "gather") is None
    assert scopes.idle_ms(scopes.summary(run), "unnamed") is None
    assert run["scopes"] is None


@pytest.mark.parametrize("name", ["mag-nc.train", "citation2-lp.train"])
def test_traced_run_records_one_more_epoch(name, monkeypatch):
    """One recording serves every reader of a traced run.  On the CPU it
    holds no device op, so the new metrics are left out."""
    monkeypatch.setattr(spec, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    made = []
    record = scopes.record

    def counted(run):
        made.append(record(run))
        return made[-1]
    monkeypatch.setattr(scopes, "record", counted)
    out = harness.run_cell(small_cell(name), 2 ** 31 + 91, 0.05, True,
                           time.time(), log=lambda *_: None)
    assert out["correct"], out["checks"]
    assert made == [None]
    assert not [m for m in out["metrics"]
                if m.startswith("step_ms.") or m.startswith("device_idle_ms")]
    json.dumps(out)
