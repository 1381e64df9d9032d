"""Per-layer device time of the training step, read from the program's
own device scopes and host spans (``repro.trainer.tracing``).

Once per traced run, ``read`` records one more epoch of the cell under
the profiler, through a fresh ``StreamingEpochEngine`` over the window's
trainer and loader (the same compiled epoch program), after the window.
The recording is reduced so:

- each device op goes to the innermost program scope in its ``tf_op``,
  with ``transpose(...)`` and ``jvp(...)`` stripped, so backward ops
  count with the forward code they differentiate; ops that no scope
  claims are ``unscoped``.  An op's time is the part of its interval no
  earlier op covers, so the scopes' times add up to the busy time;
- busy time is the union of the op intervals (``trace.union``) inside
  the program's ``engine.run`` span, and its idle gaps
  (``trace.gaps``) are named by the innermost program span open at
  their midpoint.  Host times are moved onto each device's clock first,
  by the least lead of an ``XLA Modules`` event of the epoch program
  over the ``dispatch_epoch`` span that launched it.

A program without ``repro.trainer.tracing`` reads as None throughout.
A fusion that spans two scopes takes its root's.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import trace

MODULE_LINE = "XLA Modules"
WINDOW = "engine.run"
# the metrics' groups of program scopes; ``gnn.layer`` stands for every
# ``gnn.layer<l>``
GROUPS = {"sample": ("expand", "sample"), "spot_target": ("spot_target",),
          "gather": ("gather.features", "gather.embeddings"),
          "gnn": ("encode", "gnn.layer", "head"), "adamw": ("adamw",),
          "sparse_adagrad": ("sparse_adagrad",)}
UNSCOPED = "unscoped"
UNNAMED = "unnamed"
# a transform around a scope in a name stack: ``transpose(jvp(head))``
WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
MODULE_ID = re.compile(r"\(\d+\)$")

Event = Tuple[str, float, float]


@dataclasses.dataclass
class Recording:
    """Device ops ``{device: [(hlo name, tf_op, start_ns, end_ns)]}``,
    epoch-program launches ``{device: [(module, start_ns, end_ns)]}`` and
    host spans ``[(name, start_ns, end_ns)]``, all in nanoseconds of the
    profiler's clock."""
    ops: Dict[int, List[Tuple[str, str, float, float]]]
    modules: Dict[int, List[Event]]
    spans: List[Event]


def _xplane_pb2():
    """The ``XSpace`` protobuf module shipped with TensorFlow, loaded from
    its file so that TensorFlow itself is not imported."""
    name = "_chip_xplane_pb2"
    if name not in sys.modules:
        tf = importlib.util.find_spec("tensorflow")
        if tf is None or tf.origin is None:
            raise ImportError("reading a profile's event metadata needs "
                              "TensorFlow's xplane_pb2")
        path = (Path(tf.origin).parent / "tsl" / "profiler" / "protobuf"
                / "xplane_pb2.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def _stat(plane, stats, key: str):
    for st in stats:
        if plane.stat_metadata[st.metadata_id].name == key:
            return getattr(st, st.WhichOneof("value"))
    return None


def load(path: str, span_names: Sequence[str]) -> Recording:
    """The device ops, module launches and the named host spans of one
    ``.xplane.pb``."""
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    names = set(span_names)
    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    spans = []
    for plane in space.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        host = plane.name.startswith("/host:")
        if not (m or host):
            continue
        described: Dict[int, Optional[Tuple[str, str]]] = {}
        for line in plane.lines:
            for e in line.events:
                md = plane.event_metadata[e.metadata_id]
                a = line.timestamp_ns + e.offset_ps * 1e-3
                b = a + e.duration_ps * 1e-3
                if host:
                    if md.name in names:
                        spans.append((md.name, a, b))
                elif line.name == trace.OP_LINE:
                    if e.metadata_id not in described:
                        described[e.metadata_id] = _describe(plane, md)
                    op = described[e.metadata_id]
                    if op is not None:
                        ops.setdefault(int(m.group(1)), []).append(
                            op + (a, b))
                elif line.name == MODULE_LINE:
                    modules.setdefault(int(m.group(1)), []).append(
                        (MODULE_ID.sub("", md.name), a, b))
    return Recording(ops=ops, modules=modules, spans=spans)


def _describe(plane, md) -> Optional[Tuple[str, str]]:
    """An op's ``(hlo name, name stack)``; None for an op that only
    encloses others."""
    if trace.op_kind(md.name) in trace.CONTAINERS:
        return None
    tf_op = _stat(plane, md.stats, "tf_op") or ""
    # ``<name stack>:<op type>``
    return trace.op_name(md.name), tf_op.rpartition(":")[0] \
        if ":" in tf_op else tf_op


def scope_of(op_name: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost of ``scopes`` in an op's name stack (a scope that
    takes an index, ``gnn.layer``, matches ``gnn.layer0``), or None."""
    known = set(scopes)
    for part in reversed(op_name.split("/")):
        m = WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = WRAPPED.match(part)
        base = part.rstrip("0123456789")
        if part in known or (base != part and base in known):
            return part
    return None


def group_of(scope: Optional[str]) -> str:
    if scope is None:
        return UNSCOPED
    base = scope.rstrip("0123456789")
    for g, members in GROUPS.items():
        if scope in members or base in members:
            return g
    raise KeyError(f"scope {scope!r} is in no group of {GROUPS}")


def outermost(spans: Sequence[Event]) -> List[Event]:
    """Spans less those inside a longer span of the same name (a caller's
    span around the same call)."""
    return [s for s in spans
            if not any(o[0] == s[0] and o[1] <= s[1] and s[2] <= o[2]
                       and o[2] - o[1] > s[2] - s[1] for o in spans)]


def clock_offset(spans: Sequence[Event], modules: Sequence[Event]) -> float:
    """Nanoseconds to add to a host time to put it on a device's clock:
    the least lead of the epoch program's module launches (the module
    with the most device time) over the ``dispatch_epoch`` spans, paired
    in order."""
    dispatch = sorted(a for n, a, _ in outermost(spans)
                      if n == "dispatch_epoch")
    total: Dict[str, float] = {}
    for n, a, b in modules:
        total[n] = total.get(n, 0.0) + b - a
    if not dispatch or not total:
        raise ValueError("no dispatch_epoch span or no module launch to "
                         "align the host and device clocks by")
    epoch = max(total, key=total.get)
    launches = sorted(a for n, a, _ in modules if n == epoch)
    return min(m - d for d, m in zip(dispatch, launches))


def reduce(rec: Recording, scopes: Sequence[str], devices: Sequence[int],
           steps: int, top: int = 10) -> dict:
    """Milliseconds of the ``engine.run`` window, averaged over
    ``devices``: busy, each scope's share of it (``scope_ms``), idle by
    the span open over it (``idle_ms``), the longest gaps, the costliest
    ops of each group, and each device's clock offset."""
    windows = [s for s in rec.spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found "
                         f"{len(windows)}")
    _, lo_h, hi_h = windows[0]
    inner = [s for s in rec.spans if s[0] != WINDOW]
    window = busy = 0.0
    scope_ns: Dict[str, float] = {}
    idle_ns: Dict[str, float] = {}
    op_ns: Dict[Tuple[str, str], float] = {}
    gaps, offsets = [], {}
    claimed: Dict[str, str] = {}
    for d in devices:
        off = clock_offset(rec.spans, rec.modules.get(d, []))
        offsets[d] = off * 1e-6
        lo, hi = lo_h + off, hi_h + off
        window += hi - lo
        ops = sorted((a, b, name, tf) for name, tf, a, b in rec.ops.get(d, [])
                     if b > lo and a < hi)
        merged = trace.union(trace.clip([(a, b) for a, b, _, _ in ops],
                                        lo, hi))
        busy += trace.length(merged)
        covered = lo
        for a, b, name, tf in ops:
            t = max(0.0, min(b, hi) - max(a, covered))
            covered = max(covered, min(b, hi))
            if tf not in claimed:
                claimed[tf] = scope_of(tf, scopes) or UNSCOPED
            s = claimed[tf]
            scope_ns[s] = scope_ns.get(s, 0.0) + t
            key = (group_of(None if s == UNSCOPED else s), name)
            op_ns[key] = op_ns.get(key, 0.0) + t
        shifted = [(n, a + off, b + off) for n, a, b in inner]
        for a, b in trace.gaps(merged, lo, hi):
            n = trace.span_at(shifted, (a + b) / 2, UNNAMED)
            idle_ns[n] = idle_ns.get(n, 0.0) + b - a
            gaps.append((b - a, n))
    k = max(len(devices), 1)
    ms = 1e-6 / k
    gaps.sort(key=lambda g: -g[0])
    by_group: Dict[str, list] = {}
    for (g, name), t in sorted(op_ns.items(), key=lambda kv: -kv[1]):
        if len(by_group.setdefault(g, [])) < 3:
            by_group[g].append([name, t * ms / steps])
    return {"steps": steps, "window_ms": window * ms, "busy_ms": busy * ms,
            "scope_ms": {s: t * ms for s, t in sorted(scope_ns.items())},
            "idle_ms": {n: t * ms for n, t in sorted(idle_ns.items())},
            "idle_gaps": [[n, t * 1e-6] for t, n in gaps[:top]],
            "top_ops_ms_per_step": by_group, "offset_ms": offsets}


def step_ms(summary: Optional[dict], group: str) -> Optional[float]:
    """Device ms per step of one group of scopes (or ``unscoped``)."""
    if not summary:
        return None
    t = sum(v for s, v in summary["scope_ms"].items()
            if group_of(None if s == UNSCOPED else s) == group)
    return t / summary["steps"]


def idle_ms(summary: Optional[dict], name: str) -> Optional[float]:
    """Device idle ms of the recorded epoch under one host span (or
    ``unnamed``: under none finer than ``engine.run``)."""
    if not summary:
        return None
    return summary["idle_ms"].get(name, 0.0)


def xplane_file(d: str) -> str:
    for root, _, files in os.walk(d):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no .xplane.pb under {d}")


def record(run: dict) -> Optional[dict]:
    """One epoch of the run's trainer and loader under the profiler,
    reduced; None where the program has no tracing module or the
    recording holds no device op."""
    try:
        from repro.trainer import tracing
    except ImportError:
        return None
    import jax
    from repro.trainer.epoch_engine import StreamingEpochEngine
    t0 = time.perf_counter()
    runner, loader = run["runner"], run["loader"]
    engine = StreamingEpochEngine(runner.trainer, loader,
                                  **runner._fit_kwargs())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            engine.run(1)
        finally:
            jax.profiler.stop_trace()
        t_epoch = time.perf_counter() - t0
        rec = load(xplane_file(d), tracing.SPANS)
    del engine
    if not rec.ops:
        return None
    devices = sorted(dev.id for dev in jax.devices()[:run["chips"]])
    out = reduce(rec, tracing.SCOPES, devices, int(loader.num_batches))
    out["epoch_s"] = t_epoch
    out["reader_s"] = time.perf_counter() - t0
    print(json.dumps({"phase": "scopes", **out}), flush=True)
    return out


def summary(run: dict) -> Optional[dict]:
    """The run's reduced recording, made on first use and kept in
    ``run``."""
    if "scopes" not in run:
        run["scopes"] = record(run)
    return run["scopes"]
