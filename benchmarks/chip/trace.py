"""Reduction of a JAX profiler trace to device busy time, idle gaps,
exposed collective time and the costliest device operations.

A device's busy time is the union of its operation intervals inside the
window; its idle share is one minus busy over the window.  A collective
is exposed while it runs and no other operation runs on that device.
Each idle gap is named by the innermost benchmark span open on the host
at the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")
# ops that only enclose others (a scanned epoch is one ``while``): their
# span would count the gaps between the ops they run as busy
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]
# a TPU trace names each op by its HLO text: "%name = shape opcode(...)"
HLO_TEXT = re.compile(r"^%(\S+) = (.+?) ([a-z][a-z0-9-]*)\(")
LAYOUT = re.compile(r"\{[^}]*\}")


def op_kind(text: str) -> str:
    """The HLO opcode of an op's text ('' when it is not HLO)."""
    m = HLO_TEXT.match(text)
    return m.group(3) if m else ""


def op_name(text: str) -> str:
    """``name opcode shape`` of an op's HLO text, layouts dropped (the
    text itself when it is not HLO)."""
    m = HLO_TEXT.match(text)
    if not m:
        return text
    return f"{m.group(1)} {m.group(3)} {LAYOUT.sub('', m.group(2))}"


@dataclasses.dataclass
class Trace:
    """Device operations ``{device: [(name, start_ns, end_ns)]}`` and the
    benchmark's host spans ``[(name, start_ns, end_ns)]``."""
    ops: Dict[int, List[Tuple[str, float, float]]]
    spans: List[Tuple[str, float, float]]


def load(path: str, span_names: Sequence[str]) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, list] = {}
    spans = []
    names = set(span_names)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events
                        if op_kind(e.name) not in CONTAINERS)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in names)
    return Trace(ops=ops, spans=spans)


def union(intervals: Sequence[Interval]) -> np.ndarray:
    """Merged, sorted, disjoint ``(k, 2)`` intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = np.asarray(intervals, dtype=np.float64).reshape(-1, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    group = np.cumsum(new) - 1
    stops = np.zeros(len(starts))
    np.maximum.at(stops, group, iv[:, 1])
    return np.stack([starts, stops], axis=1)


def length(merged: np.ndarray) -> float:
    return float((merged[:, 1] - merged[:, 0]).sum()) if len(merged) else 0.0


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(merged: np.ndarray, lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between the busy ones."""
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def span_at(spans, t: float, default: str) -> str:
    """The innermost (shortest) span open at ``t``."""
    open_ = [(b - a, n) for n, a, b in spans if a <= t <= b]
    return min(open_)[1] if open_ else default


def summarize(tr: Trace, window: str, devices: Sequence[int],
              top: int = 10) -> dict:
    """Per-window figures averaged over ``devices``."""
    w = [(a, b) for n, a, b in tr.spans if n == window]
    if len(w) != 1:
        raise ValueError(f"expected one {window!r} span, found {len(w)}")
    lo, hi = w[0]
    inner = [s for s in tr.spans if s[0] != window]
    busy, exposed, per_op, idle = [], [], {}, []
    for d in devices:
        ops = [(n, a, b) for n, a, b in tr.ops.get(d, []) if b > lo and a < hi]
        all_iv = union(clip([(a, b) for _, a, b in ops], lo, hi))
        other = union(clip([(a, b) for n, a, b in ops
                            if not COLLECTIVE.search(n)], lo, hi))
        busy.append(length(all_iv))
        exposed.append(length(all_iv) - length(other))
        for n, a, b in ops:
            per_op[n] = per_op.get(n, 0.0) + min(b, hi) - max(a, lo)
        idle += [(b - a, span_at(inner, (a + b) / 2, window))
                 for a, b in gaps(all_iv, lo, hi)]
    n_dev = max(len(devices), 1)
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "exposed_collective_s": sum(exposed) / n_dev * 1e-9,
        "device_ops": [[n, t / n_dev * 1e-9] for n, t in ops_top],
        "idle_gaps": [[n, t * 1e-9] for t, n in idle[:top]],
    }
