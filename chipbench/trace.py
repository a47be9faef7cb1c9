"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle gaps,
top device operations and per-program device time.

Device planes are named ``/device:TPU:<n>``.  On each, the line
``XLA Ops`` holds one event per operation run, and ``XLA Modules`` one per
program (``jit_<function>(<id>)``).  The harness's own host spans
(``jax.profiler.TraceAnnotation``) sit on the host plane ``/host:CPU`` on
the same clock; the span ``window`` brackets the traced window.

Busy time is the union of the operation intervals inside the window,
averaged over the devices that ran any operation.  Operations nest (a
``while`` holds the operations of its body), so an operation's own time is
its interval less those of the operations inside it; the top operations
are ranked by own time, named by HLO name and result type.  An idle gap is a stretch
of the window in which no operation ran; it is named by the innermost
harness span that covers its middle, or ``other``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Sorted, merged copy of [start, end] intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def op_name(hlo: str) -> str:
    """'%fusion.3 = bf16[8,128]{1,0} fusion(...)' -> 'fusion.3 bf16[8,128]'."""
    head, _, rest = hlo.partition(" = ")
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + m.group(1) if m else "")


def self_times(events) -> dict:
    """Own time of each op name: its intervals less the nested ones."""
    own: dict = {}
    stack: list = []                      # [end, name]
    for a, b, n in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            own[parent] = own.get(parent, 0.0) - (min(b, stack[-1][0]) - a)
        own[n] = own.get(n, 0.0) + (b - a)
        stack.append([b, n])
    return own


def read_events(profile) -> dict:
    """{'devices': {plane: {line: [(start, end, name)]}},
    'spans': [(start, end, name)]} in ns, from a ``ProfileData``."""
    devices: dict = {}
    spans: list = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [(e.start_ns, e.end_ns, e.name)
                                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events)
    return {"devices": devices, "spans": spans}


def reduce_events(ev: dict, span_names: Iterable[str], top: int = 10) -> dict:
    """Busy and window seconds, top ops, longest idle gaps, module seconds.

    ``span_names`` are the harness spans that may name an idle gap.
    Returns None where the trace holds no window span or no device op.
    """
    names = set(span_names)
    windows = [(a, b) for a, b, n in ev["spans"] if n == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    spans = [(a, b, n) for a, b, n in ev["spans"] if n in names]

    busy, op_time, module_time, gaps = [], {}, {}, []
    for lines in ev["devices"].values():
        ops = clip([(a, b) for a, b, _ in lines.get(OPS_LINE, [])], lo, hi)
        if not ops:
            continue
        merged = union(ops)
        busy.append(sum(b - a for a, b in merged))
        inside = [(max(a, lo), min(b, hi), op_name(n))
                  for a, b, n in lines.get(OPS_LINE, []) if b > lo and a < hi]
        for n, t in self_times(inside).items():
            op_time[n] = op_time.get(n, 0.0) + t
        for a, b, n in lines.get(MODULES_LINE, []):
            if b > lo and a < hi:
                key = n.split("(")[0]
                module_time[key] = (module_time.get(key, 0.0)
                                    + min(b, hi) - max(a, lo))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _span_at(spans, (a + b) / 2)))
    if not busy:
        return None
    n_dev = len(busy)
    gaps.sort(key=lambda g: -g[0])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "devices": n_dev,
        "device_ops": [[n, t / n_dev * 1e-9] for n, t in ops_sorted],
        "idle_gaps": [[n, t * 1e-9] for t, n in gaps[:top]],
        "module_s": {k: v / n_dev * 1e-9 for k, v in module_time.items()},
    }


def _span_at(spans, t: float) -> str:
    best = None
    for a, b, n in spans:
        if a <= t <= b and (best is None or b - a < best[0]):
            best = (b - a, n)
    return best[1] if best else "other"


def reduce_file(path: str, span_names: Iterable[str], top: int = 10):
    from jax.profiler import ProfileData
    return reduce_events(read_events(ProfileData.from_file(path)),
                         span_names, top)
