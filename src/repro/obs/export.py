"""Chrome Trace Event JSON export (opens directly in Perfetto).

Renders a :class:`~repro.obs.recorder.SpanRecorder` as the Trace Event
Format's JSON *object* form::

    {"traceEvents": [...], "displayTimeUnit": "ms",
     "otherData": {"meta": {...}, "report": {...}}}

Layout: one pid per timeline track owner — pid 0 is the compute array
(op spans, off-chip spill instants, cumulative energy counters), pid
``1 + bank`` is one eDRAM/SRAM bank with three tids (port service,
hidden refresh pulses, preempting refresh stalls) plus its occupancy and
refresh-energy counters.  Duration spans are ``"X"`` events, counters
``"C"``, spills ``"i"`` instants, and track names ``"M"`` metadata.

``ts``/``dur`` are microseconds (the format's unit); every event also
carries the *raw second-domain* values in ``args`` (``t0_s``/``t1_s``,
counter ``t_s``/``value``), which are the authoritative numbers —
:func:`recorder_from_trace` rebuilds a recorder from them losslessly
(floats survive JSON round-trips exactly), so an exported trace can be
reconciled against its embedded report by ``tools/check_trace.py``.

Events are sorted by ``ts`` (metadata first); span tracks (op / port /
hidden-refresh) are non-overlapping by construction of the timeline
engine — both properties are what ``tools/check_trace.py`` validates.
"""
from __future__ import annotations

import json
from typing import Optional

from repro.obs.recorder import CounterSample, Span, SpanRecorder

# pid of the compute array track; banks are PID_BANK0 + bank index
PID_ARRAY = 0
PID_BANK0 = 1

# tids inside a bank's process
TID_PORT = 0
TID_REFRESH = 1
TID_REFRESH_STALL = 2

_SPAN_TID = {"op": 0, "spill": 1, "compile": 2, "lowering": 3,
             "port": TID_PORT, "refresh": TID_REFRESH,
             "refresh_stall": TID_REFRESH_STALL}
_TRACK_NAMES = {
    (PID_ARRAY, 0): "ops",
    (PID_ARRAY, 1): "off-chip spills",
    (PID_ARRAY, 2): "compiles",
    (PID_ARRAY, 3): "lowerings",
    TID_PORT: "port",
    TID_REFRESH: "refresh (hidden)",
    TID_REFRESH_STALL: "refresh (stall)",
}


def _us(t_s: float) -> float:
    return t_s * 1e6


def _pid(span_or_counter) -> int:
    bank = span_or_counter.bank
    return PID_ARRAY if bank < 0 else PID_BANK0 + bank


def chrome_trace_events(recorder: SpanRecorder) -> list[dict]:
    """The recorder's spans/counters as a sorted Trace Event list."""
    events: list[dict] = []
    pids = {PID_ARRAY: "array"}
    for b in recorder.banks():
        pids[PID_BANK0 + b] = f"bank {b}"

    for s in recorder.spans:
        pid = _pid(s)
        tid = _SPAN_TID[s.kind]
        args = {**s.args, "t0_s": s.t0, "t1_s": s.t1}
        if s.bank >= 0:
            args["bank"] = s.bank
        if s.kind in ("spill", "lowering"):    # zero-width: instant event
            events.append({"ph": "i", "s": "t", "pid": pid, "tid": tid,
                           "ts": _us(s.t0), "name": s.name,
                           "cat": s.kind, "args": args})
            continue
        events.append({"ph": "X", "pid": pid, "tid": tid,
                       "ts": _us(s.t0), "dur": _us(s.t1) - _us(s.t0),
                       "name": s.name, "cat": s.kind, "args": args})

    for c in recorder.counters:
        pid = _pid(c)
        args = {"value": c.value, "t_s": c.t}
        if c.bank >= 0:
            args["bank"] = c.bank
        events.append({"ph": "C", "pid": pid, "ts": _us(c.t),
                       "name": c.name, "cat": "counter", "args": args})

    events.sort(key=lambda e: e["ts"])

    meta: list[dict] = []
    for pid, name in sorted(pids.items()):
        meta.append({"ph": "M", "pid": pid, "ts": 0, "name": "process_name",
                     "args": {"name": name}})
    tids = sorted({(e["pid"], e["tid"]) for e in events if "tid" in e})
    for pid, tid in tids:
        label = _TRACK_NAMES.get((pid, tid)) or _TRACK_NAMES.get(tid) \
            or f"track {tid}"
        meta.append({"ph": "M", "pid": pid, "tid": tid, "ts": 0,
                     "name": "thread_name", "args": {"name": label}})
    return meta + events


def trace_dict(recorder: SpanRecorder, report=None) -> dict:
    """The full JSON-object-form trace.  ``report`` (an ``ArmReport`` or
    its ``to_dict()`` form) is embedded under ``otherData.report`` so the
    trace file is self-contained for reconciliation."""
    other: dict = {"meta": dict(recorder.meta)}
    if report is not None:
        other["report"] = (report.to_dict()
                           if hasattr(report, "to_dict") else dict(report))
    return {"traceEvents": chrome_trace_events(recorder),
            "displayTimeUnit": "ms", "otherData": other}


def export_chrome_trace(recorder: SpanRecorder, path, report=None) -> str:
    """Write the trace to ``path``; returns the path written.  Open the
    file in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``."""
    with open(path, "w") as f:
        json.dump(trace_dict(recorder, report=report), f)
    return str(path)


def recorder_from_trace(trace: dict) -> tuple[SpanRecorder, Optional[dict]]:
    """Rebuild ``(recorder, embedded report dict or None)`` from a trace
    produced by :func:`trace_dict` / :func:`export_chrome_trace`.

    Uses the raw second-domain values each event carries in ``args``
    (not the µs ``ts``), so the rebuilt recorder reconciles *exactly*
    against the embedded report.
    """
    rec = SpanRecorder()
    for e in trace.get("traceEvents", ()):
        ph, cat = e.get("ph"), e.get("cat")
        args = dict(e.get("args", {}))
        bank = args.pop("bank", -1)
        if ph in ("X", "i") and cat in _SPAN_TID:
            t0 = args.pop("t0_s")
            t1 = args.pop("t1_s")
            rec.spans.append(Span(kind=cat, name=e["name"], t0=t0, t1=t1,
                                  bank=bank, args=args))
        elif ph == "C":
            rec.counters.append(CounterSample(
                name=e["name"], t=args["t_s"], value=args["value"],
                bank=bank))
    other = trace.get("otherData", {})
    rec.meta = dict(other.get("meta", {}))
    return rec, other.get("report")
