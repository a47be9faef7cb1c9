"""The flight recorder: typed spans + counter series for one sim run.

A :class:`SpanRecorder` is an opt-in sink threaded through the pipeline
(``sim.run(arm, trace=...)`` → ``SimContext.recorder`` → the timeline
engine and the controller replay).  With no recorder attached every
instrumentation site is a no-op and the simulation is bit-identical —
the recorder only *observes*; it never feeds anything back into timing
or energy.

Span kinds (:data:`SPAN_KINDS`):

``op``
    One schedule op on the pushed-back (closed-loop) timeline.  Args
    carry the unconstrained schedule position (``sched_start_s`` /
    ``sched_end_s``) and the pushback this op's ports added
    (``pushback_s``), so conflict stall is visible per op.
``port``
    One op's port service on one bank — ``[start, start + slowest
    port)`` with the read/write word counts in args.
``refresh``
    A *hidden* refresh pulse, placed inside a bank-idle window (energy
    charged, zero stall).  Args: retention ``tick``, starting ``row``,
    ``rows`` multiplicity, ``words`` moved, ``deadline_s``.
``refresh_stall``
    A pulse (or an aggregated preempting run of row pulses) that found
    no idle window: it preempts at its deadline and stalls the ports
    for ``stall_s`` seconds.
``spill``
    An off-chip transfer for a spilled tensor (zero-width: the replay
    charges energy, off-chip *time* is priced globally against
    ``SystemConfig.offchip_bw_bps``).
``compile``
    One of JAX's compile events in this process (``repro.obs.runtime``):
    named ``trace``, ``lower`` or ``backend``, with the program's
    ``fun_name`` in args, stamped in realtime nanoseconds.
``lowering``
    Zero-width: one layer's lowering choice, made while a program is
    traced (``repro.obs.runtime.moe_routes``): named by the route, with
    its static sizes in args.

Counter series (:meth:`SpanRecorder.counter`) sample per-bank occupancy
in words at every allocate/free, cumulative traffic energy at each
charging event, per-bank refresh energy, and the energy stage's final
compute/leakage totals.  ``meta`` carries the run's scalars the
reconciliation needs (``schedule_s``, ``timing``, ``granularity``, …).

The recorded stream is a *checkable ground truth*: ``repro.obs.reconcile``
re-derives ``stall_s`` / ``refresh_stall_s`` / ``refresh_hidden_j`` /
``rows_refreshed`` from it and asserts exact equality with the
``ArmReport``, and ``repro.obs.export`` renders it as Chrome Trace Event
JSON for Perfetto.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Iterator, Optional

SPAN_KINDS = ("op", "port", "refresh", "refresh_stall", "spill",
              "compile", "lowering")


@dataclasses.dataclass(frozen=True)
class Span:
    """One typed interval on the run's timeline (seconds, t0 <= t1)."""
    kind: str
    name: str
    t0: float
    t1: float
    bank: int = -1                  # -1: not bank-scoped (op/spill spans)
    args: dict = dataclasses.field(default_factory=dict)

    @property
    def dur_s(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass(frozen=True)
class CounterSample:
    """One sample of a (possibly per-bank) counter series."""
    name: str
    t: float
    value: float
    bank: int = -1


class SpanRecorder:
    """Append-only sink for spans, counter samples, and run metadata.

    With ``maxlen`` it keeps only the newest ``maxlen`` spans and the
    newest ``maxlen`` counter samples, for a recorder that lives as long
    as its process."""

    def __init__(self, maxlen: Optional[int] = None) -> None:
        self.spans = [] if maxlen is None else collections.deque(
            maxlen=maxlen)
        self.counters = [] if maxlen is None else collections.deque(
            maxlen=maxlen)
        self.meta: dict = {}

    def __len__(self) -> int:
        return len(self.spans)

    def span(self, kind: str, name: str, t0: float, t1: float,
             bank: int = -1, **args) -> None:
        """Record one span; ``args`` is the kind-specific payload."""
        if kind not in SPAN_KINDS:
            raise ValueError(f"unknown span kind {kind!r}; "
                             f"choose from {SPAN_KINDS}")
        self.spans.append(Span(kind=kind, name=name, t0=t0, t1=t1,
                               bank=bank, args=args))

    def counter(self, name: str, t: float, value: float,
                bank: int = -1) -> None:
        self.counters.append(CounterSample(name=name, t=t, value=value,
                                           bank=bank))

    # ---------------------------------------------------------- queries
    def spans_of(self, *kinds: str) -> Iterator[Span]:
        """Spans of the given kinds, in recorded order."""
        return (s for s in self.spans if s.kind in kinds)

    def banks(self) -> list[int]:
        """Sorted bank indices any span or counter touched."""
        seen = {s.bank for s in self.spans if s.bank >= 0}
        seen |= {c.bank for c in self.counters if c.bank >= 0}
        return sorted(seen)

    def bank_spans(self, bank: int, *kinds: str) -> list[Span]:
        """One bank's spans of the given kinds, in recorded order."""
        return [s for s in self.spans
                if s.bank == bank and (not kinds or s.kind in kinds)]

    def counter_samples(self, name: str, bank: int = -1) -> list:
        return [c for c in self.counters
                if c.name == name and c.bank == bank]

    def makespan_s(self) -> float:
        """Last op/port span end — the walked timeline's makespan (0.0
        when no op ran)."""
        return max((s.t1 for s in self.spans_of("op", "port")),
                   default=0.0)
