"""``repro.obs`` — the opt-in flight-recorder/observability layer.

For the chip path (model and step programs), two modules that the
package does not import itself:

- :mod:`repro.obs.scopes` — the model's layer names (``LAYERS``, each a
  ``jax.named_scope`` in the program) and ``op_layers``, which maps a
  compiled program's instructions to them.
- :mod:`repro.obs.runtime` — the process's compile spans and data-layer
  counters, on the profiler's realtime clock.

For the simulator, four small pieces, none of which touch simulation
results:

- :mod:`repro.obs.recorder` — :class:`SpanRecorder`: typed spans (op
  execution, port service, hidden vs stalling refresh pulses, off-chip
  spills) plus counter series (per-bank occupancy, cumulative energy),
  recorded by the timeline engine when ``sim.run(arm, trace=...)``
  passes one in.
- :mod:`repro.obs.export` — Chrome Trace Event JSON (one pid per
  controller/bank) that opens directly in Perfetto.
- :mod:`repro.obs.reconcile` — re-derives ``stall_s`` /
  ``refresh_stall_s`` / ``refresh_hidden_j`` / ``rows_refreshed`` from
  the spans and asserts exact equality with the ``ArmReport``, so the
  trace is a checkable ground truth rather than a parallel bookkeeping
  path.
- :mod:`repro.obs.log` — structured stderr diagnostics (level via the
  ``REPRO_LOG`` env var) keeping benchmark stdout machine-separable.

Quick capture::

    from repro import obs, sim

    rep = sim.run(sim.get_arm("DuDNN+CAMEL"), trace=True)
    obs.export_chrome_trace(rep.trace, "camel.trace.json", report=rep)
    assert obs.reconcile(rep.trace, rep).ok

See ``docs/observability.md`` for the span/counter semantics and the
stage profiler (``sim.run(profile=True)``).
"""
from repro.obs import log
from repro.obs.export import (chrome_trace_events, export_chrome_trace,
                              recorder_from_trace, trace_dict)
from repro.obs.recorder import (SPAN_KINDS, CounterSample, Span,
                                SpanRecorder)
from repro.obs.reconcile import (RECONCILED_FIELDS, FieldCheck,
                                 ReconcileResult, derive, reconcile)

__all__ = [
    "SPAN_KINDS", "RECONCILED_FIELDS", "CounterSample", "FieldCheck",
    "ReconcileResult", "Span", "SpanRecorder", "aggregate_profiles",
    "chrome_trace_events", "derive", "export_chrome_trace", "log",
    "reconcile", "recorder_from_trace", "trace_dict",
]


def aggregate_profiles(reports) -> dict:
    """Aggregate ``sim.sweep(..., profile=True)`` stage timings across a
    grid: ``{stage: {"total_s", "mean_s", "max_s"}}`` over the reports
    that carry a profile (``report.profile["stages"]``)."""
    stages: dict[str, list[float]] = {}
    for rep in reports:
        prof = rep.profile if hasattr(rep, "profile") else rep.get("profile")
        if not prof:
            continue
        for name, wall in prof["stages"].items():
            stages.setdefault(name, []).append(wall)
    return {name: {"total_s": sum(walls), "mean_s": sum(walls) / len(walls),
                   "max_s": max(walls)}
            for name, walls in stages.items()}
