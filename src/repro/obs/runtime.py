"""Counters of the chip path, held in this process's memory.

Four :class:`~repro.obs.recorder.SpanRecorder` instances, one per process:

- :data:`compiles`: a span of kind ``compile`` for each of JAX's compile
  events (tracing to a jaxpr, lowering, and the backend compile, which also
  covers a load from the persistent compilation cache), named by
  :data:`COMPILE_EVENTS` with the program's ``fun_name`` in its args.
  :func:`record_compiles` installs the listener; importing
  ``repro.train.train_step`` or ``repro.train.serve_step`` calls it.  It
  fires only when JAX compiles, so a steady step loop pays nothing.
- :data:`data`: the data layer's counters, the newest :data:`MAXLEN`
  samples: ``prefetch_wait_s`` for each ``Prefetcher.next`` call (the
  seconds it blocked on its queue) and ``prefetch_produce_s`` for each
  batch its producer made (the seconds that took).
- :data:`moe_routes`: the newest :data:`MAXLEN` MoE lowerings, each a
  zero-width span of kind ``lowering`` that ``repro.models.moe`` records
  while a program is traced: named by the route (``index`` or
  ``onehot``), with the group size, experts and capacity in its args.  A
  program runs the route it was lowered with, so :func:`moe_routes_of`
  says which route each step program took.  The args also hold the
  router's width (``routed``) and the first held expert (``offset``).
- :data:`ssd_plans`: the newest :data:`MAXLEN` SSD mixer lowerings
  (``repro.models.ssm``), named ``ssd``: chunk, heads, groups of B and C,
  and the bytes of the largest temporary of one chunk; read per program
  by :func:`ssd_plans_of`.

Stamps (span ``t0``/``t1``, sample ``t``) are realtime nanoseconds, the
profiler's clock: a trace's event times are nanoseconds after its
``profile_start_time``, so a compile or a data wait can be placed against
a device trace's idle gaps.
"""
from __future__ import annotations

import threading
import time

from repro.obs.recorder import SpanRecorder

MAXLEN = 4096

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}

compiles = SpanRecorder()
data = SpanRecorder(maxlen=MAXLEN)
moe_routes = SpanRecorder(maxlen=MAXLEN)
ssd_plans = SpanRecorder(maxlen=MAXLEN)

_installed = False
_lock = threading.Lock()


def _on_event(event: str, start_time: float, end_time: float,
              **kwargs) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is not None:
        compiles.span("compile", name, int(start_time * 1e9),
                      int(end_time * 1e9),
                      fun_name=str(kwargs.get("fun_name", "")))


def record_compiles() -> None:
    """Record JAX's compile events into :data:`compiles` from now on; a
    second call does nothing."""
    global _installed
    with _lock:
        if not _installed:
            import jax
            jax.monitoring.register_event_time_span_listener(_on_event)
            _installed = True


def backend_compiles(fun_name: str) -> int:
    """Backend compiles (or persistent-cache loads) of the jitted function
    ``fun_name`` recorded in this process."""
    want = f"jit({fun_name})"
    return sum(1 for s in compiles.spans_of("compile")
               if s.name == "backend" and s.args.get("fun_name") == want)


def record_moe_route(route: str, group: int, experts: int,
                     capacity: int, routed: int | None = None,
                     offset: int = 0) -> None:
    """Record one MoE layer's lowering; called while a program is traced.
    ``experts`` are those the layer holds, from router output ``offset``
    on, of the ``routed`` the router scores (``experts`` when None)."""
    t = int(time.time() * 1e9)        # the clock of JAX's compile events
    moe_routes.span("lowering", route, t, t, group=group, experts=experts,
                    capacity=capacity, routed=routed or experts,
                    offset=offset)


def record_ssd_plan(chunk: int, heads: int, groups: int,
                    temp_bytes: int) -> None:
    """Record one SSD mixer's lowering; called while a program is traced:
    its chunk, heads and groups of B and C, and the bytes of its largest
    temporary of one chunk."""
    t = int(time.time() * 1e9)
    ssd_plans.span("lowering", "ssd", t, t, chunk=chunk, heads=heads,
                   groups=groups, temp_bytes=temp_bytes)


def _lowered_in(recorder: SpanRecorder, fun_name: str) -> list:
    traces = [(s.t0, s.t1) for s in compiles.spans_of("compile")
              if s.name == "trace" and s.args.get("fun_name") == fun_name]
    return [r for r in recorder.spans
            if any(a <= r.t0 <= b for a, b in traces)]


def moe_routes_of(fun_name: str) -> list:
    """The MoE lowerings recorded while the jitted function ``fun_name``
    was traced, in order (its ``trace`` compile spans hold them)."""
    return _lowered_in(moe_routes, fun_name)


def ssd_plans_of(fun_name: str) -> list:
    """The SSD lowerings recorded while ``fun_name`` was traced, in order."""
    return _lowered_in(ssd_plans, fun_name)
