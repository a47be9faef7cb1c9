"""The staged simulation pipeline behind ``sim.run``.

Every arm flows through the same five stages::

    schedule  — resolve blocks and build the iteration's op stream
                (reversible pattern or whole-iteration activation
                buffering); ops carry *work*, not durations
    cost      — resolve the arm's cost model (``repro.sim.cost``) into an
                operating point and time the op stream: work → seconds at
                the point's clock, then simulate the timed schedule
    trace     — flatten the schedule onto one trace timeline; aggregate
                traffic, peak-live and lifetime numbers
    memory    — replay the trace through the bank-level ``repro.memory``
                controller (eDRAM banks, or the SRAM baseline's banks with
                an infinite retention floor and off-chip spills) at the
                cost model's clock; retention deadlines stay wall-clock
    energy    — systolic-array compute energy (scaled by the operating
                point's dynamic-energy factor), scalar cross-validation
                oracle, latency/TTA/ETA; assembles the ArmReport

Stages are pluggable: each is a ``(name, fn(arm, ctx))`` pair and
``Pipeline.with_stage`` / ``insert_after`` produce modified pipelines.
The closed-loop timeline model (``repro.sim.timeline``) is exactly such a
replacement: ``DEFAULT_PIPELINE.with_stage("memory", stage_timeline)`` —
selected by ``sim.run(arm, timing="timeline")``, the default.  The
additive model (``timing="additive"``) is this module's ``stage_memory``
and is kept bit-compatible as a cross-validation baseline.
"""
from __future__ import annotations

import dataclasses
import math
import multiprocessing as mp
import os
import time
from concurrent.futures import ProcessPoolExecutor, as_completed
from typing import Callable, Optional, Sequence, Tuple

from repro.core import edram as ed
from repro.core import hwmodel as hw
from repro.core import schedule as sc
from repro.core.lifetime import array_throughput
from repro.memory import trace as mtr
from repro.sim.arm import Arm
from repro.sim.cost import FixedClock, cost_dict, op_timer, resolve_cost
from repro.sim.report import ArmReport

# the SRAM tier stores FP16 values; one value per word
SRAM_WORD_BITS = 16


@dataclasses.dataclass
class SimContext:
    """Mutable scratchpad threaded through the stages; custom stages read
    and write whichever fields they need."""
    blocks: tuple = ()
    bits: float = 0.0              # bits per value (BFP on eDRAM, FP16 else)
    specs: tuple = ()              # flattened OpSpecs (utilization inputs)
    cost: object = None            # resolved OperatingPoint (cost stage)
    freq_hz: float = 0.0           # the operating point's clock
    compute_scale: float = 1.0     # dynamic-energy multiplier on compute
    R: float = 0.0                 # effective MAC/s at the operating point
    batch: float = 1.0
    fwd: object = None             # SimResult (reversible pattern)
    bwd: object = None
    combined: object = None        # SimResult (irreversible single timeline)
    events: list = dataclasses.field(default_factory=list)
    op_durations: dict = dataclasses.field(default_factory=dict)
    # the merged op schedule [(name, start_s, end_s), ...] in execution
    # order — the timeline model walks this
    op_schedule: list = dataclasses.field(default_factory=list)
    duration_s: float = 0.0
    read_bits: float = 0.0
    write_bits: float = 0.0
    peak_live_bits: float = 0.0
    max_lifetime_s: float = 0.0    # per-sample data lifetime
    mem_cfg: object = None         # EDRAMConfig the controller replayed with
    controller: object = None      # ControllerReport (None on scalar path)
    report: object = None          # ArmReport (set by the energy stage)
    # optional repro.obs.SpanRecorder (sim.run(trace=...)); stages that
    # support it record spans/counters — observation only, never timing
    recorder: object = None
    # free-form scratch for custom stages (e.g. repro.serve stashes its
    # traffic/engine statistics here for its energy stage to read)
    extra: dict = dataclasses.field(default_factory=dict)


# ------------------------------------------------------------------ stages

def stage_schedule(arm: Arm, ctx: SimContext) -> None:
    """Resolve the workload: blocks, value width, utilization specs.
    Timing is deliberately absent — the ``cost`` stage owns work→seconds."""
    cfg = arm.system
    blocks = arm.resolve_blocks()
    ctx.blocks = blocks
    ctx.bits = hw.BFP_BITS if cfg.use_edram else hw.FP16_BITS
    ctx.specs = tuple(s for b in blocks for s in (b.f1, b.f2, b.g))
    ctx.batch = max(blocks[0].f1.batch, 1)


def stage_cost(arm: Arm, ctx: SimContext) -> None:
    """Resolve the arm's cost model into an operating point and time the
    op stream: every downstream second — op durations, bank-port service,
    refresh pulse widths — derives from this point's clock, while
    retention deadlines stay wall-clock (temperature-set)."""
    cfg = arm.system
    point = resolve_cost(arm.cost, cfg)
    ctx.cost = point
    ctx.freq_hz = point.freq_hz
    ctx.compute_scale = point.compute_scale
    ctx.R = array_throughput(cfg.array, point.freq_hz, list(ctx.specs),
                             cfg.bfp_group)
    seconds = op_timer(point, ctx.R)
    if arm.reversible:
        ctx.fwd, ctx.bwd = sc.simulate_training_iteration(
            ctx.blocks, ctx.R, ctx.bits, op_seconds=seconds)
    else:
        ctx.combined = sc.simulate_irreversible_iteration(
            ctx.blocks, ctx.R, ctx.bits, op_seconds=seconds)


def stage_trace(arm: Arm, ctx: SimContext) -> None:
    """One trace timeline + aggregate traffic/lifetime numbers."""
    if arm.reversible:
        ctx.events, ctx.op_durations, ctx.duration_s = mtr.merge_traces(
            ctx.fwd, ctx.bwd)
        off = ctx.fwd.total_time
        ctx.op_schedule = list(ctx.fwd.schedule) + [
            (name, start + off, end + off)
            for name, start, end in ctx.bwd.schedule]
        ctx.read_bits = ctx.fwd.read_bits + ctx.bwd.read_bits
        ctx.write_bits = ctx.fwd.write_bits + ctx.bwd.write_bits
        ctx.peak_live_bits = max(ctx.fwd.peak_live_bits,
                                 ctx.bwd.peak_live_bits)
        # weight-stationary streaming: per-sample producer→consumer window
        ctx.max_lifetime_s = max(ctx.fwd.max_lifetime,
                                 ctx.bwd.max_lifetime) / ctx.batch
        return
    sim = ctx.combined
    ctx.events = list(sim.trace)
    ctx.op_durations = {name: end - start
                        for name, start, end in sim.schedule}
    ctx.op_schedule = list(sim.schedule)
    ctx.duration_s = sim.total_time
    ctx.read_bits = sim.read_bits
    ctx.write_bits = sim.write_bits
    ctx.peak_live_bits = sim.peak_live_bits
    # whole-iteration buffers hold every sample, so their residency IS the
    # data lifetime; transients stream per sample
    buffered = {e.tensor for e in sim.trace if e.buffered}
    life = [(t, d) for t, d in sim.lifetimes.items()]
    ctx.max_lifetime_s = max(
        [d if t in buffered else d / ctx.batch for t, d in life],
        default=0.0)


def _sram_mem_config(cfg: hw.SystemConfig) -> ed.EDRAMConfig:
    """The SRAM baseline's on-chip tier as controller geometry: the same
    bank/word machinery, SRAM access energies, no refresh."""
    return dataclasses.replace(
        cfg.edram,
        word_bits=SRAM_WORD_BITS,
        n_banks=cfg.sram_banks,
        bank_kb=cfg.onchip_bits / 8.0 / 1024.0 / cfg.sram_banks,
        read_pj_per_bit=cfg.edram.sram_read_pj_per_bit,
        write_pj_per_bit=cfg.edram.sram_write_pj_per_bit)


def memory_config(cfg: hw.SystemConfig):
    """The controller-replay parameters an arm's system implies:
    ``(mem_cfg, retention_s, refresh_policy)``.  eDRAM arms replay their
    own geometry; the SRAM baseline replays the same bank machinery with
    an infinite retention floor and refresh disabled.  Tiered arms
    (``cfg.tiers``) carry their geometry and retention floors on the
    ``TierSpec``s themselves — the eDRAM config only supplies the
    off-chip energy and the per-tier defaults."""
    if cfg.tiers:
        return cfg.edram, None, cfg.refresh_policy
    if cfg.use_edram:
        return cfg.edram, None, cfg.refresh_policy
    # SRAM holds data indefinitely: infinite retention, never refresh
    return _sram_mem_config(cfg), math.inf, "none"


def stage_memory(arm: Arm, ctx: SimContext) -> None:
    """Trace-driven replay through the bank-level controller (additive
    stall model; the timeline model's stage lives in
    ``repro.sim.timeline``)."""
    cfg = arm.system
    if not cfg.use_controller:
        return
    mem_cfg, retention, policy = memory_config(cfg)
    ctx.mem_cfg = mem_cfg
    ctx.controller = mtr.replay(
        ctx.events, mem_cfg, temp_c=cfg.temp_c, duration_s=ctx.duration_s,
        refresh_policy=policy, alloc_policy=cfg.alloc_policy,
        freq_hz=ctx.freq_hz or cfg.freq_hz, sample_scale=ctx.batch,
        op_durations=ctx.op_durations, retention_s=retention,
        granularity=cfg.refresh_granularity,
        reads_restore=cfg.reads_restore, recorder=ctx.recorder,
        backend=cfg.replay_backend, tiers=cfg.tiers)


def _buffered_partition(events) -> tuple[float, list]:
    """Peak live bits of the streamed transients, and the whole-iteration
    buffers as (tensor, bits) in first-write order."""
    live: dict = {}
    peak = 0.0
    saves: list = []
    seen: set = set()
    for ev in events:
        if ev.buffered:
            if ev.kind in ("alloc", "write") and ev.tensor not in seen:
                seen.add(ev.tensor)
                saves.append((ev.tensor, ev.bits))
            continue
        if ev.kind in ("alloc", "write"):
            live[ev.tensor] = ev.bits
            peak = max(peak, sum(live.values()))
        elif ev.kind == "free":
            live.pop(ev.tensor, None)
    return peak, saves


def _scalar_memory(arm: Arm, ctx: SimContext):
    """The closed-form cross-validation oracle: per-sample streamed
    transients on-chip, whole-iteration buffers held greedily until
    capacity runs out, one store + one load per spilled buffer.

    When even the per-sample transients overflow on-chip capacity, the
    proportional overflow term below moves the overflowing share of the
    streamed traffic off-chip — a first-order model of the controller's
    per-tensor spills (it has no placement order), so ``oracle_rel_err``
    stays a useful cross-check instead of growing with the overflow
    (the PR 2 carried-over debt).  On the pinned workloads the streamed
    set fits and the term is exactly zero.

    Returns ``(MemoryEnergy, offchip_bits, refresh_free)``.
    """
    cfg = arm.system
    transient_peak, saves = _buffered_partition(ctx.events)
    stream_bits = transient_peak / ctx.batch
    budget = cfg.onchip_bits - stream_bits
    held = spilled = 0.0
    for _, bits in saves:
        if held + bits <= budget:
            held += bits
        else:
            spilled += bits
    offchip_bits = 2.0 * spilled          # store once, load once
    # a spilled buffer's store/load traffic moves off-chip, not on-chip
    read_bits = ctx.read_bits - spilled
    write_bits = ctx.write_bits - spilled
    overflow = max(0.0, stream_bits - cfg.onchip_bits)
    if overflow > 0.0:
        # streamed transients themselves overflow capacity: the
        # overflowing fraction of the streamed working set forces the
        # same fraction of the remaining on-chip traffic through DRAM
        frac = overflow / stream_bits
        off_r, off_w = read_bits * frac, write_bits * frac
        offchip_bits += off_r + off_w
        read_bits -= off_r
        write_bits -= off_w
    if cfg.use_edram:
        rf = ed.refresh_free(ctx.max_lifetime_s, cfg.temp_c)
        mem = ed.edram_energy(cfg.edram, read_bits, write_bits,
                              ctx.peak_live_bits, ctx.duration_s,
                              cfg.temp_c, needs_refresh=not rf)
        if offchip_bits:
            mem = dataclasses.replace(
                mem, offchip_j=offchip_bits * cfg.edram.dram_pj_per_bit
                * 1e-12)
        return mem, offchip_bits, rf
    mem = ed.sram_energy(cfg.edram, read_bits, write_bits, offchip_bits)
    return mem, offchip_bits, True


def stage_energy(arm: Arm, ctx: SimContext) -> None:
    """Compute energy + latency accounting; assembles the ArmReport."""
    cfg = arm.system
    blocks = ctx.blocks
    # gradient ops (U1a/U1w/U2a/U2w); the reversible arm also pays the
    # eq-2 input recompute (the paper's accepted overhead, §III)
    macs = sum(s.macs for s in ctx.specs) + sum(
        b.f1.macs_out * 2 + b.f2.macs_out * 2 for b in blocks)
    if arm.reversible:
        macs += sum(b.f1.macs_out + b.f2.macs_out for b in blocks)
    # dynamic compute energy at the operating point (∝ V², ×1.0 fixed)
    compute_j = macs * (cfg.mac_pj if cfg.use_edram
                        else cfg.mac_pj_fp16) * 1e-12 * ctx.compute_scale

    scalar_mem, scalar_offchip, rf_scalar = _scalar_memory(arm, ctx)
    ctrl = ctx.controller
    if ctrl is not None:
        memory_j = ctrl.energy.total_j
        stall_s = ctrl.stall_s
        offchip_bits = ctrl.offchip_bits
        # the bank-level verdict: refresh-free iff no bank refreshed and no
        # over-retention bank was left unrefreshed (data loss)
        rf = ((not any(b.refreshed for b in ctrl.banks)) and ctrl.safe
              if cfg.use_edram else True)
    else:
        memory_j = scalar_mem.total_j
        stall_s = 0.0
        offchip_bits = scalar_offchip
        rf = rf_scalar if cfg.use_edram else True

    latency_s = ctx.duration_s + stall_s + (
        offchip_bits / cfg.offchip_bw_bps if offchip_bits else 0.0)
    # leakage burns on the whole on-chip tier for the iteration's
    # wall-clock duration — the term that stops slow DVFS points from
    # looking free on energy (opt-in: see SystemConfig.charge_leakage)
    leakage_j = 0.0
    if cfg.charge_leakage:
        if cfg.tiers:
            # each tier leaks at its own cell's rate over its own
            # capacity (the SRAM share is what the iso-area sweep pays)
            leakage_j = sum(t.leakage_mw * 1e-3 * latency_s
                            for t in cfg.tiers)
        else:
            mw_per_kb = (cfg.edram.leakage_mw_per_kb if cfg.use_edram
                         else cfg.edram.sram_leakage_mw_per_kb)
            leakage_j = mw_per_kb * 1e-3 \
                * (cfg.onchip_bits / 8.0 / 1024.0) * latency_s
    energy_j = compute_j + memory_j + leakage_j
    rel_err = (abs(memory_j - scalar_mem.total_j) / scalar_mem.total_j
               if scalar_mem.total_j > 0 else 0.0)
    iters = arm.iters_to_target
    if ctx.recorder is not None:
        ctx.recorder.meta.setdefault("arm", arm.name)
        ctx.recorder.counter("compute_j", latency_s, compute_j)
        ctx.recorder.counter("leakage_j", latency_s, leakage_j)
        ctx.recorder.counter("energy_j", latency_s, energy_j)
    ctx.report = ArmReport(
        arm=arm.name,
        reversible=arm.reversible,
        latency_s=latency_s,
        energy_j=energy_j,
        compute_j=compute_j,
        memory_j=memory_j,
        scalar_memory_j=scalar_mem.total_j,
        oracle_rel_err=rel_err,
        stall_s=stall_s,
        max_lifetime_s=ctx.max_lifetime_s,
        refresh_free=rf,
        peak_live_bits=ctx.peak_live_bits,
        offchip_bits=offchip_bits,
        iters_to_target=iters,
        tta_s=latency_s * iters if iters else None,
        eta_j=energy_j * iters if iters else None,
        timing=ctrl.timing if ctrl is not None else "scalar",
        refresh_stall_s=ctrl.refresh_stall_s if ctrl is not None else 0.0,
        refresh_hidden_j=ctrl.refresh_hidden_j if ctrl is not None else 0.0,
        leakage_j=leakage_j,
        rows_refreshed=ctrl.rows_refreshed if ctrl is not None else 0,
        row_hidden_frac=ctrl.row_hidden_frac if ctrl is not None else 0.0,
        freq_hz=ctx.freq_hz or cfg.freq_hz,
        pulse_exceeds_retention=(ctrl.pulse_exceeds_retention
                                 if ctrl is not None else False),
        timeline=(dict(ctrl.timeline)
                  if ctrl is not None and ctrl.timeline else {}),
        tiers=(tuple(dict(t) for t in ctrl.tiers)
               if ctrl is not None and ctrl.tiers else ()),
        config=_config_dict(arm),
        memory=_memory_dict(ctrl),
        controller=ctrl,
        trace=ctx.recorder,
    )


def _config_dict(arm: Arm) -> dict:
    """The fully resolved arm as a JSON-safe dict."""
    system = dataclasses.asdict(arm.system)
    if system.get("tiers"):
        # asdict keeps the TierSpec tuple a tuple; JSON reads it back as
        # a list, so serialize it as one for a lossless round trip
        system["tiers"] = [dict(t) for t in system["tiers"]]
    return {
        "name": arm.name,
        "reversible": arm.reversible,
        "iters_to_target": arm.iters_to_target,
        "cost": cost_dict(arm.cost),
        "system": system,
        "workload": (dataclasses.asdict(arm.workload)
                     if arm.workload is not None and arm.blocks is None
                     else None),
        "blocks": ([dataclasses.asdict(b) for b in arm.blocks]
                   if arm.blocks is not None else None),
    }


def _memory_dict(ctrl) -> dict:
    """ControllerReport as a JSON-safe dict (empty-ish on the scalar path)."""
    if ctrl is None:
        return {"mode": "scalar", "banks": [], "spilled": []}
    out = {
        "mode": "controller",
        "timing": ctrl.timing,
        "refresh_policy": ctrl.refresh_policy,
        "granularity": ctrl.granularity,
        "rows_refreshed": ctrl.rows_refreshed,
        "row_hidden_frac": ctrl.row_hidden_frac,
        "alloc_policy": ctrl.alloc_policy,
        "temp_c": ctrl.temp_c,
        "duration_s": ctrl.duration_s,
        # strict-JSON safety: math.inf (SRAM's never-refresh floor) is not
        # representable in plain JSON, so it serializes as null
        "retention_s": (ctrl.retention_s
                        if math.isfinite(ctrl.retention_s) else None),
        "interval_s": (ctrl.interval_s
                       if math.isfinite(ctrl.interval_s) else None),
        "pulse_exceeds_retention": ctrl.pulse_exceeds_retention,
        "read_j": ctrl.read_j,
        "restore_j": ctrl.restore_j,
        "write_j": ctrl.write_j,
        "refresh_j": ctrl.refresh_j,
        "refresh_read_j": ctrl.refresh_read_j,
        "refresh_restore_j": ctrl.refresh_restore_j,
        "refresh_hidden_j": ctrl.refresh_hidden_j,
        "offchip_j": ctrl.offchip_j,
        "stall_s": ctrl.stall_s,
        "conflict_stall_s": ctrl.conflict_stall_s,
        "refresh_stall_s": ctrl.refresh_stall_s,
        "spill_bits": ctrl.spill_bits,
        "offchip_bits": ctrl.offchip_bits,
        "refresh_count": ctrl.refresh_count,
        "safe": ctrl.safe,
        "spilled": list(ctrl.spilled_tensors),
        "evicted": list(ctrl.evicted_tensors),
        "timeline": dict(ctrl.timeline) if ctrl.timeline else None,
        "banks": [dataclasses.asdict(b) for b in ctrl.banks],
    }
    # only hybrid replays carry tiers; omitted otherwise so the classic
    # reports' serialized shape (and their golden pins) stays unchanged
    if ctrl.tiers:
        out["tiers"] = [dict(t) for t in ctrl.tiers]
    return out


# ---------------------------------------------------------------- pipeline

Stage = Tuple[str, Callable[[Arm, SimContext], None]]

DEFAULT_STAGES: Tuple[Stage, ...] = (
    ("schedule", stage_schedule),
    ("cost", stage_cost),
    ("trace", stage_trace),
    ("memory", stage_memory),
    ("energy", stage_energy),
)


@dataclasses.dataclass(frozen=True)
class Pipeline:
    """An ordered tuple of named stages; immutable — the ``with_*``
    helpers return modified copies."""
    stages: Tuple[Stage, ...] = DEFAULT_STAGES

    def stage_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.stages)

    def _require(self, name: str) -> None:
        if name not in self.stage_names():
            raise KeyError(f"no stage {name!r}; have "
                           f"{', '.join(self.stage_names())}")

    def with_stage(self, name: str, fn: Callable) -> "Pipeline":
        """Replace stage ``name`` with ``fn(arm, ctx)``.

        Args:
            name: an existing stage name (``schedule`` / ``cost`` /
                ``trace`` / ``memory`` / ``energy`` on the default
                pipeline); ``KeyError`` if absent.
            fn: callable ``(arm: Arm, ctx: SimContext) -> None`` that
                mutates ``ctx`` in place — e.g. set ``ctx.controller`` to
                a custom ``ControllerReport`` (this is how the timeline
                model replaces the ``memory`` stage).

        Returns:
            A new ``Pipeline``; ``self`` is unchanged (frozen).
        """
        self._require(name)
        return Pipeline(tuple((n, fn if n == name else f)
                              for n, f in self.stages))

    def insert_after(self, name: str, new_name: str,
                     fn: Callable) -> "Pipeline":
        """Insert stage ``new_name`` (same ``fn(arm, ctx)`` contract as
        :meth:`with_stage`) right after ``name`` — e.g. a post-processor
        that rewrites the controller report before energy accounting.
        Returns a new ``Pipeline``; ``self`` is unchanged."""
        self._require(name)
        out: list = []
        for n, f in self.stages:
            out.append((n, f))
            if n == name:
                out.append((new_name, fn))
        return Pipeline(tuple(out))

    def run(self, arm: Arm, *, recorder=None, profile: bool = False) -> tuple:
        """Run all stages; returns ``(ArmReport, SimContext)``.

        ``recorder`` (a ``repro.obs.SpanRecorder``) is threaded to every
        stage via ``ctx.recorder`` and ends up on ``report.trace``;
        ``profile=True`` wall-clocks each stage (``time.perf_counter``)
        into ``report.profile`` — both are pure observation, so every
        report scalar is bit-identical either way."""
        ctx = SimContext()
        ctx.recorder = recorder
        stages_s: dict = {}
        for name, fn in self.stages:
            if profile:
                t0 = time.perf_counter()
                fn(arm, ctx)
                stages_s[name] = time.perf_counter() - t0
            else:
                fn(arm, ctx)
        if profile and ctx.report is not None:
            ctx.report = dataclasses.replace(
                ctx.report,
                profile={"stages": stages_s,
                         "total_s": sum(stages_s.values())})
        return ctx.report, ctx


DEFAULT_PIPELINE = Pipeline()

# stall-model names sim.run/sweep resolve; "timeline" is the default
TIMINGS = ("additive", "timeline")
DEFAULT_TIMING = "timeline"


def resolve_pipeline(timing: Optional[str] = None,
                     pipeline: Optional[Pipeline] = None) -> Pipeline:
    """The pipeline a ``timing`` name selects: ``"additive"`` is
    :data:`DEFAULT_PIPELINE`, ``"timeline"`` swaps in the closed-loop
    memory stage.  An explicit ``pipeline`` wins and excludes
    ``timing``."""
    if pipeline is not None:
        if timing is not None:
            raise ValueError("pass either pipeline= or timing=, not both")
        return pipeline
    timing = DEFAULT_TIMING if timing is None else timing
    if timing == "additive":
        return DEFAULT_PIPELINE
    if timing == "timeline":
        from repro.sim.timeline import TIMELINE_PIPELINE
        return TIMELINE_PIPELINE
    raise ValueError(f"unknown timing {timing!r}; choose from {TIMINGS}")


def run(arm: Arm, pipeline: Optional[Pipeline] = None, *,
        timing: Optional[str] = None, trace=None,
        profile: bool = False) -> ArmReport:
    """Simulate one arm through the staged pipeline.

    Args:
        arm: the declarative :class:`~repro.sim.arm.Arm` (workload +
            ``SystemConfig`` + memory policies).
        pipeline: explicit stage list; mutually exclusive with
            ``timing``.
        timing: stall-model selector — ``"timeline"`` (default; the
            closed-loop event-interleaved model where refresh hides in
            bank-idle windows) or ``"additive"`` (per-op overshoot and
            per-pulse serialization summed; the PR-2-compatible
            cross-validation baseline).
        trace: flight-recorder opt-in — ``True`` allocates a fresh
            ``repro.obs.SpanRecorder``, or pass your own; it records
            typed spans (op/port/refresh/spill) and counter series as
            the engine runs and lands on ``report.trace`` (export with
            ``repro.obs.export_chrome_trace``, check with
            ``repro.obs.reconcile``).  Pure observation: with or
            without it, every report number is bit-identical.
        profile: wall-clock each pipeline stage into
            ``report.profile["stages"]`` (also observation-only).

    Returns:
        An :class:`~repro.sim.report.ArmReport` — latency/energy in
        s/J, the controller's per-bank breakdown under ``.memory``, and
        (timeline model) ``refresh_stall_s`` / ``refresh_hidden_j`` plus
        the ``.timeline`` makespan summary.
    """
    recorder = trace
    if trace is True:
        from repro.obs.recorder import SpanRecorder
        recorder = SpanRecorder()
    # an arm that owns a pipeline family (e.g. the repro.serve arms, whose
    # schedule/trace/energy stages are serving-specific) maps the timing
    # name to its own Pipeline; an explicit pipeline= still wins
    if pipeline is None and hasattr(arm, "select_pipeline"):
        pipe = arm.select_pipeline(
            DEFAULT_TIMING if timing is None else timing)
    else:
        pipe = resolve_pipeline(timing, pipeline)
    report, _ = pipe.run(arm, recorder=recorder, profile=profile)
    return report


def _with_freq(arm: Arm, f) -> Arm:
    """One frequency-axis grid point: a number pins a ``FixedClock`` at
    that many Hz; a cost model (anything with ``resolve``) is installed
    as-is — e.g. a ``DVFSState`` for voltage-scaled points."""
    if hasattr(f, "resolve"):
        return arm.with_cost(f)
    return arm.with_cost(FixedClock(freq_hz=float(f)))


def _with_split(arm: Arm, s) -> Arm:
    """One iso-area-split grid point: replace the arm's memory with the
    hybrid SRAM+eDRAM tiering at SRAM area share ``s`` (see
    ``repro.memory.tiers.iso_area_tiers``) under the ``lifetime_tiered``
    routing policy.  ``onchip_bits`` tracks the tiers' total capacity so
    the scalar oracle sees the same budget the controller enforces."""
    from repro.memory.tiers import iso_area_tiers
    tiers = iso_area_tiers(arm.system.edram, float(s),
                           sram_banks=arm.system.sram_banks)
    return arm.with_system(
        tiers=tiers, alloc_policy="lifetime_tiered", use_edram=True,
        onchip_bits=sum(t.capacity_bits for t in tiers))


def _expand_grid(arms: Sequence[Arm], workloads, temps, freqs,
                 splits=None) -> list:
    """``arms × workloads × temps × freqs × splits`` as concrete arms,
    in deterministic (arms-outer, splits-inner) order."""
    out = []
    for arm in arms:
        for wl in (workloads if workloads is not None else (None,)):
            if wl is None:
                a = arm
            elif isinstance(wl, dict):
                a = arm.with_workload(**wl)
            else:                       # a WorkloadSpec replaces wholesale
                a = dataclasses.replace(arm, workload=wl, blocks=None)
            for t in (temps if temps is not None else (None,)):
                at = a if t is None else a.with_system(temp_c=t)
                for f in (freqs if freqs is not None else (None,)):
                    af = at if f is None else _with_freq(at, f)
                    for s in (splits if splits is not None else (None,)):
                        out.append(af if s is None else _with_split(af, s))
    return out


def _sweep_one(job: tuple) -> ArmReport:
    """Process-pool worker: simulate one (arm, timing, pipeline, profile)
    job.  Top-level so it pickles by reference."""
    arm, timing, pipeline, profile = job
    return run(arm, pipeline, timing=timing, profile=profile)


def sweep(arms: Sequence[Arm], pipeline: Optional[Pipeline] = None, *,
          timing: Optional[str] = None,
          workloads: Optional[Sequence] = None,
          temps: Optional[Sequence[float]] = None,
          freqs: Optional[Sequence] = None,
          splits: Optional[Sequence[float]] = None,
          parallel=None, profile: bool = False,
          progress=None) -> list:
    """Simulate a grid of arms; one :class:`ArmReport` per grid point.

    Args:
        arms: the arms to sweep.
        pipeline: explicit stage list (mutually exclusive with
            ``timing``); must be picklable (module-level stage
            functions) when ``parallel`` is used.
        timing: stall-model selector, as in :func:`run`.
        workloads: optional workload axis — each entry is either a
            ``WorkloadSpec`` (replaces the arm's workload) or a dict of
            ``WorkloadSpec`` field overrides (``with_workload``).
        temps: optional die-temperature axis (°C, ``with_system``).
        freqs: optional operating-point axis — each entry is a frequency
            in Hz (installs ``FixedClock(freq_hz=...)``) or a cost model
            (e.g. ``DVFSState``; installed via ``Arm.with_cost``).
            Retention deadlines stay wall-clock, so refresh hiding and
            the refresh-free verdict move across this axis.
        splits: optional iso-area SRAM:eDRAM capacity-split axis — each
            entry is an SRAM area share in [0, 1]; the grid point
            replaces the arm's memory with the hybrid tiering from
            ``repro.memory.tiers.iso_area_tiers`` under the
            ``lifetime_tiered`` routing policy (``0.0`` is the stock
            all-eDRAM array, ``1.0`` the all-SRAM iso-area equivalent).
        parallel: ``None``/``0``/``1`` → sequential; ``True`` → one
            worker per CPU; an int → that many process-pool workers.
        profile: wall-clock each grid point's stages into its report's
            ``profile`` field (aggregate across the grid with
            ``repro.obs.aggregate_profiles``).
        progress: per-completion visibility for long grids — ``True``
            emits a ``repro.obs.log`` info line per finished point
            (grid index, arm, elapsed seconds) to stderr regardless of
            the ``REPRO_LOG`` threshold (you asked for it), or pass a
            callable ``progress(i, arm_name, elapsed_s)``.  Completion
            order, not grid order; the returned list stays in grid
            order.

    Returns:
        Reports in deterministic grid order — ``arms`` outermost, then
        ``workloads``, then ``temps``, then ``freqs``, then ``splits``
        — identical regardless of ``parallel`` (results are collected
        in submission order).
    """
    resolve_pipeline(timing, pipeline)      # validate eagerly
    grid = _expand_grid(arms, workloads, temps, freqs, splits)
    jobs = [(a, timing, pipeline, profile) for a in grid]
    if progress is True:
        from repro.obs import log as _obslog
        progress = (lambda i, name, dt:
                    _obslog.log("info", "sweep_point", force=True,
                                index=i, arm=name, elapsed_s=dt))
    t0 = time.perf_counter()
    workers = (os.cpu_count() or 1) if parallel is True else int(parallel or 0)
    if workers > 1 and len(jobs) > 1:
        # spawn: a forked child would inherit a parent that may already
        # hold the accelerator through JAX
        with ProcessPoolExecutor(max_workers=min(workers, len(jobs)),
                                 mp_context=mp.get_context("spawn")) as ex:
            if progress is None:
                return list(ex.map(_sweep_one, jobs))
            futs = {ex.submit(_sweep_one, j): i for i, j in enumerate(jobs)}
            for fut in as_completed(futs):
                i = futs[fut]
                progress(i, grid[i].name, time.perf_counter() - t0)
            return [fut.result() for fut in futs]  # dicts keep insert order
    out = []
    for i, j in enumerate(jobs):
        out.append(_sweep_one(j))
        if progress is not None:
            progress(i, grid[i].name, time.perf_counter() - t0)
    return out
