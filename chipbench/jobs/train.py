"""Duplex fine-tuning: the program's jitted train step, fed through its
``Prefetcher`` from the benchmark's seeded Zipf source, steps back to back.

Traffic keys: ``batch``, ``seq``, ``zipf_a``, ``check_steps`` (the steps of
set-up that the reference follows).

Set-up builds the state (weights drawn on the device in one call from the
seed), compiles the step and runs the first ``check_steps`` steps through
the window's own call and feed; the window then goes on with that same
state.  After the window the reference runs those first steps again, from
the seed alone, and the run compares per step the loss, the per-leaf norms
of the first gradient as the optimizer got it (its momentum after one
step), and the per-leaf norms of the branch's change over the steps.
"""
from __future__ import annotations

import math
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, weights, work
from chipbench.common import span
from chipbench.tokens import ZipfSource


def run(cell, seed: int, seconds: float, trace: bool, clock, hooks=None,
        control: bool = False) -> common.Outcome:
    from repro.data.pipeline import Prefetcher
    from repro.distributed import ctx, sharding as sh
    from repro.launch.cells import activation_rules, duplex_tcfg
    from repro.launch.mesh import make_host_mesh
    from repro.models import layers as L
    from repro.train import train_step as ts
    from chipbench import spec

    hooks = hooks or {}
    cfg, tr = cell.config, cell.traffic
    ref_mod = spec.reference_module(cell)
    entry, mc = common.program_config(cfg)
    tcfg = duplex_tcfg(mc)
    _check_duplex(tcfg, cfg["duplex"])
    policy = L.Policy(compute_dtype=jnp.bfloat16)
    devices = jax.devices()[:cell.chips]
    mesh = make_host_mesh(devices=devices)
    b, s, n_check = tr["batch"], tr["seq"], tr["check_steps"]

    with mesh, ctx.activation_sharding(mesh, activation_rules(mc, mesh)):
        shapes = jax.eval_shape(
            lambda k: ts.init_state(k, entry, mc, tcfg, policy),
            jax.random.PRNGKey(0))
        common.check_shapes(shapes["backbone"], ref_mod.backbone_shapes(cfg),
                            "backbone")
        common.check_shapes(shapes["branch"], ref_mod.branch_shapes(cfg),
                            "branch")
        specs = sh.to_named(sh.state_pspecs(shapes, mesh), mesh)
        init = cfg["init"]

        def make_state(key):
            return {"step": jnp.zeros((), jnp.int32),
                    "backbone": weights.make_tree(key, "backbone",
                                                  shapes["backbone"], init),
                    "branch": weights.make_tree(key, "branch",
                                                shapes["branch"], init),
                    "opt": jax.tree_util.tree_map(
                        lambda x: jnp.zeros(x.shape, x.dtype), shapes["opt"])}

        state = jax.jit(make_state, out_shardings=specs)(
            weights.seed_key(seed))
        step = jax.jit(ts.make_train_step(entry, mc, tcfg, policy),
                       donate_argnums=0)
        step = hooks.get("train_step", lambda f: f)(step)
        batch_sharding = jax.NamedSharding(mesh, sh.batch_pspec((b, s), mesh))
        source = ZipfSource(seed, b, s, cfg["vocab_size"], tr["zipf_a"])
        feed = Prefetcher(source, depth=2)
        try:
            # the checked first steps: the window's own call and feed
            branch0 = jax.device_get(state["branch"])
            losses = []
            for i in range(n_check):
                state, m = step(state, jax.device_put(feed.next(),
                                                      batch_sharding))
                losses.append(float(m["loss"]))
                if i == 0:
                    mu1 = jax.device_get(state["opt"]["mu"])
            branch_n = jax.device_get(state["branch"])
            setup_s = clock()
            if control:
                memory = common.compiled_memory(step, state, jax.device_put(
                    source.batch(0), batch_sharding))

            tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
                else None
            if trace:
                jax.profiler.start_trace(tdir)
            steps, failed, data_wait = 0, 0, 0.0
            with common.no_gc():
                t0 = clock()
                with span("window"):
                    pending = None
                    while True:
                        t = clock()
                        with span("data"):
                            batch = feed.next()
                        data_wait += clock() - t
                        with span("dispatch"):
                            state, m = step(state, jax.device_put(
                                batch, batch_sharding))
                        steps += 1
                        if pending is not None:
                            with span("sync"):
                                failed += not math.isfinite(float(pending))
                        pending = m["loss"]
                        if clock() - t0 >= seconds:
                            break
                    with span("sync"):
                        failed += not math.isfinite(float(pending))
                        jax.block_until_ready(state)
                window_s = clock() - t0
            if trace:
                jax.profiler.stop_trace()
        finally:
            feed.close()
        peak = common.memory_peak_bytes(devices)
        del state, m

    tokens = b * s
    flops = work.train_step_flops(cfg, cfg["duplex"], b, s)
    out = common.Outcome(
        end_to_end={"train_tokens_per_s": steps * tokens / window_s,
                    "setup_s": setup_s, "peak_hbm_gib": peak / 2**30},
        counters={"steps": steps, "window_s": window_s,
                  "data_wait_s": data_wait, "step_flops": flops},
        checks={}, attempted=steps, failed=failed, memory_peak_bytes=peak,
        trace_dir=tdir)

    # ---- the reference follows the first steps, from the seed alone ----
    t_check = time.perf_counter()
    batches = [ZipfSource(seed, b, s, cfg["vocab_size"],
                          tr["zipf_a"]).batch(i) for i in range(n_check)]
    ref = ref_mod.Reference(cfg, seed, stored=jnp.bfloat16).train(
        batches, n_check)
    prog = {"losses": losses, "grad": common.leaf_norms(mu1),
            "change": common.leaf_norms(jax.tree_util.tree_map(
                lambda a, c: np.asarray(a, np.float64) - np.asarray(c, np.float64),
                branch_n, branch0))}
    out.checks = compare(prog, ref, cell.limits)
    out.counters["check_s"] = time.perf_counter() - t_check
    if control:
        out.counters["compiled_memory"] = memory
        low = ref_mod.Reference(cfg, seed, stored=jnp.bfloat16,
                                lowp="fp8").train(batches, n_check)
        out.counters["control"] = {k: v for k, (v, _) in
                                   compare(low, ref, cell.limits).items()}
    return out


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """Relative gaps: the worst step's loss; the worst leaf's norm of the
    first gradient and of the change.  Leaves whose reference gradient is
    under a thousandth of the median leaf's move by round-off alone and are
    left out of the change."""
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"],
                                                     ref["losses"]))
    raw = ref["raw_grad"]
    med = float(np.median(list(raw.values())))
    moving = {k for k, v in raw.items() if v >= 1e-3 * med}
    return {"loss": (loss, limits["loss"]),
            "grad": (common.worst_leaf_gap(prog["grad"], ref["grad"]),
                     limits["grad"]),
            "change": (common.worst_leaf_gap(prog["change"], ref["change"],
                                             moving), limits["change"])}


def _check_duplex(tcfg, want: dict) -> None:
    d, o = tcfg.duplex, tcfg.opt
    got = {"n_blocks": d.n_blocks, "d_branch": d.d_branch,
           "pool_factor": d.pool_factor, "branch_heads": d.branch_heads,
           "branch_ff_mult": d.branch_ff_mult, "bfp_group": d.bfp.group[0],
           "bfp_ebits": d.bfp.ebits, "bfp_mbits": d.bfp.mbits,
           "lr": tcfg.lr, "z_loss": tcfg.z_loss,
           "sgd": {"momentum": o.momentum, "weight_decay": o.weight_decay,
                   "clip_norm": o.clip_norm}}
    if got != want or d.use_norm or not d.causal or o.nesterov \
            or d.bfp.group[0] != d.bfp.group[1] or not d.bfp.enabled:
        raise ValueError(f"program duplex config {got} differs from the "
                         f"file's {want}")
