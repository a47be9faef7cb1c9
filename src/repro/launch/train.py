"""Production training launcher.

On a real TPU slice this process runs once per host (``jax.distributed``
initializes from the cluster env); the same entry point runs on CPU for
local smoke runs with ``--preset smoke``.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b \
        --preset smoke --steps 50 --ckpt-dir /tmp/ckpt

``train(argv)`` runs the same launcher inside the calling process and
returns its report.
"""
from __future__ import annotations

import argparse
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import CheckpointConfig
from repro.data.pipeline import DataConfig
from repro.distributed import ctx, sharding as sh
from repro.launch.cells import activation_rules, duplex_tcfg
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import layers as L, registry
from repro.train import loop, train_step as ts


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(registry.ARCHS))
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--mode", default="duplex", choices=["duplex", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation splits of the global batch")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "pod", "multipod"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--distributed", action="store_true",
                    help="initialize jax.distributed from cluster env")
    return ap


def train(argv: list[str] | None = None, *, mesh=None) -> dict:
    """Parse ``argv`` as the CLI does and train; returns the report.

    ``mesh`` replaces the one ``--mesh`` names.  The report holds the loop's
    logged ``history`` (loss, step_time_s, … per logged step), the final
    ``state``, and ``branch_delta``: the largest absolute change of any
    duplex branch parameter (None in full mode or after a resume).
    """
    args = _parser().parse_args(argv)
    use_compile_cache()
    if args.distributed:
        jax.distributed.initialize()

    entry = registry.get(args.arch)
    cfg = entry.config(args.preset)
    policy = L.Policy(compute_dtype=(jnp.bfloat16 if args.preset == "full"
                                     else jnp.float32))
    tcfg = duplex_tcfg(cfg) if args.mode == "duplex" else \
        ts.TrainConfig(mode="full")
    if args.preset == "smoke":
        from repro.core import duplex as dx
        tcfg = dc.replace(
            tcfg, backbone_dtype=jnp.float32,
            duplex=dx.DuplexConfig(n_blocks=2, d_branch=32, pool_factor=4,
                                   branch_heads=2,
                                   bfp=L.BFPPolicy(enabled=True,
                                                   group=(3, 3))))
    tcfg = dc.replace(tcfg, microbatch=args.microbatch)

    if mesh is None:
        mesh = make_host_mesh() if args.mesh == "host" else \
            make_production_mesh(multi_pod=args.mesh == "multipod")
    initial_branch = {}

    with mesh, ctx.activation_sharding(mesh, activation_rules(cfg, mesh)):
        def init(k):
            return ts.init_state(k, entry, cfg, tcfg, policy)

        state_specs = sh.to_named(
            sh.state_pspecs(jax.eval_shape(init, jax.random.PRNGKey(0)),
                            mesh), mesh)
        step = jax.jit(ts.make_train_step(entry, cfg, tcfg, policy),
                       donate_argnums=0)
        batch_sharding = jax.NamedSharding(
            mesh, sh.batch_pspec((args.batch, args.seq), mesh))

        def init_fn():
            st = jax.jit(init, out_shardings=state_specs)(
                jax.random.PRNGKey(0))
            if "branch" in st:
                initial_branch["params"] = jax.device_get(st["branch"])
            return st

        def step_fn(state, batch):
            return step(state, jax.device_put(batch, batch_sharding))

        report = loop.run(
            loop.LoopConfig(
                total_steps=args.steps, ckpt_every=args.ckpt_every,
                ckpt=(CheckpointConfig(args.ckpt_dir)
                      if args.ckpt_dir else None),
                log_every=args.log_every, step_deadline_s=60.0),
            DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                       batch_per_host=args.batch,
                       seed=jax.process_index()),
            step_fn, init_fn)

    branch_delta = None
    if initial_branch:
        final = jax.device_get(report.state["branch"])
        branch_delta = max(
            float(np.max(np.abs(np.asarray(a, np.float32) -
                                np.asarray(b, np.float32))))
            for a, b in zip(jax.tree_util.tree_leaves(final),
                            jax.tree_util.tree_leaves(
                                initial_branch["params"])))
    return {"steps_run": report.steps_run, "wall_s": report.wall_s,
            "history": report.metrics_history, "state": report.state,
            "branch_delta": branch_delta}


def main():
    rep = train()
    print(f"finished {rep['steps_run']} steps in {rep['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
