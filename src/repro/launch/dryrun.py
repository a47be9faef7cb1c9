"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real step function (duplex train_step /
prefill_step / decode_step), the ShapeDtypeStruct input specs, and the
NamedShardings from ``distributed.sharding``; lowers, compiles, and records
``memory_analysis()`` / ``cost_analysis()`` / HLO collective traffic to a
JSON file that §Dry-run / §Roofline read.

One cell per process (jax locks the device count at first init, so
``main`` sets 512 host devices before JAX's first backend use; fresh
processes also keep compile memory bounded):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
        --shape train_4k --mesh pod --out experiments/dryrun
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.configs.common import SHAPES, ShapeSpec
from repro.core import duplex as dx
from repro.distributed import ctx, sharding as sh
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import layers as L, registry
from repro.optim import SGDConfig
from repro.train import serve_step as ss, train_step as ts

from repro.launch.cells import (POLICY, activation_rules, build_cell,
                                duplex_tcfg, input_specs, tuned_cfg)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             save_hlo: bool = False, variant: str = "baseline") -> dict:
    shape = SHAPES[shape_name]
    entry = registry.get(arch)
    mesh_name = "multipod" if multi_pod else "pod"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "mode": shape.mode, "variant": variant}

    if shape.name == "long_500k" and not entry.full.supports_long_context:
        rec["status"] = "skipped"
        rec["reason"] = "quadratic attention cannot serve 500k context"
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, args, in_sh, out_sh, donate, cfg, fsdp_pure = build_cell(
        arch, shape, mesh, variant)

    with mesh, ctx.activation_sharding(
            mesh, activation_rules(cfg, mesh, fsdp_pure=fsdp_pure)):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    mod = hlo_analysis.HloModule(hlo)
    coll = mod.collective_bytes()

    def _mem(field):
        return int(getattr(mem, field, -1)) if mem is not None else -1

    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "memory": {
            "argument_bytes": _mem("argument_size_in_bytes"),
            "output_bytes": _mem("output_size_in_bytes"),
            "temp_bytes": _mem("temp_size_in_bytes"),
            "generated_code_bytes": _mem("generated_code_size_in_bytes"),
        },
        "cost": {
            # raw XLA numbers (while bodies counted once — see hlo_analysis)
            "xla_flops": float(cost.get("flops", -1)),
            "xla_bytes_accessed": float(cost.get("bytes accessed", -1)),
            # trip-weighted re-derivations (per device)
            "dot_flops": mod.dot_flops(),
            "traffic_bytes": mod.traffic_bytes(fusion_aware=True),
            "traffic_bytes_pessimistic": mod.traffic_bytes(fusion_aware=False),
        },
        "collectives": coll,
        "hlo_ops": {k: mod.op_census().get(k, 0)
                    for k in ("fusion", "dot", "while", "custom-call")},
    })
    if save_hlo:
        suffix = "" if variant == "baseline" else f"__{variant}"
        (out_dir / f"{arch}__{shape_name}__{mesh_name}{suffix}.hlo.txt"
         ).write_text(hlo)
    return rec


def main():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(registry.ARCHS))
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "tuned", "tuned2"])
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{args.arch}__{args.shape}__{args.mesh}"
    if args.variant != "baseline":
        name += f"__{args.variant}"
    try:
        rec = run_cell(args.arch, args.shape, args.mesh == "multipod",
                       out_dir, save_hlo=args.save_hlo,
                       variant=args.variant)
    except Exception as e:  # recorded, not swallowed — sweep reports it
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
    status = rec["status"]
    extra = rec.get("reason") or rec.get("error", "")
    print(f"[dryrun] {name}: {status} {extra}")
    if status == "ok":
        m, c = rec["memory"], rec["cost"]
        print(f"  args={m['argument_bytes']/2**30:.2f}GiB "
              f"temp={m['temp_bytes']/2**30:.2f}GiB "
              f"dot_flops={c['dot_flops']:.3e} "
              f"coll={rec['collectives'].get('total', 0)/2**30:.2f}GiB "
              f"compile={rec['compile_s']:.0f}s")
    raise SystemExit(0 if status in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
