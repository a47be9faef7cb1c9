"""Time both MoE routes of ``repro.models.moe`` by group size, on the chip.

    PYTHONPATH=src python tools/moe_routes.py [--groups 16,64,256,1024,4096]
        [--layers 8] [--json chiprun_out/moe_routes.json]

At granite-moe-1b-a400m's widths (32 experts, top 8, d 1024, expert d_ff
512, capacity factor 1.25, bf16), each route runs a stack of ``--layers``
MoE layers (router, dispatch, experts, combine) under one ``lax.scan``, on
one group of ``g`` tokens: a group smaller than the configured size holds
all the tokens there are, as a decode batch does.  The training shape, 8
groups of 4,096, is timed too.  Calls are dispatched back to back and
waited on once, so the time per layer is the device's.  The one-hot route
at g tokens is what ``moe_apply`` runs below ``_INDEX_ROUTE_MIN_GROUP`` and
the index route what it runs from there; the readings set that constant.

Run it on the chip: a CPU timing says nothing about the device.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.models import layers as L, moe

CFG = moe.MoEConfig(d_model=1024, d_ff=512, n_experts=32, top_k=8,
                    capacity_factor=1.25, group_size=4096)
POLICY = L.Policy(compute_dtype=jnp.bfloat16)
ROUTES = {"onehot": moe._onehot_route, "index": moe._index_route}


def stack(layers: int, key) -> dict:
    """``layers`` MoE layers' weights, stacked, in bf16."""
    keys = jax.random.split(key, layers)
    params = jax.vmap(lambda k: moe.moe_init(k, CFG))(keys)
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)


def time_route(route, params, n_groups: int, group: int, calls: int = 10,
               repeats: int = 3) -> float:
    """Median device seconds per layer of ``route`` on [n_groups, group]."""
    layers = jax.tree_util.tree_leaves(params)[0].shape[0]

    @jax.jit
    def run(params, x):
        def body(x, p):
            y, _ = route(p, x, CFG, policy=POLICY, bfp=L.NO_BFP)
            return x + y.astype(x.dtype), None
        return jax.lax.scan(body, x, params)[0]

    x = jax.random.normal(jax.random.PRNGKey(1),
                          (n_groups, group, CFG.d_model), jnp.bfloat16)
    x = run(params, x).block_until_ready()       # compile and warm
    per_layer = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            x = run(params, x)
        x.block_until_ready()
        per_layer.append((time.perf_counter() - t0) / calls / layers)
    return statistics.median(per_layer)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--groups", default="16,64,256,1024,4096")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    params = stack(args.layers, jax.random.PRNGKey(0))
    shapes = [(1, int(g)) for g in args.groups.split(",")] + [(8, 4096)]
    rows = []
    print(f"device {dev.device_kind}; microseconds per layer")
    print("G x g       C     " + "  ".join(f"{r:>10}" for r in ROUTES))
    for n_groups, group in shapes:
        us = {name: 1e6 * time_route(fn, params, n_groups, group)
              for name, fn in ROUTES.items()}
        rows.append({"groups": n_groups, "group": group,
                     "capacity": moe.capacity(CFG, group), "us": us})
        print(f"{n_groups} x {group:<6} {moe.capacity(CFG, group):>5} "
              + "  ".join(f"{us[r]:>10.1f}" for r in ROUTES))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"device": dev.device_kind, "layers": args.layers,
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
