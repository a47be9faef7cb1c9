"""§Roofline: three-term analysis per (arch × shape × mesh) from the dry-run.

    compute term    = dot_FLOPs_per_device / peak_FLOP/s
    memory term     = traffic_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / (links × link_bw)

Hardware constants come from ``PEAKS``, keyed by the device kind JAX
reports; the dry-run models a TPU v5e pod (``launch/mesh.py``).  Collective
traffic is budgeted against one ICI link (conservative).  Also reports
MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) — fwd-only terms (2·N·D)
for the frozen duplex backbone — and the useful-compute ratio
MODEL_FLOPS / HLO_FLOPs.
"""
from __future__ import annotations

import json
from pathlib import Path

from repro.configs.common import SHAPES
from repro.models import registry
from repro.utils import count_params

# Per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of
# ICI over 4 links, i.e. 50 GB/s per link and direction).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip; an unknown kind is an error, never a default."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def param_counts(arch: str) -> dict:
    """Total & active parameter counts for MODEL_FLOPS (cached analytic)."""
    import jax
    entry = registry.get(arch)
    cfg = entry.full
    shapes = jax.eval_shape(lambda k: entry.module.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    total = count_params(shapes)
    active = total
    if cfg.n_experts:
        # only top_k (+shared) experts are active per token
        expert_params = cfg.n_experts * (cfg.d_model * cfg.d_ff *
                                         (3 if cfg.gated_mlp else 2))
        per_layer_moe = sum(1 for s in cfg.pattern if s.mlp == "moe")
        total_moe = expert_params * cfg.n_rep * per_layer_moe
        active_frac = cfg.top_k / cfg.n_experts
        active = total - total_moe * (1 - active_frac)
    return {"total": total, "active": active}


def model_flops(arch: str, shape_name: str, counts: dict) -> float:
    """Global useful FLOPs for the cell (duplex: fwd-only backbone)."""
    cfg = registry.get(arch).full
    shape = SHAPES[shape_name]
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        # frozen backbone forward (2·N·D) + branch fwd+bwd (6·n_branch·D/16)
        return 2.0 * counts["active"] * tokens
    if shape.mode == "prefill":
        return 2.0 * counts["active"] * shape.global_batch * shape.seq_len
    # decode: one token per sequence
    return 2.0 * counts["active"] * shape.global_batch


def load_cells(dryrun_dir: str = "experiments/dryrun") -> list[dict]:
    cells = []
    for p in sorted(Path(dryrun_dir).glob("*.json")):
        cells.append(json.loads(p.read_text()))
    return cells


def roofline_row(rec: dict, counts: dict) -> dict:
    peak = peaks(DRYRUN_DEVICE_KIND)
    n_dev = rec["n_devices"]
    flops_dev = rec["cost"]["dot_flops"]          # already per device (SPMD)
    traffic_dev = rec["cost"]["traffic_bytes"]
    coll_dev = rec["collectives"].get("total", 0)
    t_compute = flops_dev / peak["flops"]
    t_memory = traffic_dev / peak["hbm_bw"]
    t_coll = coll_dev / peak["link_bw"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    mflops = model_flops(rec["arch"], rec["shape"], counts)
    hlo_global = flops_dev * n_dev
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll,
        "bottleneck": bottleneck,
        "model_flops": mflops,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mflops / hlo_global if hlo_global else 0.0,
        "step_s_bound": max(terms.values()),
        # fraction of the step bound spent on MXU compute (1.0 ⇔ compute-bound)
        "compute_bound_fraction": (t_compute / max(terms.values())
                                   if max(terms.values()) > 0 else 0.0),
        # useful-model-FLOP/s at the bound, as a fraction of peak — §Perf score
        "roofline_fraction": (mflops / n_dev / max(terms.values())
                              / peak["flops"]
                              if max(terms.values()) > 0 else 0.0),
        "temp_gib": rec["memory"]["temp_bytes"] / 2**30,
        "args_gib": rec["memory"]["argument_bytes"] / 2**30,
    }


def build_table(dryrun_dir: str = "experiments/dryrun",
                mesh: str = "pod", variant: str = "baseline") -> list[dict]:
    counts_cache: dict = {}
    rows = []
    for rec in load_cells(dryrun_dir):
        if rec["mesh"] != mesh or rec.get("variant", "baseline") != variant:
            continue
        if rec["status"] == "skipped":
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "mesh": mesh, "bottleneck": "SKIP",
                         "note": rec["reason"]})
            continue
        if rec["status"] != "ok":
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "mesh": mesh, "bottleneck": "ERROR"})
            continue
        if rec["arch"] not in counts_cache:
            counts_cache[rec["arch"]] = param_counts(rec["arch"])
        rows.append(roofline_row(rec, counts_cache[rec["arch"]]))
    return rows


def markdown_table(rows: list[dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | bound | "
           "useful | roofline frac |\n|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        if r.get("bottleneck") in ("SKIP", "ERROR"):
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                         f"{r['bottleneck']} | — | — |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"{r['bottleneck']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.3f} |")
    return hdr + "\n".join(lines)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="experiments/dryrun")
    ap.add_argument("--mesh", default="pod")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--json-out", default="experiments/roofline.json")
    args = ap.parse_args()
    rows = build_table(args.dryrun_dir, args.mesh, args.variant)
    Path(args.json_out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.json_out).write_text(json.dumps(rows, indent=2))
    print(markdown_table(rows))


if __name__ == "__main__":
    main()
