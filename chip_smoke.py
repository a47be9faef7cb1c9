"""Bring-up check on a TPU: the repo's main JAX path, end to end, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded duplex step on a 2x2 mesh

One chip runs three phases on granite-moe-1b-a400m at its full published
width and depth (random weights from a fixed seed):

1. duplex training through ``repro.launch.train`` (8 x 4096 tokens, 5 steps):
   every loss finite and the branch parameters changed;
2. prefill + greedy decode through ``repro.launch.serve`` (8 x 2048-token
   prompts, 32 tokens): logits and cache finite, tokens inside the vocabulary;
3. the compiled Pallas BFP kernels at the duplex branch width against
   ``kernels.ref``: ``kernels.ops.bfp_dense`` forward and backward, and the
   packed quantizer and the matmul on its output.

``--chips 4`` runs only the duplex step on a 2x2 ("data", "model") mesh at
global batch 16 x 4096, and the same batch on one device as two
microbatches, and compares the losses.

Exits non-zero, printing no result line, when JAX finds no TPU or any check
fails; the last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "granite-moe-1b-a400m"
# Both runs of --chips 4 compute the same bf16 operations and differ only in
# how they are partitioned, i.e. in reduction order.  Each per-token loss
# term then differs by at most about one bf16 rounding (2**-8 relative), and
# so does their mean.
LOSS_RTOL = 2.0**-8


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _train_argv(seq: int, batch: int, steps: int) -> list[str]:
    return ["--arch", ARCH, "--preset", "full", "--mode", "duplex",
            "--seq", str(seq), "--batch", str(batch),
            "--steps", str(steps), "--log-every", "1"]


def _report_steps(name: str, history: list[dict]) -> list[float]:
    losses = [h["loss"] for h in history]
    times = [h["step_time_s"] for h in history]
    for h in history:
        print(f"[{name}] step {h['step']}: loss={h['loss']!r} "
              f"step_time_s={h['step_time_s']!r}")
    print(f"[{name}] set-up (compile + first step): {times[0]!r} s")
    if len(times) > 1:
        print(f"[{name}] steady step time, median of {len(times) - 1}: "
              f"{statistics.median(times[1:])!r} s")
    check(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    return losses


def _memory(device) -> str:
    stats = device.memory_stats()
    return " ".join(f"{k}={stats[k]}" for k in sorted(stats))


def phase_train(train) -> None:
    steps = 5
    rep = train(_train_argv(seq=4096, batch=8, steps=steps))
    del rep["state"]
    check(len(rep["history"]) == steps,
          f"train: {len(rep['history'])} of {steps} steps logged")
    _report_steps("train", rep["history"])
    print(f"[train] branch max |delta|: {rep['branch_delta']!r}")
    check(rep["branch_delta"] is not None and rep["branch_delta"] > 0,
          "train: branch parameters did not change")
    print(f"[train] memory: {_memory(jax.devices()[0])}")


def phase_serve(serve) -> None:
    batch, prompt, gen = 8, 2048, 32
    rep = serve(["--arch", ARCH, "--preset", "full", "--batch", str(batch),
                 "--prompt-len", str(prompt), "--gen", str(gen)])
    toks = rep["tokens"]
    print(f"[serve] set-up (compile + first prefill + first decode): "
          f"{rep['setup_s']!r} s")
    print(f"[serve] prefill {batch} x {prompt}: {rep['prefill_s']!r} s")
    print(f"[serve] decode: {rep['decode_steps']} steps, "
          f"{rep['decode_tok_s']!r} tok/s")
    print(f"[serve] first sequence: {toks[0].tolist()}")
    check(rep["logits_finite"], "serve: non-finite prefill logits")
    check(rep["cache_finite"], "serve: non-finite cache after decode")
    check(toks.shape == (batch, gen), f"serve: tokens shape {toks.shape}")
    check(bool(np.all((toks >= 0) & (toks < rep["vocab"]))),
          "serve: token outside [0, vocab)")
    print(f"[serve] memory: {_memory(jax.devices()[0])}")


def phase_bfp_kernel() -> None:
    from repro.core import bfp
    from repro.kernels import ops, ref

    # the duplex branch of granite at 8 x 4096 tokens: d_branch 256, pooled
    # 16x -> 2048 rows; 32x32 groups and 256 blocks (launch.cells.duplex_tcfg)
    rows, d, group = 2048, 256, 32
    cfg = ops.BFPKernelConfig(group=group, block_m=256, block_n=256,
                              block_k=256)
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (rows, d), jnp.float32)
    w = jax.random.normal(kw, (d, d), jnp.float32) * d**-0.5
    g = jax.random.normal(kg, (rows, d), jnp.float32)

    def fwd_bwd(x, w, g):
        y, vjp = jax.vjp(lambda a, b: ops.bfp_dense(a, b, cfg), x, w)
        return (y,) + vjp(g)

    lowered = jax.jit(fwd_bwd).lower(x, w, g)
    check("tpu_custom_call" in lowered.as_text(),
          "bfp kernel: no Mosaic kernel in the lowered program")
    y, dx, dw = lowered.compile()(x, w, g)

    def q(a):
        return bfp.bfp_dequantize(bfp.bfp_quantize(
            a, group=(group, group), ebits=cfg.ebits, mbits=cfg.mbits))

    def agree(name, got, want, a, b):
        # Quantized operands are exact in bf16 and f32 (<= 5-bit mantissas,
        # power-of-two scales), so every product is exact on either side;
        # the two sums differ only in f32 accumulation order, bounded by
        # 2 * K * 2**-24 * sum_k |Qa||Qb|.
        bound = 2 * a.shape[1] * 2.0**-24 * jnp.matmul(
            jnp.abs(q(a)), jnp.abs(q(b)), precision=jax.lax.Precision.HIGHEST)
        err = jnp.abs(got - want)
        worst = float(jnp.max(err / jnp.maximum(bound, 1e-30)))
        print(f"[bfp] {name}: max |kernel - ref| = {float(jnp.max(err))!r}, "
              f"{worst!r} of the accumulation-order bound")
        check(bool(jnp.all(err <= bound)), f"bfp kernel: {name} off the ref")

    for name, got, a, b in (("y = x.w", y, x, w), ("dx = g.wT", dx, g, w.T),
                            ("dw = xT.g", dw, x.T, g)):
        agree(name, got, ref.ref_bfp_matmul(a, b, group=group, mbits=cfg.mbits,
                                            ebits=cfg.ebits), a, b)

    # the storage path: packed quantizer, then the matmul on packed operands
    (xm, xe), (wm, we) = ops.quantize(x, cfg), ops.quantize(w, cfg)
    for name, got, a in (("quantize x", (xm, xe), x),
                         ("quantize w", (wm, we), w)):
        want = ref.ref_bfp_quantize(a, group=group, mbits=cfg.mbits,
                                    ebits=cfg.ebits)
        same = all(bool(jnp.array_equal(u, v)) for u, v in zip(got, want))
        print(f"[bfp] {name}: mantissas and exponents equal to ref: {same}")
        check(same, f"bfp kernel: {name} differs from the ref")
    agree("packed x.w", ops.matmul_packed(xm, xe, wm, we, cfg),
          ref.ref_bfp_matmul_packed(xm, xe, wm, we, group=group,
                                    mbits=cfg.mbits), x, w)


def phase_sharded(train, make_host_mesh) -> None:
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    argv = _train_argv(seq=4096, batch=16, steps=3)
    rep4 = train(argv, mesh=make_host_mesh(model=2, devices=devices[:4]))
    backbone = rep4.pop("state")["backbone"]["stack"]["sub0"]
    for name, arr in (("attn/wq/w", backbone["attn"]["wq"]["w"]),
                      ("moe/wi", backbone["moe"]["wi"])):
        shards = sorted((s.device.id, s.data.shape)
                        for s in arr.addressable_shards)
        print(f"[mesh 2x2] {name} {arr.shape}: {arr.sharding.spec}; "
              f"shards (device, shape): {shards}")
        check(len({d for d, _ in shards}) == 4 and
              all(np.prod(shp) * 4 == np.prod(arr.shape)
                  for _, shp in shards),
              f"{name} is not split over the 4 devices")
    del backbone
    l4 = _report_steps("mesh 2x2", rep4["history"])
    for d in devices[:4]:
        print(f"[mesh 2x2] device {d.id} memory: {_memory(d)}")

    rep1 = train(argv + ["--microbatch", "2"],
                 mesh=make_host_mesh(devices=devices[:1]))
    del rep1["state"]
    l1 = _report_steps("1 device, 2 microbatches", rep1["history"])
    for step, (a, b) in enumerate(zip(l4, l1)):
        print(f"[compare] step {step}: 2x2 {a!r} vs 1 device {b!r}, "
              f"rel diff {abs(a - b) / abs(b)!r} (tol {LOSS_RTOL!r})")
        check(abs(a - b) <= LOSS_RTOL * abs(b),
              f"step {step}: sharded loss {a} != one-device loss {b}")
    check(len(l4) == len(l1) == 3, "compare: missing steps")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    devices = jax.devices()
    kind = devices[0].device_kind
    print(f"devices: platform={devices[0].platform} kind={kind} "
          f"count={len(devices)}", flush=True)
    if devices[0].platform != "tpu":
        print("no TPU found: this check runs on the chip only",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import serve
    from repro.launch.train import train

    print(f"compilation cache: {use_compile_cache()}", flush=True)
    try:
        if args.chips == 4:
            phase_sharded(train, make_host_mesh)
        else:
            phase_train(train)
            phase_serve(serve)
            phase_bfp_kernel()
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
