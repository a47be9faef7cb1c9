"""Production serving launcher: batched prefill + decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b \
        --preset smoke --batch 4 --gen 16

``serve(argv)`` runs the same launcher inside the calling process and
returns its report.  The first prefill and the first decode step compile;
they are reported as set-up, and the times after them are steady state.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import ctx, sharding as sh
from repro.launch.cells import activation_rules
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import layers as L, registry
from repro.train import serve_step as ss


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(registry.ARCHS))
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "pod", "multipod"])
    return ap


def _all_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree_util.tree_leaves(tree)
               if jnp.issubdtype(x.dtype, jnp.floating))


def serve(argv: list[str] | None = None) -> dict:
    """Parse ``argv`` as the CLI does, prefill and decode; returns the report.

    Report: ``tokens`` [batch, gen] (the greedy continuation), ``vocab``,
    ``logits_finite`` (prefill's next-token logits), ``cache_finite`` (the
    cache after the last decode step), ``setup_s`` (first prefill + first
    decode step, compilation included), ``prefill_s`` (a second prefill on
    the compiled step) and ``decode_tok_s`` over the remaining steps.
    """
    args = _parser().parse_args(argv)
    if args.gen < 2:
        raise ValueError("--gen must be at least 2 (one compiled decode "
                         "step before the timed ones)")
    use_compile_cache()
    entry = registry.get(args.arch)
    cfg = entry.config(args.preset)
    policy = L.Policy(compute_dtype=(jnp.bfloat16 if args.preset == "full"
                                     else jnp.float32))
    cache_dtype = jnp.bfloat16 if args.preset == "full" else jnp.float32
    mesh = make_host_mesh() if args.mesh == "host" else \
        make_production_mesh(multi_pod=args.mesh == "multipod")
    max_len = args.prompt_len + args.gen + 8

    with mesh, ctx.activation_sharding(mesh, activation_rules(cfg, mesh)):
        def init(k):
            return entry.module.init_params(k, cfg)

        param_specs = sh.to_named(
            sh.tree_pspecs(jax.eval_shape(init, jax.random.PRNGKey(0)),
                           mesh, sh.param_pspec), mesh)
        params = jax.jit(init, out_shardings=param_specs)(
            jax.random.PRNGKey(0))

        fe = entry.frontend_shape(cfg, args.batch)
        frontend = None if fe is None else {
            k: jax.random.normal(jax.random.PRNGKey(7), v).astype(
                policy.compute_dtype) * 0.1 for k, v in fe.items()}

        prefill = jax.jit(ss.make_prefill_step(entry, cfg, max_len=max_len,
                                               policy=policy,
                                               cache_dtype=cache_dtype,
                                               logits_mode="last"))
        decode = jax.jit(ss.make_decode_step(entry, cfg, policy=policy),
                         donate_argnums=1)

        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
            cfg.vocab)
        t0 = time.perf_counter()
        jax.block_until_ready(prefill(params, prompts, frontend))
        t_first_prefill = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = prefill(params, prompts, frontend)
        jax.block_until_ready(out)
        prefill_s = time.perf_counter() - t0
        logits_finite = _all_finite(out["next_token_logits"])
        cache = out["cache"]
        tok = jnp.argmax(out["next_token_logits"], -1)[:, None] \
            .astype(jnp.int32)
        del out

        toks = [tok]
        t0 = time.perf_counter()
        tok, cache = decode(params, cache, tok)
        jax.block_until_ready(tok)
        t_first_decode = time.perf_counter() - t0
        toks.append(tok)
        t0 = time.perf_counter()
        for i in range(args.gen - 2):
            with jax.profiler.StepTraceAnnotation("decode", step_num=i):
                tok, cache = decode(params, cache, tok)
            toks.append(tok)
        jax.block_until_ready(tok)
        decode_s = time.perf_counter() - t0
        gen = np.asarray(jnp.concatenate(toks, axis=1))
        cache_finite = _all_finite(cache)

    steps = args.gen - 2
    return {"tokens": gen, "vocab": cfg.vocab,
            "logits_finite": logits_finite, "cache_finite": cache_finite,
            "setup_s": t_first_prefill + t_first_decode,
            "prefill_s": prefill_s, "decode_steps": steps,
            "decode_tok_s": (steps * args.batch / decode_s if steps
                             else float("nan"))}


def main():
    rep = serve()
    print(f"set-up (compile + first prefill + first decode): "
          f"{rep['setup_s']:.2f}s")
    print(f"prefill: {rep['prefill_s']:.2f}s")
    print(f"decode: {rep['decode_steps']} steps, "
          f"{rep['decode_tok_s']:.1f} tok/s")
    print("first sequence:", [int(t) for t in rep["tokens"][0]])


if __name__ == "__main__":
    main()
