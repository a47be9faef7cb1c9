"""Cell construction shared by the dry-run, launchers, and benchmarks.

Importing this module never mutates XLA flags or jax device state.
"""
from __future__ import annotations

import dataclasses as dc
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.common import SHAPES, ShapeSpec
from repro.core import duplex as dx
from repro.distributed import sharding as sh
from repro.models import layers as L, registry
from repro.optim import SGDConfig
from repro.train import serve_step as ss, train_step as ts

POLICY = L.Policy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16)


def duplex_tcfg(cfg, backbone_dtype=jnp.bfloat16) -> ts.TrainConfig:
    """Production duplex config: branch width scales with the backbone.

    ``backbone_dtype=float8_e4m3fn`` (§Perf H1 iter-3): the frozen backbone
    is *storage*-quantized to 8 bits — the paper stores every tensor in
    ≤6.44-bit BFP (§III-E); fp8 is the closest native-dtype analogue — so
    FSDP weight gathers move half the bytes of bf16.  Compute still upcasts
    to bf16 at use.
    """
    d_branch = max(256, cfg.d_model // 8)
    n_blocks = max(2, min(8, ts.tapped_layers(cfg)))
    return ts.TrainConfig(
        mode="duplex",
        duplex=dx.DuplexConfig(
            n_blocks=n_blocks, d_branch=d_branch, pool_factor=16,
            branch_heads=max(4, d_branch // 128),
            bfp=L.BFPPolicy(enabled=True, group=(32, 32))),
        opt=SGDConfig(), lr=1e-3, backbone_dtype=backbone_dtype)


def activation_rules(cfg, mesh, fsdp_pure: bool = False) -> dict:
    """Per-arch activation PartitionSpecs (DESIGN.md §6).

    Heads divide TP → shard the flat query-head axis; otherwise fall back to
    sequence parallelism (q sharded on seq, kv replicated and all-gathered).
    ``fsdp_pure`` (§Perf H1): the batch dim spreads over ALL mesh axes and
    nothing else is sharded — per-layer TP psums vanish.
    """
    tp = mesh.shape["model"]
    if fsdp_pure:
        dpm = sh.dp_axes(mesh, include_model=True)
        return {"resid": P(dpm, None, None),
                "act_q": P(dpm, None, None, None),
                "act_kv": P(dpm, None, None, None),
                "act_lru": P(dpm, None, None)}
    dp = sh.dp_axes(mesh)
    rules = {"resid": P(dp, None, None),
             "act_lru": P(dp, None, "model"),
             # decode scores follow the seq-sharded KV cache (§Perf H4):
             # without this GSPMD all-gathers the whole cache per token
             "dec_scores": P(dp, None, None, "model")}
    if cfg.n_heads and cfg.n_heads % tp == 0:
        rules["act_q"] = P(dp, None, "model", None)
        rules["act_kv"] = P(dp, None,
                            "model" if cfg.n_kv % tp == 0 else None, None)
    elif cfg.n_heads:
        rules["act_q"] = P(dp, "model", None, None)      # sequence parallel
        rules["act_kv"] = P(dp, None, None, None)
    return rules


def input_specs(arch: str, shape: ShapeSpec, mesh, fsdp_pure: bool = False):
    """ShapeDtypeStructs + NamedShardings for one cell (no allocation)."""
    entry = registry.get(arch)
    cfg = entry.full
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def sds(shp, dt=i32):
        return jax.ShapeDtypeStruct(shp, dt)

    def batch_sharding(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.NamedSharding(
                mesh, sh.batch_pspec(x.shape, mesh,
                                     include_model=fsdp_pure)),
            tree)

    fe_shapes = entry.frontend_shape(cfg, b)
    frontend = None if fe_shapes is None else {
        k: sds(v, jnp.bfloat16) for k, v in fe_shapes.items()}

    if shape.mode == "train":
        batch = {"tokens": sds((b, s)), "labels": sds((b, s))}
        if frontend is not None:
            batch["frontend"] = frontend
        return batch, batch_sharding(batch)
    if shape.mode == "prefill":
        batch = {"tokens": sds((b, s))}
        if frontend is not None:
            batch["frontend"] = frontend
        return batch, batch_sharding(batch)
    # decode: one new token against a cache of seq_len
    tokens = {"tokens": sds((b, 1))}
    return tokens, batch_sharding(tokens)


def tuned_cfg(cfg, level: int = 1):
    """§Perf 'tuned' model-config overrides (baseline = registry config)."""

    over = dict(causal_skip=True,
                lru_scan_chunk=4096 if cfg.lru_width else None)
    if level >= 2:
        # fewer, fatter attention chunks: kv re-reads scale with n_q_chunks
        over.update(q_chunk=1024, kv_chunk=2048)
    return dc.replace(cfg, **over)


def build_cell(arch: str, shape: ShapeSpec, mesh, variant: str = "baseline"):
    """Returns (fn, example_args, in_shardings, out_shardings, donate)."""

    entry = registry.get(arch)
    level = {"baseline": 0, "tuned": 1, "tuned2": 2}[variant]
    cfg = entry.full if level == 0 else tuned_cfg(entry.full, level)
    b, s = shape.global_batch, shape.seq_len
    tuned = level >= 1
    # fsdp_pure: frozen-backbone training of non-MoE archs (EP needs TP)
    fsdp_pure = tuned and shape.mode == "train" and cfg.n_experts == 0
    pspec = functools.partial(sh.param_pspec, fsdp_pure=fsdp_pure,
                              lru_gates_colparallel=tuned)

    if shape.mode == "train":
        tcfg = duplex_tcfg(cfg, backbone_dtype=(
            jnp.float8_e4m3fn if level >= 2 else jnp.bfloat16))
        state_shapes = jax.eval_shape(
            lambda k: ts.init_state(k, entry, cfg, tcfg, POLICY),
            jax.random.PRNGKey(0))
        state_specs = sh.to_named(
            sh.state_pspecs(state_shapes, mesh, pspec=pspec), mesh)
        batch, batch_specs = input_specs(arch, shape, mesh, fsdp_pure)
        fn = ts.make_train_step(entry, cfg, tcfg, POLICY)
        # out_shardings left to the compiler (donation keeps state in place)
        return (fn, (state_shapes, batch), (state_specs, batch_specs),
                None, (0,), cfg, fsdp_pure)

    params_shapes = jax.eval_shape(
        lambda k: entry.module.init_params(k, cfg), jax.random.PRNGKey(0))
    param_specs = sh.to_named(sh.tree_pspecs(params_shapes, mesh, pspec), mesh)

    if shape.mode == "prefill":
        batch, batch_specs = input_specs(arch, shape, mesh)
        step = ss.make_prefill_step(entry, cfg, max_len=s + 64, policy=POLICY,
                                    logits_mode="last" if tuned else "all")

        def fn(params, batch):
            return step(params, batch["tokens"], batch.get("frontend"))

        return (fn, (params_shapes, batch), (param_specs, batch_specs),
                None, (), cfg, False)

    # decode
    cache_shapes = jax.eval_shape(
        lambda: entry.module.init_cache(cfg, batch=b, max_len=s,
                                        dtype=jnp.bfloat16))
    cache_specs = sh.to_named(
        sh.tree_pspecs(cache_shapes, mesh, sh.cache_pspec), mesh)
    tokens, tok_specs = input_specs(arch, shape, mesh)
    step = ss.make_decode_step(entry, cfg, policy=POLICY)

    def fn(params, cache, tokens):
        return step(params, cache, tokens["tokens"])

    return (fn, (params_shapes, cache_shapes, tokens),
            (param_specs, cache_specs, tok_specs), None, (1,), cfg, False)


