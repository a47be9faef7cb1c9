"""Generic pattern-driven transformer stack.

Expresses every assigned architecture from a ``ModelConfig``: the repeating
layer pattern is scanned (stacked params ⇒ compact HLO even at 100 layers),
the remainder layers run unrolled.  Three execution paths share the sublayer
implementations:

* ``forward``      — training / scoring (full sequence, optional taps for
                     the Duplex branch, MoE aux-loss accumulation);
* ``prefill``      — forward + KV/state cache construction for serving;
* ``decode_step``  — one-token step updating the cache (ring buffers for
                     sliding-window layers, recurrent states for SSD/LRU).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.common import LayerSpec, ModelConfig
from repro.distributed.ctx import constrain
from repro.models import hybrid, layers as L, moe as moe_mod, ssm
from repro.utils import split_keys

# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------


def _norm_init(cfg: ModelConfig, d: int) -> dict:
    return L.layernorm_init(d) if cfg.norm == "layernorm" else L.rmsnorm_init(d)


def _norm(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    return (L.layernorm(p, x) if cfg.norm == "layernorm"
            else L.rmsnorm(p, x, cfg.norm_eps))


def _resid(cfg: ModelConfig, h: jax.Array, y: jax.Array) -> jax.Array:
    """The residual stream plus a sublayer's output (times granite's
    residual multiplier, where the config has one)."""
    return h + y if cfg.resid_mult == 1.0 else h + y * cfg.resid_mult


def _act(cfg: ModelConfig):
    return jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu


def attn_cfg_for(cfg: ModelConfig, spec: LayerSpec) -> L.AttnConfig:
    return L.AttnConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv=cfg.n_kv,
        head_dim=cfg.head_dim,
        qkv_bias=cfg.qkv_bias,
        # cross-attn queries/keys live in different position spaces → no rope
        rope_theta=(cfg.rope_theta
                    if cfg.pos_embed == "rope" and spec.kind != "cross"
                    else None),
        softcap=cfg.softcap_attn,
        window=cfg.window if spec.kind == "local" else None,
        causal=cfg.causal and spec.kind != "cross",
        blockwise_threshold=cfg.blockwise_threshold,
        q_chunk=cfg.q_chunk,
        kv_chunk=cfg.kv_chunk,
        causal_skip=cfg.causal_skip,
        use_flash=cfg.use_flash and spec.kind == "attn",
        scale=cfg.attn_mult,
    )


def _ssd_cfg(cfg: ModelConfig) -> ssm.SSDConfig:
    return ssm.SSDConfig(
        d_model=cfg.d_model, d_state=cfg.ssm_state, headdim=cfg.ssm_headdim,
        expand=cfg.ssm_expand, conv_width=cfg.conv_width, chunk=cfg.ssm_chunk,
        eps=cfg.norm_eps)


def _lru_cfg(cfg: ModelConfig) -> hybrid.LRUConfig:
    return hybrid.LRUConfig(d_model=cfg.d_model, lru_width=cfg.lru_width,
                            conv_width=cfg.conv_width,
                            scan_chunk=cfg.lru_scan_chunk)


def _moe_cfg(cfg: ModelConfig) -> moe_mod.MoEConfig:
    return moe_mod.MoEConfig(
        d_model=cfg.d_model, d_ff=cfg.d_ff, n_experts=cfg.n_experts,
        top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
        group_size=cfg.moe_group_size, gated=cfg.gated_mlp,
        shared_expert=cfg.shared_expert, shared_d_ff=cfg.shared_d_ff,
        n_routed=cfg.n_routed_experts, offset=cfg.expert_offset)


def sinusoidal_embed(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freq = jnp.exp(-math.log(10000.0) *
                   jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# sublayer init / apply
# --------------------------------------------------------------------------

def _sub_init(key: jax.Array, cfg: ModelConfig, spec: LayerSpec) -> dict:
    ks = split_keys(key, ["mix", "mlp"])
    p: dict = {}
    if spec.kind in ("attn", "local", "cross"):
        p["norm"] = _norm_init(cfg, cfg.d_model)
        p["attn"] = L.attn_init(ks["mix"], attn_cfg_for(cfg, spec))
        if cfg.post_norm:
            p["post_norm"] = _norm_init(cfg, cfg.d_model)
    elif spec.kind == "ssd":
        p["norm"] = _norm_init(cfg, cfg.d_model)
        p["ssd"] = ssm.ssd_init(ks["mix"], _ssd_cfg(cfg))
    elif spec.kind == "lru":
        p["norm"] = _norm_init(cfg, cfg.d_model)
        p["lru"] = hybrid.lru_init(ks["mix"], _lru_cfg(cfg))
    else:
        raise ValueError(spec.kind)

    if spec.mlp == "dense":
        p["mlp_norm"] = _norm_init(cfg, cfg.d_model)
        p["mlp"] = L.mlp_init(ks["mlp"], cfg.d_model, cfg.d_ff,
                              gated=cfg.gated_mlp)
        if cfg.post_norm:
            p["mlp_post_norm"] = _norm_init(cfg, cfg.d_model)
    elif spec.mlp == "moe":
        p["mlp_norm"] = _norm_init(cfg, cfg.d_model)
        p["moe"] = moe_mod.moe_init(ks["mlp"], _moe_cfg(cfg))
        if cfg.post_norm:
            p["mlp_post_norm"] = _norm_init(cfg, cfg.d_model)
    return p


def _apply_mlp(p, h, spec, cfg, policy, bfp):
    """Channel mixer + residual; returns (h, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if spec.mlp == "none":
        return h, aux
    u = _norm(cfg, p["mlp_norm"], h)
    if spec.mlp == "dense":
        with jax.named_scope("mlp"):
            y = L.mlp(p["mlp"], u, policy=policy, bfp=bfp, act=_act(cfg))
    else:
        y, aux = moe_mod.moe_apply(p["moe"], u, _moe_cfg(cfg), policy=policy,
                                   bfp=bfp)
    if cfg.post_norm:
        y = _norm(cfg, p["mlp_post_norm"], y)
    return _resid(cfg, h, y), aux


def _sub_apply(p, h, spec, cfg, *, policy, bfp, cross_kv, positions):
    """Full-sequence sublayer (train / scoring). Returns (h, aux)."""
    acfg = attn_cfg_for(cfg, spec)
    if spec.kind in ("attn", "local", "cross"):
        u = _norm(cfg, p["norm"], h)
        kv = cross_kv if spec.kind == "cross" else None
        with jax.named_scope("attention"):
            y = L.attention_layer(p["attn"], u, acfg, policy=policy, bfp=bfp,
                                  kv_x=kv, positions=positions)
        if cfg.post_norm:
            y = _norm(cfg, p["post_norm"], y)
        h = _resid(cfg, h, y)
    elif spec.kind == "ssd":
        u = _norm(cfg, p["norm"], h)
        y, _ = ssm.ssd_block(p["ssd"], u, _ssd_cfg(cfg), policy=policy, bfp=bfp)
        h = _resid(cfg, h, y)
    elif spec.kind == "lru":
        u = _norm(cfg, p["norm"], h)
        y, _ = hybrid.lru_block(p["lru"], u, _lru_cfg(cfg), policy=policy,
                                bfp=bfp)
        h = _resid(cfg, h, y)
    return _apply_mlp(p, h, spec, cfg, policy, bfp)


# --------------------------------------------------------------------------
# top-level params / forward
# --------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    cfg.validate()
    ks = split_keys(key, ["embed", "stack", "rem", "final"])
    params: dict = {
        "embed": L.embed_init(ks["embed"], cfg.vocab, cfg.d_model,
                              pad_to=cfg.vocab_pad_multiple),
        "final_norm": _norm_init(cfg, cfg.d_model),
    }
    if cfg.n_rep:
        def init_rep(k):
            kk = jax.random.split(k, len(cfg.pattern))
            return {f"sub{i}": _sub_init(kk[i], cfg, s)
                    for i, s in enumerate(cfg.pattern)}
        keys = jax.random.split(ks["stack"], cfg.n_rep)
        params["stack"] = jax.vmap(init_rep)(keys)
    if cfg.remainder:
        kk = jax.random.split(ks["rem"], len(cfg.remainder))
        params["rem"] = {f"sub{i}": _sub_init(kk[i], cfg, s)
                         for i, s in enumerate(cfg.remainder)}
    return params


@jax.named_scope("embed")
def embed_tokens(params, cfg: ModelConfig, tokens: jax.Array,
                 positions: jax.Array, policy: L.Policy) -> jax.Array:
    h = L.embed_lookup(params["embed"], tokens, policy)
    if cfg.embed_scale:
        h = h * math.sqrt(cfg.d_model)
    if cfg.embed_mult != 1.0:
        h = h * cfg.embed_mult
    if cfg.pos_embed == "sinusoidal":
        h = h + sinusoidal_embed(positions, cfg.d_model).astype(h.dtype)
    return h


def forward(params, cfg: ModelConfig, tokens: jax.Array, *,
            frontend: Optional[dict] = None,
            policy: L.Policy = L.Policy(), bfp: L.BFPPolicy = L.NO_BFP,
            collect_taps: bool = False,
            tap_indices=None, tap_pool: int = 1,
            inputs_embeds: Optional[jax.Array] = None) -> dict:
    """Full-sequence forward. Returns {hidden, taps, aux, emb}.

    Tap memory: with ``tap_indices`` (+ ``tap_pool``) only the selected
    layers' hidden states are kept, *pooled inside the scan body* into a
    small carry buffer — [n_sel, B, S/pool, D] instead of [n_rep, B, S, D].
    A tap index names a layer of the scanned stack (period ``p``, rep
    ``r``, sublayer ``i``: layer ``r·p + i``), so a period of several
    layers is tapped inside.  Without ``tap_indices`` the taps are each
    period's output.
    At pod scale this is the difference between 0.5 GB and 85 GB of tap
    residuals per device (DESIGN.md §3).
    """
    b, s = tokens.shape[:2] if inputs_embeds is None else inputs_embeds.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = (embed_tokens(params, cfg, tokens, positions, policy)
         if inputs_embeds is None else inputs_embeds)
    h = constrain(h, "resid")
    emb = h
    cross_kv = None if frontend is None else frontend.get("cross_kv")

    aux = jnp.zeros((), jnp.float32)
    taps = None
    if cfg.n_rep:
        use_buf = collect_taps and tap_indices is not None
        if use_buf:
            from repro.core.duplex import pool_seq  # local import, no cycle
            idx = jnp.asarray(tap_indices, jnp.int32)
            sp = -(-s // tap_pool)
            tap_buf0 = jnp.zeros((len(tap_indices), b, sp, cfg.d_model),
                                 h.dtype)

        period = len(cfg.pattern)
        # the sublayers of a period that some tap index names
        tapped = {int(t) % period for t in tap_indices} if use_buf else ()

        def body(carry, xs):
            if use_buf:
                (h, aux, buf), (p_rep, step_i) = carry, xs
            else:
                (h, aux), p_rep = carry, xs
            for i, spec in enumerate(cfg.pattern):
                h, a = _sub_apply(p_rep[f"sub{i}"], h, spec, cfg,
                                  policy=policy, bfp=bfp, cross_kv=cross_kv,
                                  positions=positions)
                h = constrain(h, "resid")
                aux = aux + a
                if i in tapped:
                    layer = step_i if period == 1 else step_i * period + i
                    pooled = pool_seq(h, tap_pool)
                    match = (idx == layer)[:, None, None, None]
                    buf = jnp.where(match, pooled[None], buf)
            if use_buf:
                return (h, aux, buf), None
            return (h, aux), (h if collect_taps else jnp.zeros((), h.dtype))

        if use_buf:
            (h, aux, taps), _ = lax.scan(
                body, (h, aux, tap_buf0),
                (params["stack"], jnp.arange(cfg.n_rep)))
        else:
            (h, aux), tap_out = lax.scan(body, (h, aux), params["stack"])
            if collect_taps:
                taps = tap_out                            # [n_rep,B,S,D]
    for i, spec in enumerate(cfg.remainder):
        h, a = _sub_apply(params["rem"][f"sub{i}"], h, spec, cfg,
                          policy=policy, bfp=bfp, cross_kv=cross_kv,
                          positions=positions)
        aux = aux + a
    h = _norm(cfg, params["final_norm"], h)
    return {"hidden": h, "taps": taps, "aux": aux, "emb": emb}


def lm_logits(params, cfg: ModelConfig, hidden: jax.Array,
              policy: L.Policy = L.Policy()) -> jax.Array:
    logits = L.unembed_logits(params["embed"], hidden, cfg.vocab, policy,
                              softcap=cfg.softcap_final)
    return logits if cfg.logits_div == 1.0 else logits / cfg.logits_div


# --------------------------------------------------------------------------
# serving: prefill + decode with caches
# --------------------------------------------------------------------------

def _ring_size(cfg: ModelConfig, spec: LayerSpec, max_len: int) -> int:
    if spec.kind == "local" and cfg.window is not None:
        return min(cfg.window, max_len)
    return max_len


def _sub_cache_zeros(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, dtype, lead: tuple = ()) -> Optional[dict]:
    """Zero-initialized cache for one sublayer (no params needed)."""
    if spec.kind in ("attn", "local"):
        size = _ring_size(cfg, spec, max_len)
        c = {
            "k": jnp.zeros(lead + (batch, size, cfg.n_kv, cfg.head_dim), dtype),
            "v": jnp.zeros(lead + (batch, size, cfg.n_kv, cfg.head_dim), dtype),
            "len": jnp.zeros(lead, jnp.int32),
        }
        if spec.kind == "local":
            c["pos"] = jnp.full(lead + (size,), -1, jnp.int32)
        return c
    if spec.kind == "cross":
        # filled by prefill (projected frontend); zeros as dry-run stand-in
        t = max(cfg.n_frontend_tokens, 1)
        return {
            "k": jnp.zeros(lead + (batch, t, cfg.n_kv, cfg.head_dim), dtype),
            "v": jnp.zeros(lead + (batch, t, cfg.n_kv, cfg.head_dim), dtype),
        }
    if spec.kind == "ssd":
        base = ssm.ssd_state_init(_ssd_cfg(cfg), batch, dtype)
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros(lead + a.shape, a.dtype), base)
    if spec.kind == "lru":
        base = hybrid.lru_state_init(_lru_cfg(cfg), batch, dtype)
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros(lead + a.shape, a.dtype), base)
    return None


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Shape-complete zero cache (decode dry-run entry point)."""
    cache: dict = {"stack": {}, "rem": {}}
    for i, spec in enumerate(cfg.pattern):
        c = _sub_cache_zeros(cfg, spec, batch, max_len, dtype,
                             lead=(cfg.n_rep,))
        if c is not None:
            cache["stack"][f"sub{i}"] = c
    for i, spec in enumerate(cfg.remainder):
        c = _sub_cache_zeros(cfg, spec, batch, max_len, dtype)
        if c is not None:
            cache["rem"][f"sub{i}"] = c
    kinds = {s.kind for s in cfg.pattern + cfg.remainder}
    if not kinds & {"attn", "local"}:
        cache["step"] = jnp.zeros((), jnp.int32)  # pure-SSM position counter
    return cache


def _sub_prefill(p, h, spec, cfg, *, policy, cross_kv, positions, max_len,
                 dtype):
    """Sublayer forward that also emits its cache. Returns (h, cache)."""
    acfg = attn_cfg_for(cfg, spec)
    b, s, _ = h.shape
    if spec.kind in ("attn", "local"):
        u = _norm(cfg, p["norm"], h)
        with jax.named_scope("attention"):
            q, k, v = L._project_qkv(p["attn"], u, u, acfg, policy, L.NO_BFP,
                                     positions)
            if s > acfg.blockwise_threshold:
                o = L.blockwise_attention(q, k, v, causal=acfg.causal,
                                          softcap=acfg.softcap,
                                          window=acfg.window,
                                          q_chunk=acfg.q_chunk,
                                          kv_chunk=acfg.kv_chunk,
                                          causal_skip=acfg.causal_skip,
                                          scale=acfg.scale)
            else:
                o = L.full_attention(q, k, v, causal=acfg.causal,
                                     softcap=acfg.softcap, window=acfg.window,
                                     scale=acfg.scale)
            o = o.reshape(b, s, acfg.n_heads * acfg.head_dim)
            y = L.dense(p["attn"]["wo"], o, policy=policy)
        if cfg.post_norm:
            y = _norm(cfg, p["post_norm"], y)
        h = _resid(cfg, h, y)
        size = _ring_size(cfg, spec, max_len)
        with jax.named_scope("kv_cache"):
            if spec.kind == "local" and size < max_len:
                keep = min(size, s)
                idx = (jnp.arange(s - keep, s) % size)
                kc = jnp.zeros((b, size, cfg.n_kv, cfg.head_dim), dtype)
                vc = jnp.zeros_like(kc)
                kc = kc.at[:, idx].set(k[:, -keep:].astype(dtype))
                vc = vc.at[:, idx].set(v[:, -keep:].astype(dtype))
                pos = jnp.full((size,), -1, jnp.int32).at[idx].set(
                    jnp.arange(s - keep, s))
                cache = {"k": kc, "v": vc, "len": jnp.asarray(s, jnp.int32),
                         "pos": pos}
            else:
                kc = jnp.zeros((b, max_len, cfg.n_kv, cfg.head_dim), dtype)
                vc = jnp.zeros_like(kc)
                kc = lax.dynamic_update_slice_in_dim(kc, k.astype(dtype), 0, 1)
                vc = lax.dynamic_update_slice_in_dim(vc, v.astype(dtype), 0, 1)
                cache = {"k": kc, "v": vc, "len": jnp.asarray(s, jnp.int32)}
        h, _ = _apply_mlp(p, h, spec, cfg, policy, L.NO_BFP)
        return h, cache

    if spec.kind == "cross":
        u = _norm(cfg, p["norm"], h)
        with jax.named_scope("attention"):
            y = L.attention_layer(p["attn"], u, acfg, policy=policy,
                                  kv_x=cross_kv, positions=positions)
        if cfg.post_norm:
            y = _norm(cfg, p["post_norm"], y)
        h = _resid(cfg, h, y)
        skv = cross_kv.shape[1]
        with jax.named_scope("kv_cache"):
            k = L.dense(p["attn"]["wk"], cross_kv, policy=policy).reshape(
                b, skv, cfg.n_kv, cfg.head_dim)
            v = L.dense(p["attn"]["wv"], cross_kv, policy=policy).reshape(
                b, skv, cfg.n_kv, cfg.head_dim)
            cache = {"k": k.astype(dtype), "v": v.astype(dtype)}
        h, _ = _apply_mlp(p, h, spec, cfg, policy, L.NO_BFP)
        return h, cache
    if spec.kind == "ssd":
        u = _norm(cfg, p["norm"], h)
        c = _ssd_cfg(cfg)
        y, st = ssm.ssd_block(p["ssd"], u, c, policy=policy,
                              state=ssm.ssd_state_init(c, b, dtype))
        h = _resid(cfg, h, y)
        h, _ = _apply_mlp(p, h, spec, cfg, policy, L.NO_BFP)
        return h, st

    if spec.kind == "lru":
        u = _norm(cfg, p["norm"], h)
        c = _lru_cfg(cfg)
        y, st = hybrid.lru_block(p["lru"], u, c, policy=policy,
                                 state=hybrid.lru_state_init(c, b, dtype))
        h = _resid(cfg, h, y)
        h, _ = _apply_mlp(p, h, spec, cfg, policy, L.NO_BFP)
        return h, st
    raise ValueError(spec.kind)


def prefill(params, cfg: ModelConfig, tokens: jax.Array, *,
            frontend: Optional[dict] = None, max_len: int,
            policy: L.Policy = L.Policy(), cache_dtype=jnp.bfloat16,
            logits_mode: str = "all") -> dict:
    """Process a prompt, return {logits, cache} (cache ready for decode).

    ``logits_mode="last"`` (§Perf): unembed only the final position — a
    serving prefill only needs the next-token distribution, and the full
    [B,S,V] logit tensor is a V-wide matmul plus (for data-sharded vocab
    projections) a giant cross-device reduction.
    """
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = embed_tokens(params, cfg, tokens, positions, policy)
    cross_kv = None if frontend is None else frontend.get("cross_kv")

    cache: dict = {"stack": {}, "rem": {}}
    if cfg.n_rep:
        def body(h, p_rep):
            caches = {}
            for i, spec in enumerate(cfg.pattern):
                h, c = _sub_prefill(p_rep[f"sub{i}"], h, spec, cfg,
                                    policy=policy, cross_kv=cross_kv,
                                    positions=positions, max_len=max_len,
                                    dtype=cache_dtype)
                if c is not None:
                    caches[f"sub{i}"] = c
            return h, caches

        h, cache["stack"] = lax.scan(body, h, params["stack"])
    for i, spec in enumerate(cfg.remainder):
        h, c = _sub_prefill(params["rem"][f"sub{i}"], h, spec, cfg,
                            policy=policy, cross_kv=cross_kv,
                            positions=positions, max_len=max_len,
                            dtype=cache_dtype)
        if c is not None:
            cache["rem"][f"sub{i}"] = c
    kinds = {sp.kind for sp in cfg.pattern + cfg.remainder}
    if not kinds & {"attn", "local"}:
        cache["step"] = jnp.asarray(s, jnp.int32)
    h = _norm(cfg, params["final_norm"], h)
    h_out = h[:, -1:] if logits_mode == "last" else h
    with jax.named_scope("unembed_loss"):
        logits = lm_logits(params, cfg, h_out, policy)
    return {"logits": logits, "cache": cache, "hidden": h}


def _sub_decode(p, h, spec, cfg, cache, *, policy):
    """One-token sublayer step. Returns (h, new_cache)."""
    acfg = attn_cfg_for(cfg, spec)
    b = h.shape[0]
    if spec.kind in ("attn", "local"):
        u = _norm(cfg, p["norm"], h)
        with jax.named_scope("attention"):
            if spec.kind == "local" and "pos" in cache:
                y, new_cache = _ring_decode(p["attn"], u, cache, acfg, cfg,
                                            policy)
            else:
                y, new_cache = L.attention_decode(p["attn"], u, cache, acfg,
                                                  policy=policy)
        if cfg.post_norm:
            y = _norm(cfg, p["post_norm"], y)
        h = _resid(cfg, h, y)
        h, _ = _apply_mlp(p, h, spec, cfg, policy, L.NO_BFP)
        return h, new_cache
    if spec.kind == "cross":
        u = _norm(cfg, p["norm"], h)
        with jax.named_scope("attention"):
            q = L.dense(p["attn"]["wq"], u, policy=policy).reshape(
                b, 1, cfg.n_heads, cfg.head_dim)
            o = L.full_attention(q, cache["k"], cache["v"], causal=False,
                                 softcap=acfg.softcap, scale=acfg.scale)
            y = L.dense(p["attn"]["wo"],
                        o.reshape(b, 1, cfg.n_heads * cfg.head_dim),
                        policy=policy)
        if cfg.post_norm:
            y = _norm(cfg, p["post_norm"], y)
        h = _resid(cfg, h, y)
        h, _ = _apply_mlp(p, h, spec, cfg, policy, L.NO_BFP)
        return h, cache
    if spec.kind == "ssd":
        u = _norm(cfg, p["norm"], h)
        y, st = ssm.ssd_block(p["ssd"], u, _ssd_cfg(cfg), policy=policy,
                              state=cache)
        h = _resid(cfg, h, y)
        h, _ = _apply_mlp(p, h, spec, cfg, policy, L.NO_BFP)
        return h, st
    if spec.kind == "lru":
        u = _norm(cfg, p["norm"], h)
        y, st = hybrid.lru_block(p["lru"], u, _lru_cfg(cfg), policy=policy,
                                 state=cache)
        h = _resid(cfg, h, y)
        h, _ = _apply_mlp(p, h, spec, cfg, policy, L.NO_BFP)
        return h, st
    raise ValueError(spec.kind)


def _ring_decode(p_attn, u, cache, acfg: L.AttnConfig, cfg: ModelConfig,
                 policy):
    """Sliding-window decode over a ring buffer cache."""
    b = u.shape[0]
    cur = cache["len"]
    size = cache["k"].shape[1]
    positions = jnp.full((b, 1), cur, jnp.int32)
    q, k, v = L._project_qkv(p_attn, u, u, acfg, policy, L.NO_BFP, positions)
    slot = cur % size
    with jax.named_scope("kv_cache"):
        kc = lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        vc = lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        pos = lax.dynamic_update_slice_in_dim(
            cache["pos"], cur[None].astype(jnp.int32), slot, axis=0)
    g = acfg.n_heads // acfg.n_kv
    scores = L._softcap(
        L._scaled(L._gqa_scores(q, L.expand_kv(kc, g)), acfg.head_dim,
                  acfg.scale),
        acfg.softcap)
    scores = constrain(scores, "dec_scores")   # keep ring cache seq-sharded
    valid = (pos >= 0) & (pos <= cur) & (pos > cur - (acfg.window or size))
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    w = constrain(jax.nn.softmax(scores, axis=-1), "dec_scores")
    o = L._gqa_out(w, L.expand_kv(vc, g)).astype(u.dtype)
    y = L.dense(p_attn["wo"], o.reshape(b, 1, acfg.n_heads * acfg.head_dim),
                policy=policy)
    return y, {"k": kc, "v": vc, "len": cur + 1, "pos": pos}


def decode_step(params, cfg: ModelConfig, tokens: jax.Array, cache: dict, *,
                policy: L.Policy = L.Policy()) -> tuple[jax.Array, dict]:
    """One decode step: tokens [B,1] + cache → (logits [B,1,V], new cache).

    The position is taken from the first attention cache's ``len`` (all
    sublayers advance in lockstep); pure-SSM models carry an explicit
    ``step`` counter instead.
    """
    b = tokens.shape[0]
    step = cache.get("step")
    if step is None:
        step = _first_len(cfg, cache)
    positions = jnp.full((b, 1), step, jnp.int32)
    h = embed_tokens(params, cfg, tokens, positions, policy)

    new_cache: dict = {"stack": {}, "rem": {}}
    if cfg.n_rep:
        def body(h, inp):
            p_rep, c_rep = inp
            new_c = {}
            for i, spec in enumerate(cfg.pattern):
                key = f"sub{i}"
                sub_c = c_rep.get(key)
                h, nc = _sub_decode(p_rep[key], h, spec, cfg, sub_c,
                                    policy=policy)
                if nc is not None:
                    new_c[key] = nc
            return h, new_c

        h, new_cache["stack"] = lax.scan(body, h,
                                         (params["stack"], cache["stack"]))
    for i, spec in enumerate(cfg.remainder):
        key = f"sub{i}"
        h, nc = _sub_decode(params["rem"][key], h, spec, cfg,
                            cache["rem"].get(key), policy=policy)
        if nc is not None:
            new_cache["rem"][key] = nc
    if "step" in cache:
        new_cache["step"] = step + 1
    h = _norm(cfg, params["final_norm"], h)
    with jax.named_scope("unembed_loss"):
        logits = lm_logits(params, cfg, h, policy)
    return logits, new_cache


def _first_len(cfg: ModelConfig, cache: dict):
    for i, spec in enumerate(cfg.pattern):
        if spec.kind in ("attn", "local"):
            return cache["stack"][f"sub{i}"]["len"][0]
    for i, spec in enumerate(cfg.remainder):
        if spec.kind in ("attn", "local"):
            return cache["rem"][f"sub{i}"]["len"]
    raise ValueError("no attention cache; provide cache['step']")
