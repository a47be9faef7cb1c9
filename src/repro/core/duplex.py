"""Duplex DNN (DuDNN) — CAMEL §III: frozen backbone + reversible branch.

Structure (paper Fig 8/9, generalized from CNN/ViT classification to the
LM-family backbones this framework ships):

* the **backbone** (any registry architecture) runs forward-only under
  ``stop_gradient`` — its weights are frozen, its normalization stays (and is
  statically foldable since it never trains);
* the **branch** is a stack of reversible blocks (``core.reversible``) over a
  *pooled* stream (paper §III-C: aggressive pooling, factor ~16, cuts branch
  compute quadratically) with **no normalization layers** (§III-D) and
  **2D-BFP quantized matmuls** (§III-E);
* backbone hidden states are *tapped* at matching depths, pooled, projected,
  and injected into the branch's ``x2`` stream (knowledge transfer).

LM-causality note (an adaptation the paper didn't need): pooling mixes a
segment's future tokens, so the branch correction for token ``t`` uses only
*fully-past* segments (``floor(t/r) − 1``) and branch attention is causal in
pooled positions.  This keeps next-token training leak-free; see
``upsample_causal``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.reversible import ReversibleStack, stack_params
from repro.models import layers as L
from repro.utils import ceil_to, split_keys


@dataclasses.dataclass(frozen=True)
class DuplexConfig:
    n_blocks: int = 4            # reversible branch depth (paper: 4–6)
    d_branch: int = 256          # branch stream width
    pool_factor: int = 16        # §III-C; paper uses up to 16
    branch_heads: int = 4
    branch_ff_mult: int = 4
    use_norm: bool = False       # §III-D ablation (Fig 21b): default norm-free
    causal: bool = True          # LM mode; False for classification
    bfp: L.BFPPolicy = L.BFPPolicy(enabled=True)  # §III-E on branch matmuls


# --------------------------------------------------------------------------
# pooling / upsampling (seq-dim analogue of the paper's spatial pooling)
# --------------------------------------------------------------------------

def pool_seq(x: jax.Array, r: int) -> jax.Array:
    """Non-overlapping mean pooling along seq: [B,S,D] → [B,ceil(S/r),D]."""
    if r == 1:
        return x
    b, s, d = x.shape
    sp = ceil_to(s, r)
    if sp != s:
        x = jnp.pad(x, ((0, 0), (0, sp - s), (0, 0)))
        # renormalize the ragged tail so padding doesn't dilute the mean
        counts = jnp.clip(jnp.minimum(r, s - jnp.arange(0, sp, r)), 1, r)
    else:
        counts = jnp.full((sp // r,), r)
    pooled = x.reshape(b, sp // r, r, d).sum(axis=2)
    return pooled / counts[None, :, None].astype(x.dtype)


def upsample_causal(y: jax.Array, r: int, s: int) -> jax.Array:
    """Causal upsample: token t receives pooled segment floor(t/r) − 1.

    Segment i pools tokens [i·r, (i+1)·r); only *complete, strictly past*
    segments may influence a token's correction (no label leak).
    """
    if r == 1:
        # even at r=1 a one-step shift is required for strict causality of
        # the *additive correction* path (token t's correction from segment
        # t would include token t itself — fine for LM hidden states, but we
        # keep the shifted convention uniform).
        seg = jnp.arange(s)
    else:
        seg = jnp.arange(s) // r
    idx = jnp.clip(seg - 1, 0, y.shape[1] - 1)
    gathered = y[:, idx]                               # [B,S,D]
    valid = (seg >= 1)[None, :, None]
    return jnp.where(valid, gathered, jnp.zeros_like(gathered))


def upsample_full(y: jax.Array, r: int, s: int) -> jax.Array:
    """Non-causal upsample (classification mode): repeat each segment."""
    idx = jnp.clip(jnp.arange(s) // r, 0, y.shape[1] - 1)
    return y[:, idx]


# --------------------------------------------------------------------------
# branch blocks: F1 = attention mixer, F2 = gated MLP — both norm-free
# --------------------------------------------------------------------------

def _branch_attn_cfg(cfg: DuplexConfig) -> L.AttnConfig:
    hd = max(cfg.d_branch // cfg.branch_heads, 8)
    return L.AttnConfig(
        d_model=cfg.d_branch, n_heads=cfg.branch_heads,
        n_kv=cfg.branch_heads, head_dim=hd, causal=cfg.causal,
        blockwise_threshold=4096)


def branch_block_init(key: jax.Array, cfg: DuplexConfig) -> dict:
    ks = split_keys(key, ["attn", "mlp", "n1", "n2"])
    acfg = _branch_attn_cfg(cfg)
    p = {
        "f1": {"attn": L.attn_init(ks["attn"], acfg)},
        "f2": {"mlp": L.mlp_init(ks["mlp"], cfg.d_branch,
                                 cfg.d_branch * cfg.branch_ff_mult)},
    }
    # norm-free stability: damp the residual writers (out projections)
    p["f1"]["attn"]["wo"]["w"] = p["f1"]["attn"]["wo"]["w"] * 0.1
    p["f2"]["mlp"]["wo"]["w"] = p["f2"]["mlp"]["wo"]["w"] * 0.1
    if cfg.use_norm:
        p["f1"]["norm"] = L.rmsnorm_init(cfg.d_branch)
        p["f2"]["norm"] = L.rmsnorm_init(cfg.d_branch)
    return p


def make_branch_fns(cfg: DuplexConfig, policy: L.Policy):
    acfg = _branch_attn_cfg(cfg)

    def f1(p, x):
        h = L.rmsnorm(p["norm"], x) if cfg.use_norm else x
        return L.attention_layer(p["attn"], h, acfg, policy=policy,
                                 bfp=cfg.bfp)

    def f2(p, x):
        h = L.rmsnorm(p["norm"], x) if cfg.use_norm else x
        return L.mlp(p["mlp"], h, policy=policy, bfp=cfg.bfp)

    return f1, f2


# --------------------------------------------------------------------------
# the duplex branch head: taps in, correction out
# --------------------------------------------------------------------------

def duplex_init(key: jax.Array, cfg: DuplexConfig, d_model: int) -> dict:
    ks = split_keys(key, ["in1", "in2", "taps", "out", "blocks"])
    return {
        "in_proj1": L.dense_init(ks["in1"], d_model, cfg.d_branch),
        "in_proj2": L.dense_init(ks["in2"], d_model, cfg.d_branch),
        # one tap projection per reversible block (stacked for scan)
        "tap_proj": stack_params(
            lambda k: L.dense_init(k, d_model, cfg.d_branch, scale=0.02),
            ks["taps"], cfg.n_blocks),
        "out_proj": L.dense_init(ks["out"], 2 * cfg.d_branch, d_model,
                                 scale=0.02),
        "blocks": stack_params(lambda k: branch_block_init(k, cfg),
                               ks["blocks"], cfg.n_blocks),
    }


@jax.named_scope("branch")
def duplex_apply(
    params: dict,
    cfg: DuplexConfig,
    emb: jax.Array,            # [B,S,d_model] frozen input embeddings
    taps: jax.Array,           # [n_blocks,B,S,d_model] frozen backbone taps
    *,
    policy: L.Policy = L.Policy(),
    taps_pooled: bool = False,  # taps already pooled inside the backbone scan
) -> jax.Array:
    """Branch forward: returns the additive correction [B,S,d_model].

    Everything upstream (emb, taps) is stop-gradient'ed — the backbone is
    frozen (paper Fig 9b/c) and XLA stores no residuals for it.
    """
    b, s, d_model = emb.shape
    r = cfg.pool_factor
    emb = jax.lax.stop_gradient(emb)
    taps = jax.lax.stop_gradient(taps)

    pooled_in = pool_seq(emb, r)                        # [B,Sp,D]
    pooled_taps = taps if taps_pooled else \
        jax.vmap(lambda t: pool_seq(t, r))(taps)        # [L,B,Sp,D]

    f1, f2 = make_branch_fns(cfg, policy)
    stack = ReversibleStack(f1, f2)

    x1 = L.dense(params["in_proj1"], pooled_in, policy=policy, bfp=cfg.bfp)
    x2 = L.dense(params["in_proj2"], pooled_in, policy=policy, bfp=cfg.bfp)
    inj = jax.vmap(
        lambda p, t: L.dense(p, t, policy=policy, bfp=cfg.bfp)
    )(params["tap_proj"], pooled_taps)                  # [L,B,Sp,d_branch]

    y1, y2 = stack(params["blocks"], x1, x2, inj)
    y = jnp.concatenate([y1, y2], axis=-1)              # [B,Sp,2·d_branch]
    corr = L.dense(params["out_proj"], y, policy=policy, bfp=cfg.bfp)
    up = upsample_causal if cfg.causal else upsample_full
    return up(corr, r, s)
