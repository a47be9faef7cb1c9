"""GShard-style Mixture-of-Experts layer (dropped tokens, capacity factor).

Expert-parallel by construction: the dispatch/combine einsums carry an
explicit expert axis that the sharding rules place on the ``model`` mesh axis
(EP), so GSPMD materializes the all-to-all exchange between the token-sharded
and expert-sharded layouts.  Tokens are processed in fixed-size groups so the
dispatch tensors stay bounded: ``[G, g, E, C]`` with ``C ≈ g·k/E·cf``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.utils import ceil_to, split_keys


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    group_size: int = 1024
    gated: bool = True
    shared_expert: bool = False   # llama4-style always-on expert


def moe_init(key, cfg: MoEConfig) -> dict:
    ks = split_keys(key, ["router", "wi", "wg", "wo", "shared"])
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": L.dense_init(ks["router"], d, e, scale=0.02),
        "wi": jax.random.normal(ks["wi"], (e, d, f), jnp.float32) * scale,
        "wo": jax.random.normal(ks["wo"], (e, f, d), jnp.float32) / math.sqrt(f),
    }
    if cfg.gated:
        p["wg"] = jax.random.normal(ks["wg"], (e, d, f), jnp.float32) * scale
    if cfg.shared_expert:
        p["shared"] = L.mlp_init(ks["shared"], d, f, gated=cfg.gated)
    return p


def capacity(cfg: MoEConfig, group: int) -> int:
    c = int(math.ceil(group * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(4, ceil_to(c, 4))


def moe_apply(params, x: jax.Array, cfg: MoEConfig, *,
              policy: L.Policy = L.Policy(), bfp: L.BFPPolicy = L.NO_BFP):
    """x: [B,S,D] → (y [B,S,D], aux_loss scalar)."""
    b, s, d = x.shape
    cd = policy.compute_dtype
    t = b * s
    g = min(cfg.group_size, t)
    tp = ceil_to(t, g)
    xt = x.reshape(t, d)
    if tp != t:
        xt = jnp.pad(xt, ((0, tp - t), (0, 0)))
    xg = xt.reshape(tp // g, g, d)                     # [G,g,D]
    n_groups = tp // g

    with jax.named_scope("moe_router"):
        logits = L.dense(params["router"], xg, policy=policy).astype(
            jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)        # [G,g,E]

        # load-balancing aux loss (Switch/GShard): E · Σ_e f_e · P_e
        density = jnp.mean(gates, axis=1)              # [G,E] mean router prob
        top1 = jax.nn.one_hot(jnp.argmax(gates, -1), cfg.n_experts)
        frac = jnp.mean(top1, axis=1)                  # [G,E] token fraction
        aux = cfg.n_experts * jnp.mean(jnp.sum(density * frac, axis=-1))

        cap = capacity(cfg, g)
        remaining = gates
        counts = jnp.zeros((n_groups, 1, cfg.n_experts), jnp.float32)
        dispatch = jnp.zeros((n_groups, g, cfg.n_experts, cap), cd)
        combine = jnp.zeros((n_groups, g, cfg.n_experts, cap), cd)
        for _ in range(cfg.top_k):
            idx = jnp.argmax(remaining, axis=-1)       # [G,g]
            gate_k = jnp.take_along_axis(remaining, idx[..., None], -1)[..., 0]
            onehot = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)
            # position of each token within its expert's capacity buffer
            pos = jnp.cumsum(onehot, axis=1) - 1.0 + counts  # [G,g,E]
            counts = counts + jnp.sum(onehot, axis=1, keepdims=True)
            keep = (pos < cap) & (onehot > 0)
            pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                    dtype=jnp.float32)
            d_k = (pos_oh * keep[..., None]).astype(cd)  # [G,g,E,C]
            dispatch = dispatch + d_k
            combine = combine + d_k * gate_k[..., None, None].astype(cd)
            remaining = remaining * (1.0 - onehot)

        # normalize the kept top-k gates to sum to 1 per token
        denom = jnp.sum(combine, axis=(-2, -1), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)

    with jax.named_scope("moe_dispatch"):
        xe = jnp.einsum("gsec,gsd->egcd", dispatch, xg.astype(cd))  # [E,G,C,D]
    with jax.named_scope("moe_experts"):
        wi = bfp.q(params["wi"]).astype(cd)
        wo = bfp.q(params["wo"]).astype(cd)
        h = jnp.einsum("egcd,edf->egcf", xe, wi)
        if "wg" in params:
            wg = bfp.q(params["wg"]).astype(cd)
            h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe, wg)) * h
        else:
            h = jax.nn.silu(h)
        ye = jnp.einsum("egcf,efd->egcd", h, wo)        # [E,G,C,D]
    with jax.named_scope("moe_combine"):
        y = jnp.einsum("gsec,egcd->gsd", combine, ye)   # [G,g,D]
        y = y.reshape(tp, d)[:t].reshape(b, s, d)
    if "shared" in params:
        with jax.named_scope("mlp"):
            y = y + L.mlp(params["shared"], x, policy=policy, bfp=bfp)
    return y.astype(x.dtype), aux
