"""GShard-style Mixture-of-Experts layer (dropped tokens, capacity factor).

Tokens are routed in fixed-size groups of ``g``.  Within a group the router
takes ``top_k`` rounds of argmax over its float32 softmax gates; a round
gives its choices the next free slots of their experts in token order,
after the slots of the earlier rounds, and drops a choice past its
expert's capacity ``C ≈ g·k/E·cf``; the kept gates are renormalised to sum
to one.  Two routes move the tokens by that one rule:

- **index** (``g >= _INDEX_ROUTE_MIN_GROUP``: training and prefill): the
  router scatters int32 row ids into ``owner [E, G, C]``, dispatch
  gathers each slot's row from the group and combine gathers each token's
  ``top_k`` expert outputs back and weights them.  Its work grows as
  ``g·k·D``.
- **one-hot** (smaller groups: decode, where one group holds a batch's
  tokens): dense ``[G, g, E, C]`` dispatch and combine tensors, contracted
  by einsum.  Its work grows as ``g²·k·D``, but at a few tokens one small
  contraction costs less than the gathers.

The route is chosen from the group's static size, and each lowering is
recorded in ``repro.obs.runtime.moe_routes``.

Expert parallelism: a layer may hold ``n_experts`` of the router's
``n_routed`` experts, from router output ``offset`` on, as one chip of an
expert-parallel group does.  It routes, places and drops every choice over
all the router's experts and normalises the gates over all kept choices,
as the whole layer does, then dispatches and combines only the choices of
the experts it holds: its output is their share of the whole layer's.  A
shared expert is every token's, on every chip.  Both routes hand the
experts ``[E, G, C, D]``: expert-parallel by construction, the sharding
rules place that expert axis on the ``model`` mesh axis (EP), so GSPMD
materializes the all-to-all exchange between the token-sharded and
expert-sharded layouts.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.obs import runtime
from repro.utils import ceil_to, split_keys

# Tokens per group from which moe_apply routes by index.  Set from both
# routes timed on one TPU v5e at granite-moe widths (E 32, top 8, D 1024,
# d_ff 512; tools/moe_routes.py): the one-hot route was faster up to 256
# tokens (160 against 169 us a layer at 16, 215 against 251 at 256) and the
# index route from 384 (252 against 263 us; 718 against 980 at 1,024).  A
# row gather pays a fixed cost that the contraction of a few tokens does
# not, while the contraction's cost grows with the square of the group.
_INDEX_ROUTE_MIN_GROUP = 384


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
    shared_d_ff: int = 0          # its width; 0: d_ff
    n_routed: int = 0             # the router's experts; 0: n_experts
    offset: int = 0               # router index of the first expert held

    @property
    def routed(self) -> int:
        return self.n_routed or self.n_experts

    @property
    def holds_all(self) -> bool:
        return self.routed == self.n_experts


def moe_init(key, cfg: MoEConfig) -> dict:
    ks = split_keys(key, ["router", "wi", "wg", "wo", "shared"])
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": L.dense_init(ks["router"], d, cfg.routed, scale=0.02),
        "wi": jax.random.normal(ks["wi"], (e, d, f), jnp.float32) * scale,
        "wo": jax.random.normal(ks["wo"], (e, f, d), jnp.float32) / math.sqrt(f),
    }
    if cfg.gated:
        p["wg"] = jax.random.normal(ks["wg"], (e, d, f), jnp.float32) * scale
    if cfg.shared_expert:
        p["shared"] = L.mlp_init(ks["shared"], d, cfg.shared_d_ff or f,
                                 gated=cfg.gated)
    return p


def capacity(cfg: MoEConfig, group: int) -> int:
    """Slots of each expert in a group: of the router's experts, so that a
    share of them keeps the choices the whole layer keeps."""
    c = int(math.ceil(group * cfg.top_k / cfg.routed * cfg.capacity_factor))
    return max(4, ceil_to(c, 4))


def moe_apply(params, x: jax.Array, cfg: MoEConfig, *,
              policy: L.Policy = L.Policy(), bfp: L.BFPPolicy = L.NO_BFP):
    """x: [B,S,D] → (y [B,S,D], aux_loss scalar)."""
    b, s, d = x.shape
    t = b * s
    g = min(cfg.group_size, t)
    tp = ceil_to(t, g)
    xt = x.reshape(t, d)
    if tp != t:
        xt = jnp.pad(xt, ((0, tp - t), (0, 0)))
    xg = xt.reshape(tp // g, g, d)                     # [G,g,D]

    route = _index_route if g >= _INDEX_ROUTE_MIN_GROUP else _onehot_route
    runtime.record_moe_route("index" if route is _index_route else "onehot",
                             g, cfg.n_experts, capacity(cfg, g),
                             routed=cfg.routed, offset=cfg.offset)
    y, aux = route(params, xg, cfg, policy=policy, bfp=bfp)
    with jax.named_scope("moe_combine"):
        y = y.reshape(tp, d)[:t].reshape(b, s, d)
    if "shared" in params:
        with jax.named_scope("mlp"):
            y = y + L.mlp(params["shared"], x, policy=policy, bfp=bfp)
    return y.astype(x.dtype), aux


def _gates(params, xg, cfg: MoEConfig, policy: L.Policy):
    """Router gates [G,g,E] (f32 softmax) and the load-balancing aux loss
    (Switch/GShard): E · Σ_e f_e · P_e."""
    logits = L.dense(params["router"], xg, policy=policy).astype(
        jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)            # [G,g,E]
    density = jnp.mean(gates, axis=1)                  # [G,E] mean router prob
    top1 = jax.nn.one_hot(jnp.argmax(gates, -1), cfg.routed)
    frac = jnp.mean(top1, axis=1)                      # [G,E] token fraction
    aux = cfg.routed * jnp.mean(jnp.sum(density * frac, axis=-1))
    return gates, aux


def _experts(params, xe, cd, bfp: L.BFPPolicy):
    """xe [E,G,C,D] → every expert's output on its slots, [E,G,C,D]."""
    with jax.named_scope("moe_experts"):
        wi = bfp.q(params["wi"]).astype(cd)
        wo = bfp.q(params["wo"]).astype(cd)
        h = jnp.einsum("egcd,edf->egcf", xe, wi)
        if "wg" in params:
            wg = bfp.q(params["wg"]).astype(cd)
            h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe, wg)) * h
        else:
            h = jax.nn.silu(h)
        return jnp.einsum("egcf,efd->egcd", h, wo)


def _onehot_route(params, xg, cfg: MoEConfig, *, policy: L.Policy,
                  bfp: L.BFPPolicy):
    """xg [G,g,D] → (y [G,g,D], aux): tokens moved by dense [G,g,E,C]
    dispatch and combine tensors."""
    n_groups, g, _ = xg.shape
    cd = policy.compute_dtype
    held = slice(cfg.offset, cfg.offset + cfg.n_experts)
    with jax.named_scope("moe_router"):
        gates, aux = _gates(params, xg, cfg, policy)
        cap = capacity(cfg, g)
        remaining = gates
        counts = jnp.zeros((n_groups, 1, cfg.routed), jnp.float32)
        dispatch = jnp.zeros((n_groups, g, cfg.n_experts, cap), cd)
        combine = jnp.zeros((n_groups, g, cfg.n_experts, cap), cd)
        if not cfg.holds_all:
            kept = jnp.zeros((n_groups, g), jnp.float32)   # kept gates' sum
        for _ in range(cfg.top_k):
            idx = jnp.argmax(remaining, axis=-1)       # [G,g]
            gate_k = jnp.take_along_axis(remaining, idx[..., None], -1)[..., 0]
            onehot = jax.nn.one_hot(idx, cfg.routed, dtype=jnp.float32)
            # position of each token within its expert's capacity buffer
            pos = jnp.cumsum(onehot, axis=1) - 1.0 + counts  # [G,g,E]
            counts = counts + jnp.sum(onehot, axis=1, keepdims=True)
            keep = (pos < cap) & (onehot > 0)
            if not cfg.holds_all:
                kept = kept + jnp.where(jnp.any(keep, -1), gate_k, 0.0)
                pos, keep = pos[..., held], keep[..., held]
            pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                    dtype=jnp.float32)
            d_k = (pos_oh * keep[..., None]).astype(cd)  # [G,g,E,C]
            dispatch = dispatch + d_k
            combine = combine + d_k * gate_k[..., None, None].astype(cd)
            remaining = remaining * (1.0 - onehot)

        # normalize the kept top-k gates to sum to 1 per token
        if cfg.holds_all:
            denom = jnp.sum(combine, axis=(-2, -1), keepdims=True)
        else:
            denom = kept[..., None, None].astype(cd)
        combine = combine / jnp.maximum(denom, 1e-9)

    with jax.named_scope("moe_dispatch"):
        xe = jnp.einsum("gsec,gsd->egcd", dispatch, xg.astype(cd))  # [E,G,C,D]
    ye = _experts(params, xe, cd, bfp)
    with jax.named_scope("moe_combine"):
        y = jnp.einsum("gsec,egcd->gsd", combine, ye)   # [G,g,D]
    return y, aux


def _index_route(params, xg, cfg: MoEConfig, *, policy: L.Policy,
                 bfp: L.BFPPolicy):
    """xg [G,g,D] → (y [G,g,D], aux): tokens moved by int32 indices, the
    D-wide rows gathered and never scattered."""
    n_groups, g, d = xg.shape
    e, cd = cfg.n_experts, policy.compute_dtype
    with jax.named_scope("moe_router"):
        gates, aux = _gates(params, xg, cfg, policy)
        cap = capacity(cfg, g)
        remaining = gates
        counts = jnp.zeros((n_groups, 1, cfg.routed), jnp.float32)
        idx, slot, gate = [], [], []
        for _ in range(cfg.top_k):
            i = jnp.argmax(remaining, axis=-1)         # [G,g]
            onehot = jax.nn.one_hot(i, cfg.routed, dtype=jnp.float32)
            pos = jnp.cumsum(onehot, axis=1) - 1.0 + counts  # [G,g,E]
            counts = counts + jnp.sum(onehot, axis=1, keepdims=True)
            idx.append(i)
            slot.append(jnp.sum(pos * onehot, axis=-1))
            gate.append(jnp.max(remaining, axis=-1))
            remaining = remaining * (1.0 - onehot)
        idx = jnp.stack(idx, -1)                       # [G,g,k]
        slot = jnp.stack(slot, -1).astype(jnp.int32)
        keep = slot < cap
        gate = jnp.where(keep, jnp.stack(gate, -1), 0.0)
        gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
        if not cfg.holds_all:
            # the held experts' choices, numbered from the first held
            idx = idx - cfg.offset
            mine = (idx >= 0) & (idx < e)
            keep, gate = keep & mine, jnp.where(mine, gate, 0.0)
            idx = jnp.clip(idx, 0, e - 1)

        # owner: the row of xg [G*g, D] that fills each slot; an empty
        # slot's is G*g, past the end, which the gather reads as zeros.  A
        # dropped choice writes to the spare slot ``cap``.
        grp = jnp.arange(n_groups, dtype=jnp.int32)[:, None, None]
        tok = grp * g + jnp.arange(g, dtype=jnp.int32)[:, None]
        owner = jnp.full((e, n_groups, cap + 1), n_groups * g, jnp.int32)
        owner = owner.at[idx, grp, jnp.where(keep, slot, cap)].set(
            jnp.broadcast_to(tok, idx.shape))[:, :, :cap]
        # each choice's row of ye [E*G*C, D]; a dropped one weighs 0
        row = (idx * n_groups + grp) * cap + jnp.minimum(slot, cap - 1)

    # Flat rows, not rows within each group: one chip runs the flat gather
    # 3.9 times faster at 8 x 4096, though GSPMD cannot keep it local to a
    # device that holds some of the groups.
    with jax.named_scope("moe_dispatch"):
        xe = xg.astype(cd).reshape(-1, d).at[owner].get(
            mode="fill", fill_value=0)                 # [E,G,C,D]
    ye = _experts(params, xe, cd, bfp)
    with jax.named_scope("moe_combine"):
        ye = ye.reshape(-1, d)
        y = sum(gate[..., j, None] * ye[row[..., j]].astype(jnp.float32)
                for j in range(cfg.top_k))             # [G,g,D]
    return y.astype(cd), aux
