"""Mamba-2 (SSD — state-space duality) blocks.

The training/prefill path uses the chunked SSD algorithm (Dao & Gu 2024):
within a chunk everything is batched matmuls (MXU-friendly); a ``lax.scan``
over chunks carries the [H, P, N] state and does each chunk's work, so its
temporaries are of one chunk.  Each lowering's plan is recorded in
``repro.obs.runtime.ssd_plans``.  The decode path is the exact
single-step recurrence on the same state, so prefill→decode hand-off is
bit-consistent up to float error (covered by tests against the naive
recurrent oracle).

TP note: projections are kept *separate* (z/x/B/C/dt) rather than fused,
so each output segment is head-aligned and shards cleanly on the ``model``
axis — a fused in_proj would put segment boundaries inside shards and force
GSPMD reshards (DESIGN.md §6).

Shapes: x [B,S,H,P] (P=headdim), B/C [B,S,G,N] (G router groups, N=d_state),
dt [B,S,H], A scalar per head.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.obs import runtime
from repro.utils import ceil_to, split_keys


@dataclasses.dataclass(frozen=True)
class SSDConfig:
    d_model: int
    d_state: int = 128
    headdim: int = 64
    expand: int = 2
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    eps: float = 1e-6             # the gated RMSNorm's

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.headdim


def ssd_init(key, cfg: SSDConfig) -> dict:
    ks = split_keys(key, ["z", "x", "B", "C", "dtp", "out",
                          "convx", "convb", "convc", "dt"])
    gn = cfg.n_groups * cfg.d_state
    dt = jnp.exp(jax.random.uniform(ks["dt"], (cfg.n_heads,)) *
                 (math.log(cfg.dt_max) - math.log(cfg.dt_min)) +
                 math.log(cfg.dt_min))
    conv = lambda k, c: jax.random.normal(k, (cfg.conv_width, c), jnp.float32) \
        / math.sqrt(cfg.conv_width)
    return {
        "z_proj": L.dense_init(ks["z"], cfg.d_model, cfg.d_inner),
        "x_proj": L.dense_init(ks["x"], cfg.d_model, cfg.d_inner),
        "b_proj": L.dense_init(ks["B"], cfg.d_model, gn),
        "c_proj": L.dense_init(ks["C"], cfg.d_model, gn),
        "dt_proj": L.dense_init(ks["dtp"], cfg.d_model, cfg.n_heads),
        "out_proj": L.dense_init(ks["out"], cfg.d_inner, cfg.d_model),
        "conv_x": {"w": conv(ks["convx"], cfg.d_inner),
                   "b": jnp.zeros((cfg.d_inner,), jnp.float32)},
        "conv_b": {"w": conv(ks["convb"], gn),
                   "b": jnp.zeros((gn,), jnp.float32)},
        "conv_c": {"w": conv(ks["convc"], gn),
                   "b": jnp.zeros((gn,), jnp.float32)},
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # inverse softplus
        "A_log": jnp.log(jnp.ones((cfg.n_heads,))),   # A = -1 per head
        "D": jnp.ones((cfg.n_heads,), jnp.float32),
        "norm": L.rmsnorm_init(cfg.d_inner),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None):
    """Depthwise causal conv along seq. x [B,S,C], w [K,C].

    With ``state`` [B,K-1,C] (decode), returns (y, new_state).
    """
    k = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(k)) + b
    new_state = xp[:, -(k - 1):, :]
    return jax.nn.silu(y), new_state


def _ssd_chunked(x, dt, A, B, C, chunk: int, h0=None):
    """Chunked SSD scan.

    x [B,S,H,P], dt [B,S,H] (already softplus'ed), A [H] (negative),
    B, C [B,S,G,N]; head ``h`` reads group ``h // (H/G)``.  Returns
    (y [B,S,H,P], h_final [B,H,P,N]).

    One ``lax.scan`` step per chunk of Q tokens does the chunk's whole
    work, so every temporary is of one chunk: ``C·Bᵀ`` once per group
    [B,G,Q,Q]; the decays and scores [B,G,H/G,Q,Q] (the largest); the
    chunk's own output from them; the carried state's output; and the
    state at the chunk's end.  The chunk is a tiling choice: the result
    does not depend on it beyond rounding.
    """
    b, s, h, p = x.shape
    g, n = B.shape[-2:]
    r = h // g                                     # heads per group
    q = min(chunk, s)            # decode: no padding waste for tiny s
    sp = ceil_to(s, q)
    if sp != s:                  # dt 0 past the end: no decay, no input
        pad = ((0, 0), (0, sp - s))
        x = jnp.pad(x, pad + ((0, 0), (0, 0)))
        dt = jnp.pad(dt, pad + ((0, 0),))
        B = jnp.pad(B, pad + ((0, 0), (0, 0)))
        C = jnp.pad(C, pad + ((0, 0), (0, 0)))
    runtime.record_ssd_plan(q, h, g, b * h * q * q * 4)
    xg = x.reshape(b, sp, g, r, p)
    dA = (dt * A).reshape(b, sp, g, r)
    dtg = dt.reshape(b, sp, g, r)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def step(carry, c):
        hprev, y = carry                           # [B,G,R,P,N], [B,Sp,G,R,P]
        part = lambda t: lax.dynamic_slice_in_dim(t, c * q, q, axis=1)
        xc, dtc, Bc, Cc = part(xg), part(dtg), part(B), part(C)
        cs = jnp.cumsum(part(dA), axis=1)          # [B,Q,G,R] within chunk
        xdt = xc * dtc[..., None]                  # [B,Q,G,R,P]
        # L[i,j] = exp(cs_i - cs_j) for i >= j, else 0
        seg = jnp.moveaxis(cs, 1, -1)              # [B,G,R,Q]
        diff = seg[..., :, None] - seg[..., None, :]
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))  # [B,G,R,Q,Q]
        cb = jnp.einsum("bign,bjgn->bgij", Cc, Bc)  # once per group
        yc = jnp.einsum("bgrij,bjgrp->bigrp", cb[:, :, None] * decay, xdt)
        yc = yc + jnp.einsum("bign,bgrpn->bigrp", Cc, hprev) \
            * jnp.exp(cs)[..., None]
        last = cs[:, -1]                           # [B,G,R]
        states = jnp.einsum("bjgn,bjgrp->bgrpn", Bc,
                            xdt * jnp.exp(last[:, None] - cs)[..., None])
        hnew = hprev * jnp.exp(last)[..., None, None] + states
        return (hnew, lax.dynamic_update_slice_in_dim(y, yc, c * q, 1)), None

    h_init = (jnp.zeros((b, g, r, p, n), jnp.float32) if h0 is None
              else h0.reshape(b, g, r, p, n))
    (h_fin, y), _ = lax.scan(step, (h_init, jnp.zeros_like(xg)),
                             jnp.arange(sp // q))
    return y.reshape(b, sp, h, p)[:, :s], h_fin.reshape(b, h, p, n)


@jax.named_scope("ssd")
def ssd_block(params, x: jax.Array, cfg: SSDConfig, *,
              policy: L.Policy = L.Policy(), bfp: L.BFPPolicy = L.NO_BFP,
              state: dict | None = None):
    """Full mamba2 mixer. x [B,S,D] → (y [B,S,D], new_state|None).

    ``state``: {"h": [B,H,P,N], "conv_x"/"conv_b"/"conv_c": [B,K-1,·]}
    enables stateful decode; None = stateless train/prefill.
    """
    b, s, d = x.shape
    cd = policy.compute_dtype
    zgate = L.dense(params["z_proj"], x, policy=policy, bfp=bfp)
    xr = L.dense(params["x_proj"], x, policy=policy, bfp=bfp)
    Br = L.dense(params["b_proj"], x, policy=policy, bfp=bfp)
    Cr = L.dense(params["c_proj"], x, policy=policy, bfp=bfp)
    dt_raw = L.dense(params["dt_proj"], x, policy=policy, bfp=bfp)

    cs = {"conv_x": None, "conv_b": None, "conv_c": None} if state is None \
        else state
    xs, ncx = _causal_conv(xr, params["conv_x"]["w"].astype(cd),
                           params["conv_x"]["b"].astype(cd), cs["conv_x"])
    Bs, ncb = _causal_conv(Br, params["conv_b"]["w"].astype(cd),
                           params["conv_b"]["b"].astype(cd), cs["conv_b"])
    Cs, ncc = _causal_conv(Cr, params["conv_c"]["w"].astype(cd),
                           params["conv_c"]["b"].astype(cd), cs["conv_c"])

    xs = xs.reshape(b, s, cfg.n_heads, cfg.headdim)
    B = Bs.reshape(b, s, cfg.n_groups, cfg.d_state)
    C = Cs.reshape(b, s, cfg.n_groups, cfg.d_state)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                         params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"])

    xs32, B32, C32 = (t.astype(jnp.float32) for t in (xs, B, C))
    h0 = None if state is None else state["h"]
    y, h_fin = _ssd_chunked(xs32, dt, A, B32, C32, cfg.chunk, h0=h0)
    y = y + xs32 * params["D"][None, None, :, None]

    # Mamba-2's gated RMSNorm: gate, then normalise over d_inner
    y = y.reshape(b, s, cfg.d_inner) * jax.nn.silu(zgate.astype(jnp.float32))
    y = L.rmsnorm(params["norm"], y, cfg.eps).astype(cd)
    out = L.dense(params["out_proj"], y, policy=policy, bfp=bfp)
    new_state = None if state is None else {
        "h": h_fin, "conv_x": ncx, "conv_b": ncb, "conv_c": ncc}
    return out, new_state


def ssd_state_init(cfg: SSDConfig, batch: int, dtype=jnp.float32) -> dict:
    gn = cfg.n_groups * cfg.d_state
    k = cfg.conv_width - 1
    return {
        "h": jnp.zeros((batch, cfg.n_heads, cfg.headdim, cfg.d_state),
                       jnp.float32),
        "conv_x": jnp.zeros((batch, k, cfg.d_inner), dtype),
        "conv_b": jnp.zeros((batch, k, gn), dtype),
        "conv_c": jnp.zeros((batch, k, gn), dtype),
    }


def ssd_reference(x, dt, A, B, C):
    """Naive O(S·N·P) recurrent oracle for tests. Shapes as _ssd_chunked."""
    b, s, h, p = x.shape
    g, n = B.shape[-2:]
    rep = h // g
    Bf = jnp.repeat(B, rep, axis=2)
    Cf = jnp.repeat(C, rep, axis=2)

    def step(hprev, t):
        xt, dtt, Bt, Ct = x[:, t], dt[:, t], Bf[:, t], Cf[:, t]
        dA = jnp.exp(dtt * A[None, :])                        # [B,H]
        hnew = hprev * dA[..., None, None] + jnp.einsum(
            "bhn,bhp,bh->bhpn", Bt, xt, dtt)
        y = jnp.einsum("bhn,bhpn->bhp", Ct, hnew)
        return hnew, y

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    hf, ys = lax.scan(step, h0, jnp.arange(s))
    return ys.swapaxes(0, 1), hf                              # [B,S,H,P]
