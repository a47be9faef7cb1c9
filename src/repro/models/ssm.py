"""Mamba-2 (SSD — state-space duality) blocks.

The training/prefill path uses the chunked SSD algorithm (Dao & Gu 2024):
within a chunk everything is batched matmuls (MXU-friendly); across chunks a
small ``lax.scan`` carries the [H, P, N] state.  The decode path is the exact
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
    B, C [B,S,G,N].  Returns (y [B,S,H,P], h_final [B,H,P,N]).
    """
    b, s, h, p = x.shape
    g, n = B.shape[-2:]
    chunk = min(chunk, s)        # decode: no padding waste for tiny s
    sp = ceil_to(s, chunk)
    pad = sp - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc, q = sp // chunk, chunk
    rep = h // g                                   # heads per router group

    xc = x.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h)
    Bc = jnp.repeat(B.reshape(b, nc, q, g, n), rep, axis=3)   # [B,Nc,Q,H,N]
    Cc = jnp.repeat(C.reshape(b, nc, q, g, n), rep, axis=3)

    dA = dtc * A[None, None, None, :]              # [B,Nc,Q,H] (negative)
    dAcs = jnp.cumsum(dA, axis=2)                  # within-chunk cumsum

    # --- intra-chunk (quadratic in Q, batched matmul) -----------------
    # L[i,j] = exp(dAcs_i − dAcs_j) for i ≥ j else 0
    li = dAcs[:, :, :, None, :]                    # [B,Nc,Q,1,H]
    lj = dAcs[:, :, None, :, :]                    # [B,Nc,1,Q,H]
    mask = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    Lmat = jnp.where(mask, jnp.exp(li - lj), 0.0)  # [B,Nc,Q,Q,H]
    scores = jnp.einsum("bcihn,bcjhn->bcijh", Cc, Bc) * Lmat
    xdt = xc * dtc[..., None]                      # [B,Nc,Q,H,P]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, xdt)

    # --- chunk states --------------------------------------------------
    decay_to_end = jnp.exp(dAcs[:, :, -1:, :] - dAcs)      # [B,Nc,Q,H]
    states = jnp.einsum("bcqhn,bcqhp,bcqh->bchpn", Bc, xdt, decay_to_end)

    # --- inter-chunk recurrence ----------------------------------------
    chunk_decay = jnp.exp(dAcs[:, :, -1, :])               # [B,Nc,H]

    def step(hprev, inp):
        st, dec = inp                                       # [B,H,P,N],[B,H]
        hnew = hprev * dec[..., None, None] + st
        return hnew, hprev

    h_init = jnp.zeros((b, h, p, n), jnp.float32) if h0 is None else h0
    h_fin, h_prevs = lax.scan(
        step, h_init,
        (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    h_prevs = h_prevs.swapaxes(0, 1)                        # [B,Nc,H,P,N]

    # --- inter-chunk contribution --------------------------------------
    in_decay = jnp.exp(dAcs)                                # [B,Nc,Q,H]
    y_inter = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp", Cc, h_prevs, in_decay)

    y = (y_intra + y_inter).reshape(b, sp, h, p)[:, :s]
    return y, h_fin


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

    y = y.reshape(b, s, cfg.d_inner).astype(cd)
    y = L.rmsnorm(params["norm"], y) * jax.nn.silu(zgate)
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
