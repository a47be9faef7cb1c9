"""RG-LRU recurrent block (Griffin / RecurrentGemma).

    r_t = σ(W_r x_t)                 (recurrence gate)
    i_t = σ(W_i x_t)                 (input gate)
    log a_t = −c · softplus(Λ) · r_t
    h_t = a_t ⊙ h_{t−1} + √(1 − a_t²) ⊙ (i_t ⊙ x_t)

Training/prefill uses ``lax.associative_scan`` (log-depth, parallel over
seq); decode is the exact one-step recurrence on the carried state.
The enclosing recurrent block is Griffin's: depthwise causal conv on the
recurrent branch, GeLU gate branch, elementwise merge, output projection.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.ssm import _causal_conv
from repro.utils import split_keys

_C = 8.0  # Griffin's fixed exponent scale


@dataclasses.dataclass(frozen=True)
class LRUConfig:
    d_model: int
    lru_width: int
    conv_width: int = 4
    # §Perf H2: bound associative-scan temporaries to O(chunk) by scanning
    # chunk-by-chunk with a carried state (None = single full-length scan).
    scan_chunk: int | None = None


def lru_init(key, cfg: LRUConfig) -> dict:
    ks = split_keys(key, ["wx", "wy", "wo", "conv", "wr", "wi", "lam"])
    w = cfg.lru_width
    # Λ init so a ∈ (0.9, 0.999) at r=1 (Griffin appendix)
    u = jax.random.uniform(ks["lam"], (w,), minval=0.9, maxval=0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(u) / _C))       # inverse of a=exp(-c·sp(Λ))
    return {
        "wx": L.dense_init(ks["wx"], cfg.d_model, w),
        "wy": L.dense_init(ks["wy"], cfg.d_model, w),
        "wo": L.dense_init(ks["wo"], w, cfg.d_model),
        "conv_w": jax.random.normal(ks["conv"], (cfg.conv_width, w),
                                    jnp.float32) / math.sqrt(cfg.conv_width),
        "conv_b": jnp.zeros((w,), jnp.float32),
        "wr": L.dense_init(ks["wr"], w, w, bias=True, scale=0.02),
        "wi": L.dense_init(ks["wi"], w, w, bias=True, scale=0.02),
        "lambda": lam,
    }


def _combine(u, v):
    a1, b1 = u
    a2, b2 = v
    return a2 * a1, a2 * b1 + b2


def _rg_lru(params, x: jax.Array, policy: L.Policy, h0=None,
            scan_chunk: int | None = None):
    """x: [B,S,W] → (y [B,S,W] f32, h_final [B,W] f32)."""
    from repro.distributed.ctx import constrain
    x32 = x.astype(jnp.float32)
    r = jax.nn.sigmoid(constrain(
        L.dense(params["wr"], x, policy=policy), "act_lru")
        .astype(jnp.float32))
    i = jax.nn.sigmoid(constrain(
        L.dense(params["wi"], x, policy=policy), "act_lru")
        .astype(jnp.float32))
    log_a = -_C * jax.nn.softplus(params["lambda"])[None, None, :] * r
    a = jnp.exp(log_a)
    gated_x = jnp.sqrt(jnp.maximum(-jnp.expm1(2.0 * log_a), 1e-12)) * i * x32

    if x.shape[1] == 1 and h0 is not None:            # decode fast path
        h = a[:, 0] * h0 + gated_x[:, 0]
        return h[:, None, :], h

    b, s, w = x.shape
    if scan_chunk is None or scan_chunk >= s:
        if h0 is not None:
            # fold the carried state in as a virtual step-0 contribution
            gated_x = gated_x.at[:, 0].add(a[:, 0] * h0)
        _, acc_b = lax.associative_scan(_combine, (a, gated_x), axis=1)
        return acc_b, acc_b[:, -1]

    # §Perf H2: chunked scan — log-depth within a chunk, sequential carry
    # across chunks; temporaries are O(B·chunk·W) instead of O(B·S·W).
    from repro.utils import ceil_to
    sp = ceil_to(s, scan_chunk)
    if sp != s:
        a = jnp.pad(a, ((0, 0), (0, sp - s), (0, 0)), constant_values=1.0)
        gated_x = jnp.pad(gated_x, ((0, 0), (0, sp - s), (0, 0)))
    nc = sp // scan_chunk
    ac = a.reshape(b, nc, scan_chunk, w).swapaxes(0, 1)
    gc = gated_x.reshape(b, nc, scan_chunk, w).swapaxes(0, 1)

    def chunk_step(h, inp):
        a_i, g_i = inp                                 # [B,chunk,W]
        acc_a, acc_b = lax.associative_scan(_combine, (a_i, g_i), axis=1)
        y = acc_b + acc_a * h[:, None, :]              # fold carried state
        return y[:, -1], y

    h_init = jnp.zeros((b, w), jnp.float32) if h0 is None else h0
    h_fin, ys = lax.scan(chunk_step, h_init, (ac, gc))
    y = ys.swapaxes(0, 1).reshape(b, sp, w)[:, :s]
    return y, y[:, -1]


@jax.named_scope("lru")
def lru_block(params, x: jax.Array, cfg: LRUConfig, *,
              policy: L.Policy = L.Policy(), bfp: L.BFPPolicy = L.NO_BFP,
              state: dict | None = None):
    """Griffin recurrent block. x [B,S,D] → (y [B,S,D], new_state|None)."""
    cd = policy.compute_dtype
    gate = jax.nn.gelu(L.dense(params["wy"], x, policy=policy, bfp=bfp))
    rec = L.dense(params["wx"], x, policy=policy, bfp=bfp)
    conv_state = None if state is None else state["conv"]
    rec, new_conv = _causal_conv(rec, params["conv_w"].astype(cd),
                                 params["conv_b"].astype(cd), conv_state)
    h0 = None if state is None else state["h"]
    y, h_fin = _rg_lru(params, rec, policy, h0=h0,
                       scan_chunk=cfg.scan_chunk)
    out = L.dense(params["wo"], y.astype(cd) * gate, policy=policy, bfp=bfp)
    new_state = None if state is None else {"h": h_fin, "conv": new_conv}
    return out, new_state


def lru_state_init(cfg: LRUConfig, batch: int, dtype=jnp.float32) -> dict:
    return {
        "h": jnp.zeros((batch, cfg.lru_width), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.lru_width), dtype),
    }


def rg_lru_reference(params, x, policy: L.Policy, h0=None):
    """Naive per-step recurrence oracle for tests."""
    r = jax.nn.sigmoid(L.dense(params["wr"], x, policy=policy)
                       .astype(jnp.float32))
    i = jax.nn.sigmoid(L.dense(params["wi"], x, policy=policy)
                       .astype(jnp.float32))
    log_a = -_C * jax.nn.softplus(params["lambda"])[None, None, :] * r
    a = jnp.exp(log_a)
    gx = jnp.sqrt(jnp.maximum(-jnp.expm1(2.0 * log_a), 1e-12)) * i \
        * x.astype(jnp.float32)

    def step(h, t):
        h = a[:, t] * h + gx[:, t]
        return h, h

    b, s, w = x.shape
    h_init = jnp.zeros((b, w), jnp.float32) if h0 is None else h0
    hf, ys = lax.scan(step, h_init, jnp.arange(s))
    return ys.swapaxes(0, 1), hf
