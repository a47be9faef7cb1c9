"""Shared model primitives: dense (optionally 2D-BFP), norms, embeddings,
RoPE, MLPs, and the attention cores (full / blockwise / local-window /
cross / decode-with-cache).

Conventions
-----------
* activations are ``[B, S, D]``; attention heads ``[B, S, H, hd]``.
* params are plain dicts of fp32 master arrays; every apply casts to the
  policy compute dtype at the point of use (mixed precision, DESIGN.md §2).
* 2D-BFP training quantization enters exclusively through ``dense`` — the
  paper quantizes matrix operands at matmul boundaries (Table I).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bfp as bfp_mod
from repro.utils import ceil_to, split_keys


@dataclasses.dataclass(frozen=True)
class Policy:
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class BFPPolicy:
    """Fake-quant (STE) 2D BFP applied to matmul operands during training."""
    enabled: bool = False
    group: Tuple[int, int] = bfp_mod.PAPER_GROUP
    ebits: int = bfp_mod.PAPER_EBITS
    mbits: int = bfp_mod.PAPER_MBITS

    def q(self, x: jax.Array) -> jax.Array:
        if not self.enabled:
            return x
        shape = x.shape
        x2 = x.reshape(-1, shape[-1]) if x.ndim != 2 else x
        out = bfp_mod.bfp_qdq(x2, self.group, self.ebits, self.mbits)
        return out.reshape(shape)


NO_BFP = BFPPolicy(enabled=False)


# --------------------------------------------------------------------------
# dense / norms / embeddings
# --------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, bias: bool = False,
               scale: float | None = None) -> dict:
    scale = (1.0 / math.sqrt(d_in)) if scale is None else scale
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense(p: dict, x: jax.Array, *, policy: Policy = Policy(),
          bfp: BFPPolicy = NO_BFP) -> jax.Array:
    cd = policy.compute_dtype
    w = bfp.q(p["w"]).astype(cd)
    y = jnp.matmul(bfp.q(x).astype(cd), w)
    if "b" in p:
        y = y + p["b"].astype(cd)
    return y


def rmsnorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dt)


def embed_init(key, vocab: int, d: int, pad_to: int = 1) -> dict:
    vp = ceil_to(vocab, pad_to)
    return {"table": jax.random.normal(key, (vp, d), jnp.float32) * 0.02}


def embed_lookup(p: dict, tokens: jax.Array, policy: Policy = Policy()) -> jax.Array:
    return p["table"].astype(policy.compute_dtype)[tokens]


def unembed_logits(p: dict, x: jax.Array, vocab: int,
                   policy: Policy = Policy(), softcap: float | None = None):
    """Tied unembedding with padded-vocab masking (padded rows → -inf)."""
    logits = jnp.matmul(x.astype(policy.compute_dtype),
                        p["table"].astype(policy.compute_dtype).T)
    logits = logits.astype(jnp.float32)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    vp = p["table"].shape[0]
    if vp != vocab:
        mask = jnp.arange(vp) < vocab
        logits = jnp.where(mask, logits, -1e30)
    return logits


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding, split-half convention. x: [B,S,H,hd], positions [B,S]."""
    hd = x.shape[-1]
    half = hd // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq       # [B,S,half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention cores
# --------------------------------------------------------------------------

def _softcap(scores: jax.Array, cap: float | None) -> jax.Array:
    return cap * jnp.tanh(scores / cap) if cap is not None else scores


def _scaled(scores: jax.Array, head_dim: int, scale: float | None):
    """Scores times ``scale``; None: over sqrt(head_dim)."""
    return scores / math.sqrt(head_dim) if scale is None else scores * scale


def expand_kv(k: jax.Array, g: int) -> jax.Array:
    """GQA expansion [B,S,KV,hd] → [B,S,KV·g,hd].

    Flat-head layout is deliberate: the query-head axis H = KV·g shards over
    the TP axis even when KV < TP (k/v stay replicated at KV heads; each
    shard expands only its own heads).  A nested [KV, g] layout would leave
    GSPMD nothing shardable and it starts splitting head_dim instead.
    """
    return jnp.repeat(k, g, axis=2) if g > 1 else k


def _gqa_scores(q, k):
    """q: [B,Sq,H,hd] k: [B,Skv,H,hd] (expanded) → [B,H,Sq,Skv] (f32).

    Softcapping is applied by callers AFTER the 1/√d scale (gemma2
    semantics: cap·tanh(s/√d/cap))."""
    return jnp.einsum("bqhe,bkhe->bhqk", q.astype(jnp.float32),
                      k.astype(jnp.float32))


def _gqa_out(w, v):
    """w: [B,H,Sq,Skv] v: [B,Skv,H,hd] (expanded) → [B,Sq,H,hd]."""
    return jnp.einsum("bhqk,bkhe->bqhe", w, v.astype(jnp.float32))


def full_attention(q, k, v, *, causal: bool, softcap=None,
                   window: int | None = None, scale: float | None = None):
    """Materialized-scores attention (short sequences).

    q: [B,Sq,H,hd]; k, v: [B,Skv,KV,hd] (expanded internally for GQA).
    Returns [B,Sq,H,hd] in q.dtype.
    """
    b, sq, h, hd = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    k = expand_kv(k, h // nkv)
    v = expand_kv(v, h // nkv)
    scores = _softcap(_scaled(_gqa_scores(q, k), hd, scale), softcap)
    qpos, kpos = jnp.arange(sq), jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(w, v).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool, softcap=None,
                        window: int | None = None,
                        q_chunk: int = 512, kv_chunk: int = 1024,
                        causal_skip: bool = False,
                        scale: float | None = None):
    """Flash-style online-softmax attention via lax.scan over chunks.

    Memory is O(Sq·kv_chunk) instead of O(Sq·Skv).

    ``causal_skip`` (perf knob, §Perf): query chunk i only *executes* kv
    chunks that intersect its mask (via lax.cond), eliminating the ~2×
    masked-FLOP waste of the naive schedule for causal, and the O(S/w)×
    waste for sliding-window masks.  Off by default = the paper-agnostic
    baseline schedule.

    q: [B,Sq,H,hd]; k, v: [B,Skv,KV,hd].  GQA expansion happens *per kv
    chunk inside the loop* — expanding the whole cache up front would
    materialize (and re-slice) an H/KV-times larger buffer (§Perf H3).
    """
    b, sq, h, hd = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    g_rep = h // nkv
    sq_p, skv_p = ceil_to(sq, q_chunk), ceil_to(skv, kv_chunk)
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
    nq, nkv_chunks = sq_p // q_chunk, skv_p // kv_chunk
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    qs = qp.reshape(b, nq, q_chunk, h, hd).swapaxes(0, 1)

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx
        q_pos = iq * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kv_idx):
            acc, m, l = carry
            start = kv_idx * kv_chunk
            kb = expand_kv(
                lax.dynamic_slice_in_dim(kp, start, kv_chunk, axis=1), g_rep)
            vb = expand_kv(
                lax.dynamic_slice_in_dim(vp, start, kv_chunk, axis=1), g_rep)
            s = _softcap(_gqa_scores(qi, kb) * scale, softcap)  # [B,H,qc,kc]
            k_pos = start + jnp.arange(kv_chunk)
            mask = k_pos[None, :] < skv                   # padding
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhe->bhqe", p, vb.astype(jnp.float32))
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, h, q_chunk, hd), jnp.float32)
        m0 = jnp.full((b, h, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        carry0 = (acc0, m0, l0)

        needs_skip = causal_skip and (causal or window is not None)
        if needs_skip:
            # chunk-range bounds that intersect this query chunk's mask
            hi = jnp.minimum(
                (iq * q_chunk + q_chunk + kv_chunk - 1) // kv_chunk, nkv_chunks) \
                if causal else nkv_chunks
            lo = jnp.maximum((iq * q_chunk - window) // kv_chunk, 0) \
                if window is not None else 0

            def guarded(carry, j):
                in_range = jnp.logical_and(j >= lo, j < hi)
                return lax.cond(in_range,
                                lambda c: kv_step(c, j)[0],
                                lambda c: c, carry), None

            (acc, m, l), _ = lax.scan(guarded, carry0, jnp.arange(nkv_chunks))
        else:
            (acc, m, l), _ = lax.scan(kv_step, carry0, jnp.arange(nkv_chunks))
        l = jnp.maximum(l, 1e-30)
        out = (acc / l[..., None])                       # [B,H,qc,hd]
        return None, out.transpose(0, 2, 1, 3)           # [B,qc,H,hd]

    _, outs = lax.scan(q_step, None, (qs, jnp.arange(nq)))
    out = outs.swapaxes(0, 1).reshape(b, sq_p, h, hd)[:, :sq]
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cur_len, *, softcap=None,
                     window: int | None = None, scale: float | None = None):
    """Single-token decode over a [B,Smax,KV,hd] cache. q: [B,1,H,hd].

    The score constraint (§Perf H4) keeps the KV-cache's sequence sharding
    alive through the mask/softmax: without it GSPMD all-gathers the entire
    cache per token (84 GiB/step for gemma2 decode_32k); with it only the
    online-softmax statistics and the [B,1,H,hd] output cross devices.
    """
    from repro.distributed.ctx import constrain
    b, sq, h, hd = q.shape
    smax, nkv = k_cache.shape[1], k_cache.shape[2]
    kc = expand_kv(k_cache, h // nkv)
    vc = expand_kv(v_cache, h // nkv)
    scores = _softcap(_scaled(_gqa_scores(q, kc), hd, scale), softcap)
    scores = constrain(scores, "dec_scores")              # [B,H,1,Smax]
    kpos = jnp.arange(smax)
    mask = kpos < cur_len                                 # [Smax]
    if window is not None:
        mask &= kpos > (cur_len - 1 - window)
    scores = jnp.where(mask, scores, -1e30)
    scores = constrain(scores, "dec_scores")
    w = jax.nn.softmax(scores, axis=-1)
    w = constrain(w, "dec_scores")
    return _gqa_out(w, vc).astype(q.dtype)


# --------------------------------------------------------------------------
# attention layer (proj + rope + core + out-proj), GQA with KV cache
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float | None = 10000.0   # None → no rope (e.g. whisper enc)
    softcap: float | None = None
    window: int | None = None            # sliding window (local attention)
    causal: bool = True
    blockwise_threshold: int = 1024      # switch to online-softmax above this
    q_chunk: int = 512
    kv_chunk: int = 1024
    causal_skip: bool = False            # §Perf: skip fully-masked kv chunks
    scale: float | None = None           # scores' scale; None: 1/sqrt(hd)
    # fused Pallas flash kernel (TPU runtime; interpret=True on CPU tests).
    # Scores/softmax state stay in VMEM — see EXPERIMENTS.md §Perf H3.
    use_flash: bool = False
    flash_interpret: bool = False


def attn_init(key, cfg: AttnConfig) -> dict:
    ks = split_keys(key, ["wq", "wk", "wv", "wo"])
    return {
        "wq": dense_init(ks["wq"], cfg.d_model, cfg.n_heads * cfg.head_dim,
                         cfg.qkv_bias),
        "wk": dense_init(ks["wk"], cfg.d_model, cfg.n_kv * cfg.head_dim,
                         cfg.qkv_bias),
        "wv": dense_init(ks["wv"], cfg.d_model, cfg.n_kv * cfg.head_dim,
                         cfg.qkv_bias),
        "wo": dense_init(ks["wo"], cfg.n_heads * cfg.head_dim, cfg.d_model),
    }


def _project_qkv(p, x, kv_x, cfg: AttnConfig, policy, bfp, positions,
                 kv_positions=None):
    """q: [B,S,H,hd] (flat heads, TP-shardable); k/v: [B,Skv,KV,hd]."""
    from repro.distributed.ctx import constrain
    b, s, _ = x.shape
    q = dense(p["wq"], x, policy=policy, bfp=bfp).reshape(
        b, s, cfg.n_heads, cfg.head_dim)
    skv = kv_x.shape[1]
    k = dense(p["wk"], kv_x, policy=policy, bfp=bfp).reshape(
        b, skv, cfg.n_kv, cfg.head_dim)
    v = dense(p["wv"], kv_x, policy=policy, bfp=bfp).reshape(
        b, skv, cfg.n_kv, cfg.head_dim)
    if cfg.rope_theta is not None and positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        kv_pos = positions if kv_positions is None else kv_positions
        k = rope(k, kv_pos, cfg.rope_theta)
    return constrain(q, "act_q"), constrain(k, "act_kv"), constrain(v, "act_kv")


def attention_layer(p, x, cfg: AttnConfig, *, policy=Policy(), bfp=NO_BFP,
                    kv_x=None, positions=None, kv_positions=None):
    """Full-sequence attention (train / prefill).  kv_x ≠ None → cross-attn."""
    b, s, _ = x.shape
    self_attn = kv_x is None
    kv_x = x if self_attn else kv_x
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(p, x, kv_x, cfg, policy, bfp, positions, kv_positions)
    causal = cfg.causal and self_attn
    if cfg.use_flash and cfg.window is None and cfg.scale is None:
        from repro.kernels.flash_attention import flash_attention
        qc = min(cfg.q_chunk, s)
        kc = min(cfg.kv_chunk, kv_x.shape[1])
        o = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, softcap=cfg.softcap,
            q_chunk=qc, kv_chunk=kc,
            interpret=cfg.flash_interpret).transpose(0, 2, 1, 3)
    elif max(s, kv_x.shape[1]) > cfg.blockwise_threshold:
        o = blockwise_attention(q, k, v, causal=causal, softcap=cfg.softcap,
                                window=cfg.window, q_chunk=cfg.q_chunk,
                                kv_chunk=cfg.kv_chunk,
                                causal_skip=cfg.causal_skip, scale=cfg.scale)
    else:
        o = full_attention(q, k, v, causal=causal, softcap=cfg.softcap,
                           window=cfg.window, scale=cfg.scale)
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return dense(p["wo"], o, policy=policy, bfp=bfp)


def attention_decode(p, x, cache: dict, cfg: AttnConfig, *, policy=Policy()):
    """One-token decode step; cache = {"k","v": [B,Smax,KV,hd], "len": int32}."""
    b, s, _ = x.shape
    assert s == 1, "decode step processes one token"
    cur = cache["len"]
    positions = jnp.full((b, 1), cur, dtype=jnp.int32)
    q, k, v = _project_qkv(p, x, x, cfg, policy, NO_BFP, positions)
    with jax.named_scope("kv_cache"):
        k_cache = lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), cur, axis=1)
        v_cache = lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), cur, axis=1)
    o = decode_attention(q, k_cache, v_cache, cur + 1, softcap=cfg.softcap,
                         window=cfg.window, scale=cfg.scale)
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    out = dense(p["wo"], o, policy=policy)
    new_cache = {"k": k_cache, "v": v_cache, "len": cur + 1}
    return out, new_cache


def attn_cache_init(cfg: AttnConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16) -> dict:
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv, cfg.head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, gated: bool = True) -> dict:
    ks = split_keys(key, ["wi", "wg", "wo"])
    p = {"wi": dense_init(ks["wi"], d_model, d_ff),
         "wo": dense_init(ks["wo"], d_ff, d_model)}
    if gated:
        p["wg"] = dense_init(ks["wg"], d_model, d_ff)
    return p


def mlp(p: dict, x: jax.Array, *, policy=Policy(), bfp=NO_BFP,
        act=jax.nn.silu) -> jax.Array:
    h = dense(p["wi"], x, policy=policy, bfp=bfp)
    if "wg" in p:
        h = act(dense(p["wg"], x, policy=policy, bfp=bfp)) * h
    else:
        h = act(h)
    return dense(p["wo"], h, policy=policy, bfp=bfp)
