"""Pallas TPU kernel: fused causal flash attention with GQA.

The §Perf forensics (EXPERIMENTS.md H3) show the XLA-level blockwise
attention materializes every [qc, kc] score block + f32 accumulator to HBM —
~2.7 TB/device for starcoder2 prefill_32k.  This kernel keeps scores, the
online-softmax state (m, l), and the output accumulator in VMEM scratch;
only q/k/v/o stream HBM.

Grid: (B, H, nq, nk) with the kv dimension innermost+sequential (the same
accumulation-stationary pattern as the BFP matmul kernel).  GQA is handled
by the k/v BlockSpec index maps (kv head = h // group), so the expanded KV
never exists in memory.  Causal skipping is structural: fully-masked kv
blocks execute nothing.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  q_chunk, kv_chunk, softcap, causal, scale):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * q_chunk
    k_start = ik * kv_chunk
    # causal structural skip: block computes only if any (q >= k) pair exists
    live = jnp.logical_or(not causal,
                          q_start + q_chunk - 1 >= k_start)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [qc, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [kc, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (q_chunk, kv_chunk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (q_chunk, kv_chunk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "softcap", "q_chunk", "kv_chunk", "interpret"))
def flash_attention(
    q: jax.Array,            # [B, H, Sq, d]
    k: jax.Array,            # [B, KV, Skv, d]
    v: jax.Array,            # [B, KV, Skv, d]
    *,
    causal: bool = True,
    softcap: float | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused flash attention; returns [B, H, Sq, d] in q.dtype."""
    b, h, sq, d = q.shape
    _, nkv, skv, _ = k.shape
    if h % nkv:
        raise ValueError(f"{h} query heads not a multiple of {nkv} kv heads")
    g = h // nkv
    if sq % q_chunk or skv % kv_chunk:
        raise ValueError(f"seq lens {(sq, skv)} must tile by chunks "
                         f"{(q_chunk, kv_chunk)}")
    grid = (b, h, sq // q_chunk, skv // kv_chunk)
    scale = 1.0 / math.sqrt(d)

    return pl.pallas_call(
        functools.partial(_flash_kernel, q_chunk=q_chunk, kv_chunk=kv_chunk,
                          softcap=softcap, causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_chunk, d),
                         lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
            pl.BlockSpec((1, 1, kv_chunk, d),
                         lambda bb, hh, qq, kk, g=g: (bb, hh // g, kk, 0)),
            pl.BlockSpec((1, 1, kv_chunk, d),
                         lambda bb, hh, qq, kk, g=g: (bb, hh // g, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_chunk, d),
                               lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_chunk, d), jnp.float32),
            pltpu.VMEM((q_chunk,), jnp.float32),
            pltpu.VMEM((q_chunk,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
