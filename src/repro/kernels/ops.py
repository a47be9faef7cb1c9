"""Jit'd public wrappers around the Pallas kernels.

``bfp_dense`` is the training-facing op: a linear layer whose forward AND
backward matmuls run the BFP kernel.  The backward pass consumes transposed
operands (Table I: ∇A = ∇O·Wᵀ, ∇W = Aᵀ·∇O) — with *square* 2D BFP groups the
transposed quantization is exactly the transpose of the forward quantization
(Q(Wᵀ)=Q(W)ᵀ), so no re-quantization semantics change between passes; this is
the paper's §III-E property realized end-to-end.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.bfp_matmul import bfp_matmul
from repro.kernels.bfp_quant import bfp_matmul_packed, bfp_quantize_pallas


@dataclasses.dataclass(frozen=True)
class BFPKernelConfig:
    group: int = 32
    mbits: int = 5
    ebits: int = 4
    block_m: int = 256
    block_n: int = 256
    block_k: int = 256
    # the Pallas interpreter (CPU validation); never chosen implicitly
    interpret: bool = False


def matmul(a: jax.Array, b: jax.Array, cfg: BFPKernelConfig = BFPKernelConfig()):
    return bfp_matmul(
        a, b, group=cfg.group, mbits=cfg.mbits, ebits=cfg.ebits,
        block_m=cfg.block_m, block_n=cfg.block_n, block_k=cfg.block_k,
        interpret=cfg.interpret)


def quantize(x: jax.Array, cfg: BFPKernelConfig = BFPKernelConfig()):
    return bfp_quantize_pallas(
        x, group=cfg.group, mbits=cfg.mbits, ebits=cfg.ebits,
        block_m=cfg.block_m, block_n=cfg.block_n, interpret=cfg.interpret)


def matmul_packed(a_mant, a_exp, b_mant, b_exp,
                  cfg: BFPKernelConfig = BFPKernelConfig()):
    return bfp_matmul_packed(
        a_mant, a_exp, b_mant, b_exp, group=cfg.group, mbits=cfg.mbits,
        block_m=cfg.block_m, block_n=cfg.block_n, block_k=cfg.block_k,
        interpret=cfg.interpret)


# --------------------------------------------------------------------------
# bfp_dense: linear layer with BFP forward and BFP backward (Table I).
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def bfp_dense(x: jax.Array, w: jax.Array, cfg: BFPKernelConfig) -> jax.Array:
    """``x @ w`` with both operands 2D-BFP quantized, kernel-backed.

    x: (..., K), w: (K, N) → (..., N).
    """
    return _bfp_dense_fwd(x, w, cfg)[0]


def _flatten_lead(x):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _bfp_dense_fwd(x, w, cfg):
    x2, lead = _flatten_lead(x)
    y = matmul(x2, w, cfg)
    return y.reshape(*lead, w.shape[-1]).astype(x.dtype), (x, w)


def _bfp_dense_bwd(cfg, res, g):
    x, w = res
    x2, lead = _flatten_lead(x)
    g2, _ = _flatten_lead(g)
    # ∇A = ∇O · Wᵀ ;  ∇W = Aᵀ · ∇O  — both through the BFP kernel, with the
    # transposed operand quantization inherited via square-group invariance.
    dx = matmul(g2.astype(jnp.float32), w.astype(jnp.float32).T, cfg)
    dw = matmul(x2.astype(jnp.float32).T, g2.astype(jnp.float32), cfg)
    return dx.reshape(x.shape).astype(x.dtype), dw.astype(w.dtype)


bfp_dense.defvjp(_bfp_dense_fwd, _bfp_dense_bwd)
