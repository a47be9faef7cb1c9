"""Pallas TPU kernels: standalone 2D-BFP (de)quantization.

These are the storage-path kernels: activations/gradients written to HBM in
packed BFP (int8 mantissas + per-group int8 exponents ≈ 8.25 bits/value vs
16 for bf16) — the TPU analogue of CAMEL's eDRAM density win (≥2× capacity,
§II-E), halving HBM traffic for every tensor that round-trips memory.

The packed matmul kernel consumes the quantized representation directly, so
the dequantized f32 tile exists only in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bfp_common import dequant_block, quant_block


def _quant_kernel(x_ref, mant_ref, exp_ref, *, g, mbits, ebits):
    mant, exp = quant_block(x_ref[...], g, mbits, ebits)
    mant_ref[...] = mant
    exp_ref[...] = exp.astype(exp_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group", "mbits", "ebits", "block_m", "block_n", "interpret"),
)
def bfp_quantize_pallas(
    x: jax.Array,
    *,
    group: int = 32,
    mbits: int = 5,
    ebits: int = 4,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
):
    """Quantize a 2D f32 array → (mant int8, exp int8) in packed layout."""
    if x.ndim != 2:
        raise ValueError(f"expected 2D input, got {x.shape}")
    m, n = x.shape
    bm, bn = min(block_m, _ceil(m, group)), min(block_n, _ceil(n, group))
    mp, np_ = _ceil(m, bm), _ceil(n, bn)
    x = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, np_ - n)))

    # exponents leave the kernel in row layout, one (bm/g, bn) slab per
    # block row: each block then spans the slab's full second-minor dim, as
    # the (8,128) tiling rule requires, whatever bm/g is
    mant, exp_rows = pl.pallas_call(
        functools.partial(_quant_kernel, g=group, mbits=mbits, ebits=ebits),
        grid=(mp // bm, np_ // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((None, bm // group, bn), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), jnp.int8),
            jax.ShapeDtypeStruct((mp // bm, bm // group, np_), jnp.int8),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x)
    return mant, exp_rows.reshape(mp // group, np_)[:, ::group]


def _packed_matmul_kernel(am_ref, ae_ref, bm_ref, be_ref, o_ref, acc_ref,
                          *, g, mbits):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = dequant_block(am_ref[...], ae_ref[...], g, mbits)
    b = dequant_block(bm_ref[...], be_ref[...], g, mbits)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _drain():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group", "mbits", "block_m", "block_n", "block_k",
                     "interpret", "out_dtype"),
)
def bfp_matmul_packed(
    a_mant: jax.Array, a_exp: jax.Array,
    b_mant: jax.Array, b_exp: jax.Array,
    *,
    group: int = 32,
    mbits: int = 5,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Matmul on pre-quantized packed operands (mant/exp from the quantizer).

    HBM reads are ~2× lighter than bf16; the dequantized tiles live only in
    VMEM — this is the eDRAM-as-activation-store dataflow of CAMEL mapped to
    the TPU memory hierarchy.
    """
    (m, k), (k2, n) = a_mant.shape, b_mant.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a_mant.shape} @ {b_mant.shape}")
    if m % group or k % group or n % group:
        raise ValueError("packed operands must already be group-padded")
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"dims {(m, k, n)} must tile by blocks {(bm, bk, bn)}")

    # packed (R/g, C/g) exponents → row layout (R/bR, bR/g, C): see bfp_common
    def rows(exp, br):
        r, c = exp.shape
        return jnp.repeat(exp, group, axis=1).reshape(r * group // br,
                                                      br // group, c * group)

    grid = (m // bm, n // bn, k // bk)
    out = pl.pallas_call(
        functools.partial(_packed_matmul_kernel, g=group, mbits=mbits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((None, bm // group, bk), lambda i, j, kk: (i, 0, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((None, bk // group, bn), lambda i, j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_mant, rows(a_exp, bm), b_mant, rows(b_exp, bk))
    return out


def _ceil(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
