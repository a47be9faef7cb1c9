"""Shared in-kernel helpers for the BFP Pallas kernels.

Everything here must lower on Mosaic/TPU:

* exponent extraction uses an integer bitcast (`floor(log2|x|)` = biased
  exponent − 127 for normalized floats) instead of `frexp`, which the TPU
  backend does not provide; powers of two are built the same way, so every
  scale is exact;
* a (g×g) group never becomes its own array axis.  Splitting the lane axis
  (`(bm, bn) → (bm/g, g, bn/g, g)`) is a shape cast Mosaic refuses, so the
  row half of a group reduces over a sublane split (`(bm/g, g, bn)`, legal
  for g % 8 == 0) and the column half by a segmented roll-and-max butterfly
  along the lanes.

Exponents therefore live in *row layout*: shape (bm/g, bn), one row per
group row, each lane holding the exponent of the group it falls in.  The
public wrappers convert between that and the packed (M/g, N/g) grid outside
the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

F32_EXP_BIAS = 127


def floor_log2(x: jax.Array) -> jax.Array:
    """floor(log2(x)) for x >= 0 (f32), elementwise; x == 0 → -127."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    e = jnp.right_shift(bits, 23) & 0xFF
    e = e - F32_EXP_BIAS
    return jnp.where(x > 0, e, jnp.full_like(e, -F32_EXP_BIAS))


def pow2(k: jax.Array) -> jax.Array:
    """2**k as f32 for int32 k in the normal range, built from its bits."""
    return jax.lax.bitcast_convert_type(
        jnp.left_shift(k + F32_EXP_BIAS, 23), jnp.float32)


def _lane_group_max(v: jax.Array, g: int) -> jax.Array:
    """Max over aligned runs of ``g`` lanes, broadcast back to every lane.

    Each step takes the value ``s`` lanes away in both directions when that
    lane is in the same group; after steps 1, 2, 4, … g/2 every lane has
    seen its whole group.  The lane index is rolled with the data, so the
    mask does not depend on the rotation direction.
    """
    n = v.shape[-1]
    if n == g:
        return jnp.broadcast_to(jnp.max(v, axis=-1, keepdims=True), v.shape)
    axis = v.ndim - 1
    group = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis) // g
    s = 1
    while s < g:
        for shift in (s, n - s):
            same = pltpu.roll(group, shift, axis) == group
            v = jnp.maximum(v, jnp.where(same, pltpu.roll(v, shift, axis), v))
        s *= 2
    return v


def _expand_rows(e: jax.Array, g: int) -> jax.Array:
    """(bm/g, bn) row-layout exponents → one per element, (bm, bn)."""
    r, bn = e.shape
    return jnp.broadcast_to(e[:, None, :], (r, g, bn)).reshape(r * g, bn)


def group_exponent(x: jax.Array, g: int, ebits: int) -> jax.Array:
    """Shared exponent per (g×g) group of a 2D f32 block, in row layout."""
    bm, bn = x.shape
    amax = jnp.max(jnp.abs(x).reshape(bm // g, g, bn), axis=1)
    e = floor_log2(_lane_group_max(amax, g))
    lo, hi = -(2 ** (ebits - 1)), 2 ** (ebits - 1) - 1
    return jnp.clip(e, lo, hi)


def _quantize(x: jax.Array, g: int, mbits: int, ebits: int):
    """→ (mantissas as f32 (bm, bn), row-layout exponents, per-element
    exponent of the mantissa's unit)."""
    x = x.astype(jnp.float32)
    e = group_exponent(x, g, ebits)
    unit = _expand_rows(e, g) - (mbits - 1)      # scale = 2**unit, exact
    lim = float(2**mbits - 1)
    m = jnp.clip(jnp.round(x * pow2(-unit)), -lim, lim)
    return m, e, unit


def qdq_block(x: jax.Array, g: int, mbits: int, ebits: int) -> jax.Array:
    """Quantize→dequantize a 2D f32 block with square (g×g) BFP groups.

    This is the PE-boundary quantization of the CAMEL systolic array mapped to
    a VMEM-resident tile: operands are quantized as they enter the MXU, so no
    quantized copy ever round-trips HBM.
    """
    m, _, unit = _quantize(x, g, mbits, ebits)
    return m * pow2(unit)


def quant_block(x: jax.Array, g: int, mbits: int, ebits: int):
    """Quantize a 2D block → (mant int8 [bm,bn], exp int32 [bm/g,bn] in row
    layout)."""
    m, e, _ = _quantize(x, g, mbits, ebits)
    return m.astype(jnp.int8), e


def dequant_block(mant: jax.Array, exp: jax.Array, g: int, mbits: int
                  ) -> jax.Array:
    """(mant [bm,bn], row-layout exp [bm/g,bn]) → f32 block."""
    unit = _expand_rows(exp.astype(jnp.int32), g) - (mbits - 1)
    return mant.astype(jnp.float32) * pow2(unit)
