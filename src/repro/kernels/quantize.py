"""Fused per-channel min/max + quantize Pallas kernel — paper eq. (4).

The naive pipeline reads the activation tensor from HBM twice: once to reduce
per-channel (min, max), once to apply the affine quantization. This kernel
holds one (example, channel-block) column — the full spatial/sequence extent
of a block of channels — resident in VMEM, computes the per-channel stats and
the uint8 codes in a single pass, and emits the fp16 side info the paper
transmits (C·32 bits).

Roofline: the op is purely bandwidth-bound (2 flops/byte); fusing halves HBM
traffic, so the kernel sits at the memory roofline by construction. Block
sizing: (R, BC) with R = spatial extent (e.g. 64·64 = 4096 for the paper's
split tensor) and BC channels such that R·BC·4 B ≲ 4 MiB of VMEM — BC = 128
covers the paper's tensor at 2 MiB/block with lane-aligned (·, 128) tiles.

Mosaic on v5e has no f16 vector casts, so the kernel rounds the side info to
fp16 values with integer arithmetic on the f32 bit pattern (bit-identical
to ``astype(float16)`` and ``nextafter`` in core/quant.py) and stores them as
f32; the wrapper's cast to f16 is exact. Side info is laid out (B, 1, C) so
its block's last two dims are (1, BC), legal under the TPU tiling rule.

Grid: (B, C // BC); every grid step is independent ("parallel" semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.kernels.compat import pl

_F16_MAX = 65504.0
_F16_MIN_NORMAL = 2.0 ** -14
_F16_TINY = 2.0 ** -24          # smallest fp16 subnormal = subnormal spacing
_F16_ULP_BITS = 1 << 13         # one fp16 mantissa step in an f32 bit pattern


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _from_bits(b):
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _round_f16(x):
    """f32 -> nearest fp16 value (ties to even), still as f32; ±inf past the
    fp16 range, like ``x.astype(float16)``."""
    b = _bits(x)
    normal = _from_bits((b + (_F16_ULP_BITS // 2 - 1) + ((b >> 13) & 1))
                        & ~(_F16_ULP_BITS - 1))
    sub = jnp.round(x * (1.0 / _F16_TINY)) * _F16_TINY
    r = jnp.where(jnp.abs(x) < _F16_MIN_NORMAL, sub, normal)
    return jnp.where(jnp.abs(r) > _F16_MAX, jnp.where(x > 0, jnp.inf, -jnp.inf),
                     r)


def _next_f16_up(v):
    """``nextafter(v, +inf)`` in fp16 for an fp16 value ``v`` held as f32."""
    step = jnp.where(v >= 0, _F16_ULP_BITS, -_F16_ULP_BITS)
    up = _from_bits(_bits(v) + step)
    tiny = (v >= -_F16_MIN_NORMAL) & (v < _F16_MIN_NORMAL)
    up = jnp.where(tiny, v + _F16_TINY, up)
    up = jnp.where(v == -jnp.inf, -_F16_MAX, up)
    return jnp.where((v == jnp.inf) | (up > _F16_MAX), jnp.inf, up)


def _quantize_kernel(x_ref, codes_ref, mins_ref, maxs_ref, *, levels: int):
    x = x_ref[0].astype(jnp.float32)                    # (R, BC) one VMEM block
    mn = jnp.min(x, axis=0, keepdims=True)              # (1, BC)
    mx = jnp.max(x, axis=0, keepdims=True)
    # paper §3.2: side info is fp16; widen the max to the next representable
    # so fp16 rounding can never push a data point above the top code, but
    # saturate at finite fp16 — an inf bound zeroes every code and restores NaN.
    mn16 = jnp.maximum(_round_f16(mn), -_F16_MAX)
    mx16 = _round_f16(mx)
    mx16 = jnp.minimum(jnp.maximum(mx16, _next_f16_up(mx16)), _F16_MAX)
    rng = jnp.maximum(mx16 - mn16, 1e-12)
    scaled = (x - mn16) / rng * levels
    codes = jnp.clip(jnp.round(scaled), 0, levels)
    codes_ref[0] = codes.astype(jnp.int32).astype(jnp.uint8)
    mins_ref[0] = mn16
    maxs_ref[0] = mx16


def quantize_pallas(x: jax.Array, bits: int, *, block_c: int = 128,
                    interpret: bool | None = None):
    """x: (B, R, C) channel-last -> (codes uint8, mins f16 (B,C), maxs f16 (B,C)).

    One (min,max) pair per (example, channel) — the paper's per-transmission
    side info. R·block_c·4B must fit the VMEM budget (~4 MiB/block).
    """
    if bits > 8:
        raise ValueError("uint8 code path; higher depths use the jnp "
                         "reference (core/quant.py)")
    b, r, c = x.shape
    bc = min(block_c, c)
    if c % bc or (bc != c and bc % 128):
        raise ValueError(f"block_c={bc} must equal C={c} or be a multiple "
                         f"of 128 that divides it")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    levels = (1 << bits) - 1

    grid = (b, c // bc)
    codes, mins, maxs = pl.pallas_call(
        functools.partial(_quantize_kernel, levels=levels),
        grid=grid,
        in_specs=[pl.BlockSpec((1, r, bc), lambda i, j: (i, 0, j))],
        out_specs=[
            pl.BlockSpec((1, r, bc), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, bc), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, bc), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, r, c), jnp.uint8),
            jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return (codes, mins.reshape(b, c).astype(jnp.float16),
            maxs.reshape(b, c).astype(jnp.float16))
