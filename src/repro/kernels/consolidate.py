"""Fused consolidation Pallas kernel — paper eq. (6).

For each transmitted channel element, the BaF estimate Z̃ is kept when it lies
inside the quantizer bin the decoder received, and clamped to the nearest bin
boundary otherwise — exactly ``clip(Z̃, bin_lo, bin_hi)`` (core/baf.py).

The naive formulation materializes the (lo, hi) bound tensors in HBM; this
kernel reconstructs the bounds from the uint8 codes + side info inside
VMEM and writes only the consolidated output: 3 HBM tensor reads
(z̃, codes, side info) + 1 write instead of 5 reads + 3 writes. Pure
elementwise VPU work, no MXU.

Grid: (B, R // BR), channels kept whole per block (the side info is per
channel, so a (BR, C) block needs exactly one (1, C) side-info row). The
fp16 side info is widened to f32 outside the kernel (Mosaic has no f16
loads on v5e; the widening is exact) and laid out (B, 1, C) so its block's
last two dims equal the array's, as the TPU tiling rule requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.kernels.compat import pl


def _consolidate_kernel(z_ref, codes_ref, mins_ref, maxs_ref, out_ref,
                        *, levels: int):
    z = z_ref[0].astype(jnp.float32)                    # (BR, C)
    # Mosaic casts uint8 only to integers; int32 -> f32 is exact
    c = codes_ref[0].astype(jnp.int32).astype(jnp.float32)
    m = mins_ref[0]                                     # (1, C) f32
    step = (maxs_ref[0] - m) / levels
    lo = m + (c - 0.5) * step
    hi = m + (c + 0.5) * step
    out_ref[0] = jnp.clip(z, lo, hi)


_BLOCK_R = 512


def _row_block(r: int) -> int:
    """Largest multiple of 32 that divides ``r`` and is at most 512 rows;
    ``r`` itself when there is none (a whole-extent block is always legal).

    32 rows is the uint8 tile height on TPU, so every candidate block is
    tile-aligned for the code tensor as well as the f32 ones.
    """
    for br in range(min(_BLOCK_R, r) // 32 * 32, 0, -32):
        if r % br == 0:
            return br
    return r


def consolidate_pallas(z_tilde: jax.Array, codes: jax.Array, mins: jax.Array,
                       maxs: jax.Array, bits: int, *,
                       interpret: bool | None = None) -> jax.Array:
    """z_tilde/codes: (B, R, C); mins/maxs: (B, C) f16 -> (B, R, C) f32."""
    b, r, c = z_tilde.shape
    br = _row_block(r)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    levels = (1 << bits) - 1
    side = (b, 1, c)

    grid = (b, r // br)
    return pl.pallas_call(
        functools.partial(_consolidate_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, br, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, br, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, br, c), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, c), jnp.float32),
        interpret=interpret,
    )(z_tilde, codes, mins.astype(jnp.float32).reshape(side),
      maxs.astype(jnp.float32).reshape(side))
