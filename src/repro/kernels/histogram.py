"""Per-channel symbol histogram Pallas kernel — codec table stage.

The static rANS backend (repro/codec) needs per-channel symbol counts of the
quantized BaF residual tensor before the host-side coding pass. The codes
are already on device (the quantize kernel produced them), so the histogram
should be too: one pass over the codes in VMEM instead of a host bincount
over a device->host copy.

Grid ``(C blocks, R blocks)``; the R axis revisits the same output block and
accumulates, so arbitrarily long code streams stream through a fixed VMEM
footprint. Counts are computed as a broadcast compare-and-sum against a
symbol iota — elementwise VPU work, no MXU. The symbols go eight at a time
(one sublane tile of the (nsym, BC) output), so the compare intermediate is
(8, BR, BC) whatever the alphabet size; a channel block is the whole C when
C <= 128 and 128 lanes otherwise, as the TPU tiling rule requires.

Defaults to interpret mode on CPU like the other kernels in this package;
numerics are integer-exact either way (validated against ``np.bincount`` in
tests/test_rans.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from repro.kernels.compat import pl

_SYM_GROUP = 8                  # symbols per step: one (8, BC) output tile
_LANES = 128
_BLOCK_R = 512


def _hist_kernel(x_ref, counts_ref, *, nsym: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    g = min(nsym, _SYM_GROUP)

    def group(i, carry):
        s0 = pl.multiple_of(i * g, g)
        sym = s0 + jax.lax.broadcasted_iota(jnp.int32, (g, 1, 1), 0)
        eq = (x_ref[...][None, :, :] == sym).astype(jnp.int32)  # (g, BR, BC)
        counts_ref[pl.ds(s0, g), :] += jnp.sum(eq, axis=1)
        return carry

    jax.lax.fori_loop(0, nsym // g, group, 0)


@functools.lru_cache(maxsize=64)
def _jitted_hist(nsym: int, br: int, bc: int, rp: int, cp: int,
                 interpret: bool):
    call = pl.pallas_call(
        functools.partial(_hist_kernel, nsym=nsym),
        grid=(cp // bc, rp // br),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (j, i))],
        out_specs=pl.BlockSpec((nsym, bc), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((nsym, cp), jnp.int32),
        interpret=interpret,
    )

    def histogram_kernel(codes):
        return call(codes)
    # a named function: its program reads ``jit_histogram_kernel`` in a
    # profile, where a bare pallas_call would read ``jit_wrapped``
    return jax.jit(histogram_kernel)


def histogram_pallas(codes: jax.Array, nsym: int, *,
                     interpret: bool | None = None) -> jax.Array:
    """codes: (R, C) integer array -> counts (nsym, C) int32.

    ``nsym`` is a power of two (an alphabet of ``2**bits`` symbols).
    Out-of-range values (negative or >= nsym) are counted nowhere — callers
    use ``nsym`` itself as the padding sentinel. The pallas_call is jitted
    and cached per shape, so the serving hot path (same tile shape per
    bucket) traces once.
    """
    if nsym < 2 or nsym & (nsym - 1):
        raise ValueError(f"nsym must be a power of two >= 2, got {nsym}")
    r, c = codes.shape
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bc = c if c <= _LANES else _LANES
    br = min(_BLOCK_R, -(-r // 8) * 8)
    pad_r = (-r) % br
    pad_c = (-c) % bc
    codes = codes.astype(jnp.int32)
    if pad_r or pad_c:
        codes = jnp.pad(codes, ((0, pad_r), (0, pad_c)), constant_values=nsym)
    counts = _jitted_hist(nsym, br, bc, r + pad_r, c + pad_c, interpret)(codes)
    return counts[:, :c]


def channel_histogram(codes, bits: int, *,
                      interpret: bool | None = None) -> np.ndarray:
    """Per-channel symbol counts of a channel-last code tensor, on device.

    codes: (..., C) integers in [0, 2^bits) -> counts (C, S) as a host numpy
    array, ready for table normalization (repro.codec.rans.normalize_freqs
    runs host-side; the heavy O(R·C·S) reduction stays on device).
    """
    nsym = 1 << bits
    arr = np.asarray(codes)
    if arr.ndim == 0:
        arr = arr.reshape(1, 1)
    c = arr.shape[-1]               # channel-last, matching repro.codec
    flat = arr.reshape(-1, c) if c else arr.reshape(-1, 1)
    if flat.size == 0 or c == 0:
        return np.zeros((c, nsym), np.int64)
    counts = histogram_pallas(jnp.asarray(flat, jnp.int32), nsym,
                              interpret=interpret)
    return np.asarray(counts).T.astype(np.int64)
