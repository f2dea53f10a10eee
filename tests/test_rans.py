"""Entropy-coding subsystem: rANS core, context model, container, backends.

Round-trip properties run under hypothesis when installed (via the
hypothesis_compat shim) and as seeded spot checks otherwise.
"""
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.codec import (CorruptStream, RansContainer, RansTable,
                         decode_channels, decode_ctx, decode_tensor,
                         encode_adaptive_tensor, encode_ctx,
                         encode_static_tensor, normalize_freqs, plan_lanes,
                         rans_decode)
from repro.codec.rans import RANS_L, encode_static
from repro.core import codec as wire
from repro.core.quant import QuantParams


def _qp(c, bits, rng):
    mins = rng.normal(size=(c,)).astype(np.float16)
    return QuantParams(mins=mins, maxs=(mins + 1).astype(np.float16),
                       bits=bits)


def _smooth_residuals(rng, shape, bits, rho=0.9):
    """2D spatially correlated quantized field — synthetic BaF residual.

    shape is (B, H, W, C); correlation runs along H (the up-neighbor the
    rans-ctx model keys on) and W.
    """
    z = rng.normal(size=shape)
    s = np.sqrt(1 - rho**2)
    for i in range(1, shape[1]):
        z[:, i] = rho * z[:, i - 1] + s * z[:, i]
    for j in range(1, shape[2]):
        z[:, :, j] = rho * z[:, :, j - 1] + s * z[:, :, j]
    lo = z.min(axis=tuple(range(z.ndim - 1)), keepdims=True)
    hi = z.max(axis=tuple(range(z.ndim - 1)), keepdims=True)
    q = np.round((z - lo) / np.maximum(hi - lo, 1e-9) * ((1 << bits) - 1))
    return np.clip(q, 0, (1 << bits) - 1).astype(np.uint32)


# ---------------------------------------------------------------------------
# normalize_freqs
# ---------------------------------------------------------------------------

@given(n=st.integers(1, 300), prob_bits=st.integers(9, 14),
       seed=st.integers(0, 2**16))
@settings(max_examples=50, deadline=None)
def test_property_normalize_freqs_exact_sum_min_one(n, prob_bits, seed):
    r = np.random.default_rng(seed)
    # arbitrary code distribution, including many zero-count symbols
    counts = (r.integers(0, 50, size=n)
              * (r.random(n) < 0.4)).astype(np.int64)
    f = normalize_freqs(counts, prob_bits)
    assert int(f.sum()) == 1 << prob_bits
    assert int(f.min()) >= 1


def test_normalize_freqs_all_zero_counts():
    f = normalize_freqs(np.zeros(16, np.int64), 12)
    assert int(f.sum()) == 4096 and int(f.min()) >= 1


def test_normalize_freqs_rejects_oversized_alphabet():
    with pytest.raises(ValueError, match="does not fit"):
        normalize_freqs(np.ones(1 << 13), 12)


# ---------------------------------------------------------------------------
# core coder round-trips
# ---------------------------------------------------------------------------

@given(bits=st.integers(1, 12), n=st.integers(0, 600),
       lanes=st.integers(1, 32), alpha=st.floats(0.05, 5.0),
       seed=st.integers(0, 2**16))
@settings(max_examples=60, deadline=None)
def test_property_static_roundtrip_arbitrary_distributions(bits, n, lanes,
                                                           alpha, seed):
    r = np.random.default_rng(seed)
    nsym = 1 << bits
    p = r.dirichlet(np.full(nsym, alpha))        # arbitrary code distribution
    syms = r.choice(nsym, size=n, p=p).astype(np.uint32)
    table = RansTable.from_counts(np.bincount(syms, minlength=nsym),
                                  max(12, bits + 2))
    states, words = encode_static(syms, table, lanes)
    dec = rans_decode(states, words, n, table, lanes)
    assert np.array_equal(dec, syms)


@given(bits=st.integers(1, 12), h=st.integers(1, 24), w=st.integers(1, 24),
       seed=st.integers(0, 2**16))
@settings(max_examples=40, deadline=None)
def test_property_ctx_roundtrip(bits, h, w, seed):
    r = np.random.default_rng(seed)
    syms = r.integers(0, 1 << bits, size=h * w).astype(np.uint32)
    lanes = plan_lanes(syms.size, w)
    states, words = encode_ctx(syms, bits, lanes, w)
    dec = decode_ctx(states, words, syms.size, bits, lanes, w)
    assert np.array_equal(dec, syms)


@pytest.mark.parametrize("encode_fn", [encode_static_tensor,
                                       encode_adaptive_tensor])
@pytest.mark.parametrize("shape", [(1, 1), (1,), (3, 1, 1), (2, 5, 3, 4),
                                   (0, 4), (4, 0), (6, 6, 8)])
def test_tensor_roundtrip_edge_shapes(rng, encode_fn, shape):
    codes = rng.integers(0, 32, size=shape).astype(np.uint32)
    blob = encode_fn(codes, 5)
    assert np.array_equal(decode_tensor(blob, shape, 5), codes)


@pytest.mark.parametrize("bits", [1, 2, 3, 5, 7, 9, 11, 12])
def test_odd_bit_widths_both_modes(rng, bits):
    codes = rng.integers(0, 1 << bits, size=(2, 7, 5, 3)).astype(np.uint32)
    for fn in (encode_static_tensor, encode_adaptive_tensor):
        assert np.array_equal(
            decode_tensor(fn(codes, bits), codes.shape, bits), codes)


def test_rans_rejects_out_of_range_codes(rng):
    with pytest.raises(ValueError, match="does not fit"):
        encode_static_tensor(np.full((4, 4), 300), 8)
    with pytest.raises(ValueError, match="negative"):
        encode_adaptive_tensor(np.full((4, 4), -1), 8)
    with pytest.raises(ValueError, match="1..12"):
        encode_static_tensor(np.zeros((4, 4), np.uint32), 16)


# ---------------------------------------------------------------------------
# container: partial decode + corruption
# ---------------------------------------------------------------------------

def test_partial_decode_matches_full(rng):
    codes = rng.integers(0, 256, size=(2, 8, 8, 6)).astype(np.uint32)
    for fn in (encode_static_tensor, encode_adaptive_tensor):
        blob = fn(codes, 8)
        full = decode_tensor(blob, codes.shape, 8)
        part = decode_channels(blob, [5, 0, 2])
        for row, ch in zip(part, [5, 0, 2]):
            assert np.array_equal(row, full[..., ch].reshape(-1))


def test_partial_decode_skips_corrupt_other_chunks(rng):
    """Corruption in chunk j must not prevent decoding chunk i != j."""
    codes = rng.integers(0, 64, size=(1, 16, 16, 4)).astype(np.uint32)
    blob = bytearray(encode_adaptive_tensor(codes, 6))
    blob[-3] ^= 0x55                       # flip bits inside the LAST chunk
    got = decode_channels(bytes(blob), [0])
    assert np.array_equal(got[0], codes[..., 0].reshape(-1))
    with pytest.raises(CorruptStream):
        decode_channels(bytes(blob), [3])


@pytest.mark.parametrize("mutate,msg", [
    (lambda b: b"XXXX" + b[4:], "bad container magic"),
    (lambda b: b[:1], "truncated container header"),
    (lambda b: b[:4] + bytes([9]) + b[5:], "unsupported container version"),
    (lambda b: b[:5] + bytes([7]) + b[6:], "header CRC mismatch"),
    (lambda b: b + b"zz", "trailing garbage"),
    (lambda b: b[:-5], "truncated chunk"),
])
def test_container_corruption_distinct_errors(rng, mutate, msg):
    codes = rng.integers(0, 16, size=(4, 4, 2)).astype(np.uint32)
    blob = encode_static_tensor(codes, 4)
    with pytest.raises(CorruptStream, match=msg):
        RansContainer.parse(mutate(blob)).decode_all()


def test_container_rejects_unknown_mode_with_valid_crc():
    import struct
    import zlib as _z

    from repro.codec import container as box
    hdr = box._HEADER.pack(box.MAGIC, box.VERSION, 7, 4, 12, 1, 0, 0, 0)
    blob = hdr + struct.pack("<I", _z.crc32(hdr))
    with pytest.raises(CorruptStream, match="unknown container mode"):
        RansContainer.parse(blob)


def test_decode_tensor_shape_bits_crosschecks(rng):
    codes = rng.integers(0, 16, size=(4, 4, 2)).astype(np.uint32)
    blob = encode_static_tensor(codes, 4)
    with pytest.raises(CorruptStream, match="wire header says"):
        decode_tensor(blob, codes.shape, 6)
    with pytest.raises(CorruptStream, match="tile chunks"):
        decode_tensor(blob, (4, 4, 3), 4)
    with pytest.raises(CorruptStream, match="symbols"):
        decode_tensor(blob, (2, 4, 2), 4)


@given(seed=st.integers(0, 2**12))
@settings(max_examples=25, deadline=None)
def test_property_bit_flips_never_serve_wrong_data(seed):
    """Defense in depth (header CRC, table adler32, per-chunk CRC, lane-state
    check): any single-bit flip in a container either raises CorruptStream
    or decodes to exactly the original codes (flips in semantically-neutral
    zlib metadata bits of the table blob) — wrong tensors are never served."""
    r = np.random.default_rng(seed)
    codes = r.integers(0, 256, size=(1, 8, 8, 3)).astype(np.uint32)
    fn = encode_static_tensor if seed % 2 else encode_adaptive_tensor
    blob = bytearray(fn(codes, 8))
    pos = int(r.integers(0, len(blob)))
    blob[pos] ^= 1 << int(r.integers(0, 8))
    try:
        out = decode_tensor(bytes(blob), codes.shape, 8)
    except CorruptStream:
        return
    assert np.array_equal(out, codes)


# ---------------------------------------------------------------------------
# wire-codec integration (core/codec.py registry)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["rans", "rans-ctx"])
@pytest.mark.parametrize("bits", [2, 3, 5, 8])
def test_wire_roundtrip_all_c_bits(rng, backend, bits):
    for c in (1, 4, 8):
        codes = rng.integers(0, 1 << bits, size=(2, 6, 6, c)).astype(np.uint8)
        qp = _qp(c, bits, rng)
        enc = wire.encode(codes, qp, backend=backend)
        dec, dec_qp = wire.decode(
            wire.EncodedTensor.from_bytes(enc.to_bytes()))
        assert np.array_equal(dec, codes)
        assert dec_qp.bits == bits


def test_wire_bits_counts_whole_container(rng):
    codes = rng.integers(0, 256, size=(1, 4, 4, 4)).astype(np.uint8)
    qp = _qp(4, 8, rng)
    for backend in ("raw", "zlib", "rans", "rans-ctx"):
        enc = wire.encode(codes, qp, backend=backend)
        assert enc.wire_bits() == 8 * len(enc.to_bytes())
        assert enc.total_bits() == enc.wire_bits() - 8 * enc.header_bytes()


def test_ctx_beats_order0_floor_on_baf_residuals(rng):
    """Acceptance: rans-ctx within 5% of the empirical-entropy floor on
    synthetic BaF residuals (it lands well below by using 2D context)."""
    codes = _smooth_residuals(rng, (2, 48, 48, 8), bits=6)
    qp = _qp(8, 6, rng)
    enc = wire.encode(codes, qp, backend="rans-ctx")
    floor = wire.empirical_entropy_bits(codes, 6)
    assert 8 * len(enc.payload) <= 1.05 * floor


def test_static_close_to_floor_on_skewed_stream(rng):
    """Static tables on an iid skewed stream sit near the order-0 entropy."""
    p = np.asarray([0.6, 0.2, 0.1, 0.05, 0.02, 0.01, 0.01, 0.01])
    codes = rng.choice(8, size=(1, 64, 64, 4), p=p).astype(np.uint32)
    qp = _qp(4, 3, rng)
    enc = wire.encode(codes, qp, backend="rans")
    floor = wire.empirical_entropy_bits(codes, 3)
    assert 8 * len(enc.payload) <= 1.10 * floor


# ---------------------------------------------------------------------------
# Pallas histogram kernel vs bincount
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,bits", [((4, 16, 16, 8), 8), ((37, 5), 4),
                                        ((1, 1), 1), ((3, 7, 3), 6)])
def test_histogram_kernel_matches_bincount(rng, shape, bits):
    from repro.kernels.histogram import channel_histogram
    codes = rng.integers(0, 1 << bits, size=shape)
    counts = channel_histogram(codes, bits)
    c = shape[-1]
    ref = np.stack([np.bincount(codes.reshape(-1, c)[:, i],
                                minlength=1 << bits) for i in range(c)])
    assert np.array_equal(counts, ref)


def test_histogram_kernel_empty():
    from repro.kernels.histogram import channel_histogram
    counts = channel_histogram(np.empty((0, 4), np.int32), 8)
    assert counts.shape == (4, 256) and not counts.any()
