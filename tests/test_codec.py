"""Wire codec: the BaF2 header around rANS containers, paper-style bit
accounting, and the refusal of retired backend ids."""
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import codec as wire
from repro.core.quant import QuantParams


def _qp(c, bits, rng):
    mins = rng.normal(size=(c,)).astype(np.float16)
    return QuantParams(mins=mins, maxs=(mins + 1).astype(np.float16), bits=bits)


@pytest.mark.parametrize("bits", [2, 5, 8])
def test_encode_decode_roundtrip(rng, bits):
    codes = rng.integers(0, 1 << bits, size=(6, 6, 8)).astype(np.uint8)
    qp = _qp(8, bits, rng)
    enc = wire.encode(codes, qp)
    blob = enc.to_bytes()
    dec_codes, dec_qp = wire.decode(wire.EncodedTensor.from_bytes(blob))
    assert np.array_equal(dec_codes, codes)
    assert np.array_equal(dec_qp.mins, np.asarray(qp.mins))
    assert dec_qp.bits == bits


def test_side_info_accounting(rng):
    codes = rng.integers(0, 256, size=(4, 4, 16)).astype(np.uint8)
    qp = _qp(16, 8, rng)
    enc = wire.encode(codes, qp)
    # paper: C*32 bits of fp16 min/max side info + payload
    assert enc.total_bits() == 8 * len(enc.payload) + 16 * 32


def test_entropy_floor_below_payload(rng):
    codes = (rng.random(size=(64, 64)) < 0.1).astype(np.uint8)
    h = wire.empirical_entropy_bits(codes, 8)
    raw_bits = codes.size * 8
    assert 0 < h < raw_bits


@given(seed=st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_property_entropy_is_compression_lower_bound_ish(seed):
    """The rANS payload cannot beat half the order-0 entropy floor on iid
    streams (sanity on the accounting, not a codec guarantee)."""
    r = np.random.default_rng(seed)
    codes = r.integers(0, 4, size=4096).astype(np.uint8)
    qp = QuantParams(mins=np.zeros(1, np.float16), maxs=np.ones(1, np.float16),
                     bits=2)
    enc = wire.encode(codes, qp)
    h = wire.empirical_entropy_bits(codes, 2)
    assert 8 * len(enc.payload) >= 0.5 * h


# ---------------------------------------------------------------------------
# Hardening + header integrity (serving-gateway PR satellites)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [3, 5, 6])
def test_roundtrip_odd_bit_widths(rng, bits):
    codes = rng.integers(0, 1 << bits, size=(5, 7, 4)).astype(np.uint8)
    qp = _qp(4, bits, rng)
    dec, dec_qp = wire.decode(wire.EncodedTensor.from_bytes(
        wire.encode(codes, qp).to_bytes()))
    assert np.array_equal(dec, codes)
    assert dec_qp.bits == bits


def test_roundtrip_single_element(rng):
    codes = np.asarray([[3]], np.uint8)
    qp = _qp(1, 4, rng)
    dec, _ = wire.decode(wire.encode(codes, qp))
    assert dec.shape == (1, 1) and dec[0, 0] == 3


def test_header_integrity_multidim(rng):
    shape = (2, 3, 4, 5)
    codes = rng.integers(0, 64, size=shape).astype(np.uint8)
    qp = _qp(5, 6, rng)
    enc = wire.encode(codes, qp)
    blob = enc.to_bytes()
    assert blob[4] == wire.RANS_WIRE_ID
    enc2 = wire.EncodedTensor.from_bytes(blob)
    assert enc2.shape == shape
    assert enc2.bits == 6
    assert enc2.side_info == enc.side_info
    assert enc2.payload == enc.payload
    dec, _ = wire.decode(enc2)
    assert dec.shape == shape and np.array_equal(dec, codes)


# ---------------------------------------------------------------------------
# from_bytes structural hardening: every malformation fails loudly at the
# header with its own message, instead of surfacing later inside the rANS
# container
# ---------------------------------------------------------------------------

def _blob(rng):
    codes = rng.integers(0, 64, size=(4, 6)).astype(np.uint8)
    return wire.encode(codes, _qp(6, 6, rng)).to_bytes()


def test_from_bytes_rejects_bad_magic(rng):
    blob = _blob(rng)
    with pytest.raises(ValueError, match="bad magic"):
        wire.EncodedTensor.from_bytes(b"NOPE" + blob[4:])


def test_from_bytes_rejects_old_wire_version(rng):
    blob = _blob(rng)
    with pytest.raises(ValueError, match="unsupported wire-format version"):
        wire.EncodedTensor.from_bytes(b"BaF1" + blob[4:])


def test_from_bytes_rejects_truncated_header(rng):
    blob = _blob(rng)
    with pytest.raises(ValueError, match="truncated wire header"):
        wire.EncodedTensor.from_bytes(blob[:5])
    with pytest.raises(ValueError, match="truncated wire header"):
        wire.EncodedTensor.from_bytes(blob[:9])       # mid-shape


def test_from_bytes_rejects_truncated_side_info(rng):
    blob = _blob(rng)
    hdr = 7 + 4 * 2 + 8
    with pytest.raises(ValueError, match="truncated side info"):
        wire.EncodedTensor.from_bytes(blob[:hdr + 3])


def test_from_bytes_rejects_truncated_payload(rng):
    blob = _blob(rng)
    with pytest.raises(ValueError, match="truncated payload"):
        wire.EncodedTensor.from_bytes(blob[:-1])


def test_from_bytes_rejects_trailing_garbage(rng):
    blob = _blob(rng)
    with pytest.raises(ValueError, match="trailing garbage"):
        wire.EncodedTensor.from_bytes(blob + b"\x00")


# 0, 1, 2 and 4 were the retired zlib, png, raw and adaptive-context coders
@pytest.mark.parametrize("backend_id", [250, 0, 1, 2, 4])
def test_from_bytes_rejects_unknown_backend_id(rng, backend_id):
    blob = bytearray(_blob(rng))
    blob[4] = backend_id
    with pytest.raises(ValueError, match="unknown backend id"):
        wire.EncodedTensor.from_bytes(bytes(blob))

