"""Unified pipeline API: operating points, plan compilation, batched decode
round-trips (property-based), capability negotiation, deprecation shims."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from hypothesis_compat import given, settings, st  # noqa: E402

from repro import pipeline  # noqa: E402
from repro.pipeline import (Capabilities, ModelSpec, NegotiationError,
                            OperatingPoint, negotiate)  # noqa: E402

RNG = np.random.default_rng(7)


def _z(b, h, w, p):
    """A split activation with per-channel scale variety (exercises the
    per-example side info)."""
    scale = RNG.uniform(0.1, 4.0, size=(1, 1, 1, p)).astype(np.float32)
    return (RNG.normal(size=(b, h, w, p)).astype(np.float32) * scale)


def _spec(c):
    return ModelSpec(sel_idx=np.arange(c))


# ---------------------------------------------------------------------------
# Operating-point validation
# ---------------------------------------------------------------------------

def test_op_validates_fields():
    with pytest.raises(ValueError):
        OperatingPoint(c=0, bits=8)
    with pytest.raises(ValueError):
        OperatingPoint(c=8, bits=0)
    assert OperatingPoint(c=3, bits=8).backend == "rans"


@pytest.mark.parametrize("backend", ["zlib", "png", "raw", "rans-ctx"])
def test_unknown_backend_fails_at_compile_time(backend):
    """The retired codecs are refused where the point is built, before any
    plan exists."""
    with pytest.raises(ValueError, match=f"unknown backend '{backend}'"):
        OperatingPoint(c=4, bits=8, backend=backend)


# ---------------------------------------------------------------------------
# Plan compilation and caching
# ---------------------------------------------------------------------------

def test_compile_is_cached_per_op_and_spec():
    spec = _spec(8)
    op = OperatingPoint(c=8, bits=6)
    assert pipeline.compile(op, spec) is pipeline.compile(op, spec)
    assert pipeline.compile(op, spec) is not pipeline.compile(op, _spec(8))
    op2 = OperatingPoint(c=8, bits=4)
    assert pipeline.compile(op, spec) is not pipeline.compile(op2, spec)


def test_plan_rejects_mismatched_channel_count():
    with pytest.raises(ValueError, match="C=8"):
        pipeline.compile(OperatingPoint(c=8, bits=6), _spec(4))


def test_weightless_plan_encodes_but_refuses_restore():
    plan = pipeline.compile(OperatingPoint(c=4, bits=6), _spec(4))
    blob = plan.encode(_z(1, 4, 4, 8))
    dec = plan.decode(blob)
    assert dec.codes.shape == (1, 4, 4, 4)
    with pytest.raises(ValueError, match="without model weights"):
        plan.restore(dec)


# ---------------------------------------------------------------------------
# Round trips: decode_batch(encode(z)) is bit-exact
# ---------------------------------------------------------------------------

# the paper's sweep, as the RD grid prices it
@pytest.mark.parametrize("bits", range(2, 9))
@pytest.mark.parametrize("c", [8, 16, 32, 64, 128])
def test_plan_round_trip_bit_exact(c, bits):
    plan = pipeline.compile(OperatingPoint(c=c, bits=bits), _spec(c))
    z = _z(2, 5, 3, c + 8)
    codes, mins, maxs = plan.quantize(z)
    dec = plan.decode_batch([plan.encode(z)])
    np.testing.assert_array_equal(dec.codes, codes)
    np.testing.assert_array_equal(dec.mins, mins)
    np.testing.assert_array_equal(dec.maxs, maxs)


@given(st.data())
@settings(max_examples=30, deadline=None)
def test_plan_round_trip_property(data):
    """decode_batch(encode(z)) == the quantizer's own codes/side-info,
    odd shapes and channel counts included."""
    c = data.draw(st.sampled_from([1, 2, 3, 5, 8]), label="c")
    bits = data.draw(st.integers(2, 8), label="bits")
    b = data.draw(st.integers(1, 2), label="b")
    h = data.draw(st.integers(1, 6), label="h")
    w = data.draw(st.integers(1, 6), label="w")
    n_blobs = data.draw(st.integers(1, 3), label="n_blobs")
    plan = pipeline.compile(OperatingPoint(c=c, bits=bits), _spec(c))
    zs = [_z(b, h, w, c + 2) for _ in range(n_blobs)]
    refs = [plan.quantize(z) for z in zs]
    dec = plan.decode_batch([plan.encode(z) for z in zs])
    np.testing.assert_array_equal(
        dec.codes, np.concatenate([r[0] for r in refs]))
    np.testing.assert_array_equal(
        dec.mins, np.concatenate([r[1] for r in refs]))
    np.testing.assert_array_equal(
        dec.maxs, np.concatenate([r[2] for r in refs]))


@given(st.data())
@settings(max_examples=10, deadline=None)
def test_mixed_operating_points_in_one_arrival_batch(data):
    """A shuffled stream of blobs at mixed operating points, grouped by
    bucket (as the gateway's batcher does), decodes bit-exactly per group."""
    ops = [
        OperatingPoint(c=4, bits=4),
        OperatingPoint(c=4, bits=6),
        OperatingPoint(c=8, bits=4),
        OperatingPoint(c=2, bits=8),
    ]
    stream = []
    for _ in range(data.draw(st.integers(4, 8), label="n")):
        op = data.draw(st.sampled_from(ops), label="op")
        plan = pipeline.compile(op, _spec(op.c))
        z = _z(1, 4, 4, 10)
        stream.append((plan, plan.encode(z), plan.quantize(z)))
    groups = {}
    for plan, blob, ref in stream:
        groups.setdefault((plan.op, blob.shape), []).append(
            (plan, blob, ref))
    for (_, _), members in groups.items():
        plan = members[0][0]
        dec = plan.decode_batch([blob for _, blob, _ in members])
        np.testing.assert_array_equal(
            dec.codes, np.concatenate([ref[0] for _, _, ref in members]))
        np.testing.assert_array_equal(
            dec.mins, np.concatenate([ref[1] for _, _, ref in members]))


def test_mixed_operating_points_deterministic():
    """Deterministic twin of the property test above (runs without
    hypothesis): interleaved ops and odd shapes, grouped then batch-decoded."""
    ops = [OperatingPoint(c=4, bits=4),
           OperatingPoint(c=8, bits=6),
           OperatingPoint(c=3, bits=5),
           OperatingPoint(c=4, bits=8)]
    stream = []
    for i in range(9):
        op = ops[i % len(ops)]
        plan = pipeline.compile(op, _spec(op.c))
        z = _z(1, 5, 3, 10)
        stream.append((plan, plan.encode(z), plan.quantize(z)))
    groups = {}
    for item in stream:
        groups.setdefault((item[0].op, item[1].shape), []).append(item)
    assert len(groups) == len(ops)
    for members in groups.values():
        plan = members[0][0]
        dec = plan.decode_batch([blob for _, blob, _ in members])
        np.testing.assert_array_equal(
            dec.codes, np.concatenate([ref[0] for _, _, ref in members]))
        np.testing.assert_array_equal(
            dec.mins, np.concatenate([ref[1] for _, _, ref in members]))
        np.testing.assert_array_equal(
            dec.maxs, np.concatenate([ref[2] for _, _, ref in members]))


def test_decode_batch_rejects_heterogeneous_blobs():
    plan4 = pipeline.compile(OperatingPoint(c=4, bits=6), _spec(4))
    plan8 = pipeline.compile(OperatingPoint(c=8, bits=6), _spec(8))
    b4 = plan4.encode(_z(1, 4, 4, 8))
    b8 = plan8.encode(_z(1, 4, 4, 8))
    with pytest.raises(ValueError, match="this plan executes"):
        plan4.decode_batch([b4, b8])
    small = plan4.encode(_z(1, 2, 2, 8))
    with pytest.raises(ValueError, match="mixed shapes"):
        plan4.decode_batch([b4, small])
    with pytest.raises(ValueError, match="at least one"):
        plan4.decode_batch([])


def test_wire_blob_parses_and_validates():
    plan = pipeline.compile(OperatingPoint(c=4, bits=6), _spec(4))
    blob = plan.encode(_z(1, 3, 3, 6))
    enc = blob.to_tensor()
    assert enc.bits == 6
    assert blob.nbytes == len(blob.data)
    corrupt = pipeline.WireBlob(data=blob.data[:-1], op=blob.op,
                                shape=blob.shape)
    with pytest.raises(ValueError):
        plan.decode(corrupt)


# ---------------------------------------------------------------------------
# Capability negotiation
# ---------------------------------------------------------------------------

def test_negotiate_passes_through_supported_points():
    op = OperatingPoint(c=8, bits=8)
    assert negotiate(op, None) is op
    assert negotiate(op, Capabilities()) is op
    assert negotiate(op, Capabilities(max_bits=8)) is op


def test_negotiate_clamps_bits():
    op = OperatingPoint(c=8, bits=12)
    out = negotiate(op, Capabilities(max_bits=8))
    assert out.bits == 8


def test_negotiate_refuses_without_downgrade():
    op = OperatingPoint(c=8, bits=8)
    with pytest.raises(NegotiationError):
        negotiate(op, Capabilities(max_bits=4, downgrade=False))


def test_negotiate_always_refuses_foreign_wire_profile():
    op = OperatingPoint(c=8, bits=8, profile=1)
    with pytest.raises(NegotiationError, match="profile"):
        negotiate(op, Capabilities())          # downgrade=True cannot help


# (the one-release encode_activation/decode_stream shims are gone; their
# absence is pinned in tests/test_no_deprecations.py)
