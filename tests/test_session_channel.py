"""Channel-corruption coverage: packet-granularity damage through frame
reassembly must always be *detected* — every truncation and bit-flip either
raises a distinct CorruptStream or triggers a session resync; a wrong tensor
is never served.
"""
import numpy as np
import pytest

from repro.codec.rans import CorruptStream
from repro.pipeline import ModelSpec, OperatingPoint
from repro.pipeline import compile as pcompile
from repro.serve import ChannelConfig, SimulatedChannel
from repro.session import (SessionConfig, SessionDecoder, SessionEncoder,
                           SessionError)

OP = OperatingPoint(c=8, bits=6, backend="rans")


@pytest.fixture(scope="module")
def plan_for():
    spec = ModelSpec(sel_idx=np.arange(8))
    cache = {}

    def get(op):
        if op not in cache:
            cache[op] = pcompile(op, spec)
        return cache[op]
    return get


@pytest.fixture(scope="module")
def frame_and_codes(plan_for):
    rng = np.random.default_rng(1)
    z = rng.normal(size=(1, 8, 8, 8)).astype(np.float32)
    cfg = SessionConfig(session_id=5, levels=(OP,))
    enc = SessionEncoder(cfg, plan_for)
    blob, _ = enc.encode(z)
    codes, _ = plan_for(OP)._quantize(z)
    return bytes(blob), np.asarray(codes), cfg


def _fresh_decoder(plan_for, cfg):
    return SessionDecoder(cfg, plan_for)


# ---------------------------------------------------------------------------
# Exhaustive single-frame fuzz (no channel): detection is total
# ---------------------------------------------------------------------------

def test_every_truncation_is_detected(plan_for, frame_and_codes):
    blob, _, cfg = frame_and_codes
    for cut in range(len(blob)):            # every proper prefix
        with pytest.raises(CorruptStream):
            _fresh_decoder(plan_for, cfg).decode(blob[:cut])


def test_every_seeded_bit_flip_is_detected(plan_for, frame_and_codes):
    """256 seeded single-bit flips across the whole frame (header, CRCs,
    payload): none may decode — header bytes fail framing/header-CRC,
    payload bytes fail the payload CRC. Zero wrong tensors, ever."""
    blob, codes, cfg = frame_and_codes
    rng = np.random.default_rng(7)
    messages = set()
    for _ in range(256):
        pos = int(rng.integers(0, 8 * len(blob)))
        bad = bytearray(blob)
        bad[pos >> 3] ^= 1 << (pos & 7)
        with pytest.raises((CorruptStream, SessionError)) as ei:
            _fresh_decoder(plan_for, cfg).decode(bytes(bad))
        messages.add(str(ei.value).split(":")[0])
    # damage in different regions surfaces as *distinct* diagnoses
    assert len(messages) >= 3


def test_multi_bit_burst_damage_is_detected(plan_for, frame_and_codes):
    blob, _, cfg = frame_and_codes
    rng = np.random.default_rng(13)
    for _ in range(32):
        bad = bytearray(blob)
        start = int(rng.integers(0, len(bad) - 4))
        for off in range(4):                # 4-byte burst
            bad[start + off] ^= int(rng.integers(1, 256))
        with pytest.raises((CorruptStream, SessionError)):
            _fresh_decoder(plan_for, cfg).decode(bytes(bad))


# ---------------------------------------------------------------------------
# Through the packetized channel
# ---------------------------------------------------------------------------

def test_corrupting_channel_never_yields_a_wrong_tensor(plan_for):
    """Stream 40 frames through a channel that flips a bit in ~every packet:
    every delivery either decodes to the exact quantized codes or raises —
    the decoded-equals-quantized check runs on every success."""
    cfg = SessionConfig(session_id=6, levels=(OP,))
    enc = SessionEncoder(cfg, plan_for)
    dec = SessionDecoder(cfg, plan_for)
    ch = SimulatedChannel(ChannelConfig(bandwidth_bps=50e6,
                                        corrupt_p=0.5, mtu_bytes=128),
                          seed=21)
    plan = plan_for(OP)
    rng = np.random.default_rng(3)
    z = rng.normal(size=(1, 8, 8, 8)).astype(np.float32)
    failures = successes = 0
    for _ in range(40):
        z = z + 0.01 * rng.normal(size=z.shape).astype(np.float32)
        blob, _ = enc.encode(z)
        delivery = ch.transmit_frame(blob)
        assert not delivery.lost
        try:
            decoded, _ = dec.decode(delivery.data)
        except (CorruptStream, SessionError):
            failures += 1
            enc.nack()                       # intra refresh restores sync
            continue
        successes += 1
        want, _ = plan._quantize(z)
        assert np.array_equal(decoded.codes, np.asarray(want))
    assert failures > 0 and successes > 0
    assert dec.synced


def test_lossy_channel_drops_whole_frames_and_meters_the_wire(plan_for,
                                                              frame_and_codes):
    blob, _, _ = frame_and_codes
    ch = SimulatedChannel(ChannelConfig(bandwidth_bps=50e6, loss_p=1.0,
                                        mtu_bytes=64), seed=0)
    d = ch.transmit_frame(blob)
    assert d.lost and d.data is None
    assert d.lost_packets == d.n_packets == -(-len(blob) // 64)
    # lost bits still occupied the wire
    assert d.tx.bits == 8 * len(blob)
    assert d.tx.t_arrive > 0


def test_impairment_free_frame_matches_plain_transmit(frame_and_codes):
    """With no impairments configured, transmit_frame is transmit_bytes plus
    packetization — same RNG stream, same timings on a jittered channel."""
    blob, _, _ = frame_and_codes
    cfg = ChannelConfig(bandwidth_bps=5e6, base_latency_s=0.01,
                        jitter_s=0.002)
    a, b = SimulatedChannel(cfg, seed=9), SimulatedChannel(cfg, seed=9)
    ta = a.transmit_bytes(blob)
    tb = b.transmit_frame(blob)
    assert not tb.lost and not tb.corrupted
    assert tb.tx == ta                      # bitwise-equal Transmission


def test_reorder_delays_the_whole_frame(frame_and_codes):
    blob, _, _ = frame_and_codes
    base = SimulatedChannel(ChannelConfig(bandwidth_bps=50e6,
                                          mtu_bytes=64), seed=4)
    t_clean = base.transmit_frame(blob).tx.t_arrive
    ch = SimulatedChannel(ChannelConfig(bandwidth_bps=50e6, mtu_bytes=64,
                                        reorder_p=1.0, reorder_delay_s=0.05),
                          seed=4)
    d = ch.transmit_frame(blob)
    assert not d.lost
    assert d.tx.t_arrive == pytest.approx(t_clean + 0.05)


def test_channel_config_validates_impairments():
    with pytest.raises(ValueError):
        ChannelConfig(loss_p=1.5)
    with pytest.raises(ValueError):
        ChannelConfig(corrupt_p=-0.1)
    with pytest.raises(ValueError):
        ChannelConfig(reorder_delay_s=-1.0)
    with pytest.raises(ValueError):
        ChannelConfig(mtu_bytes=0)
    with pytest.raises(ValueError):
        SimulatedChannel(ChannelConfig()).transmit_frame(b"")
