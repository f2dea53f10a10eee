"""Streaming session subsystem: temporal delta codec, wire-format hardening,
desync/NACK recovery, and the QoS'd session manager on the virtual clock.
"""
import time

import jax
import numpy as np
import pytest

from repro.analysis import ReplaySanitizerError, replay_sanitizer

from repro.codec.rans import CorruptStream
from repro.configs.yolo_baf import smoke_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.data.synthetic import correlated_frames
from repro.models.cnn import init_cnn
from repro.pipeline import (Capabilities, ModelSpec, NegotiationError,
                            OperatingPoint)
from repro.pipeline import compile as pcompile
from repro.serve import (AdmissionDecision, AdmissionPolicy, ChannelConfig,
                         LinearCostModel, MultiQueueExecutor,
                         MultiTenantGateway, TenantSpec)
from repro.session import (QosLevel, SessionConfig, SessionDecoder,
                           SessionDesync, SessionEncoder, SessionFrame,
                           SessionManager, SessionSpec)
from repro.session.recovery import (RecoveryConfig, RecoveryTracker,
                                    recovery_bound_s)

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


def _z_stream(n, *, shape=(1, 8, 8, 8), drift=0.01, seed=0):
    """Temporally correlated split activations (frame t ~ frame t-1)."""
    rng = np.random.default_rng(seed)
    z = rng.normal(size=shape).astype(np.float32)
    out = [z]
    for _ in range(n - 1):
        z = z + drift * rng.normal(size=shape).astype(np.float32)
        out.append(z)
    return out


def _pair(plan_for, **cfg_kw):
    cfg = SessionConfig(session_id=1, levels=(OP,), **cfg_kw)
    return (SessionEncoder(cfg, plan_for), SessionDecoder(cfg, plan_for))


# ---------------------------------------------------------------------------
# Codec: I/P round trips
# ---------------------------------------------------------------------------

def test_i_frame_payload_is_the_stateless_container(plan_for):
    """An I-frame's payload is byte-identical to plan.encode — a keyframe
    stream is the stateless wire format, just framed."""
    enc, _ = _pair(plan_for)
    z = _z_stream(1)[0]
    blob, meta = enc.encode(z)
    assert meta.intra
    frame = SessionFrame.parse(blob)
    assert frame.payload == plan_for(OP).encode(z).data


@pytest.mark.parametrize("bits", range(2, 9))
def test_p_chain_reconstructs_codes_exactly(plan_for, bits):
    """Temporal prediction is lossless on top of quantization: every frame
    of a long P-chain decodes to the exact codes the encoder quantized —
    zero drift at any chain length, at every bit depth (the mod-2^bits
    delta rides the rANS coder)."""
    op = OperatingPoint(c=8, bits=bits)
    cfg = SessionConfig(session_id=1, levels=(op,))
    enc, dec = SessionEncoder(cfg, plan_for), SessionDecoder(cfg, plan_for)
    plan = plan_for(op)
    for i, z in enumerate(_z_stream(12)):
        blob, meta = enc.encode(z)
        assert meta.intra == (i == 0)
        decoded, frame = dec.decode(blob)
        want, _ = plan._quantize(z)
        assert np.array_equal(decoded.codes, np.asarray(want))
        assert frame.seq == i


def test_p_frames_code_far_below_i_frames_on_correlated_stream(plan_for):
    """The wire-bit win the subsystem exists for: on temporally correlated
    activations the P-frame delta entropy-codes well under 0.7x the
    I-frame, and the whole session beats I-only by >= 1.4x."""
    enc, _ = _pair(plan_for)
    i_bits, p_bits = [], []
    for z in _z_stream(16):
        _, meta = enc.encode(z)
        (i_bits if meta.intra else p_bits).append(meta.wire_bits)
    assert len(i_bits) == 1 and len(p_bits) == 15
    assert np.mean(p_bits) <= 0.7 * np.mean(i_bits)
    i_only = len(p_bits + i_bits) * np.mean(i_bits)
    assert i_only / (sum(i_bits) + sum(p_bits)) >= 1.4


def test_keyframe_interval_forces_periodic_i(plan_for):
    enc, _ = _pair(plan_for, keyframe_interval=4)
    intras = [enc.encode(z)[1].intra for z in _z_stream(9)]
    assert intras == [True, False, False, False,
                      True, False, False, False, True]


def test_nack_forces_intra_refresh(plan_for):
    enc, _ = _pair(plan_for)
    zs = _z_stream(3)
    enc.encode(zs[0])
    assert not enc.encode(zs[1])[1].intra
    enc.nack()
    assert enc.force_intra_pending
    assert enc.encode(zs[2])[1].intra
    assert not enc.force_intra_pending


def test_level_change_forces_i_frame(plan_for):
    """A delta across operating points is meaningless — switching QoS rung
    must restart the chain."""
    coarse = OperatingPoint(c=8, bits=4, backend="rans")
    cfg = SessionConfig(session_id=2, levels=(OP, coarse))
    enc = SessionEncoder(cfg, plan_for)
    dec = SessionDecoder(cfg, plan_for)
    zs = _z_stream(4)
    dec.decode(enc.encode(zs[0], level=0)[0])
    dec.decode(enc.encode(zs[1], level=0)[0])
    blob, meta = enc.encode(zs[2], level=1)
    assert meta.intra and meta.level == 1
    decoded, _ = dec.decode(blob)
    want, _ = plan_for(coarse)._quantize(zs[2])
    assert np.array_equal(decoded.codes, np.asarray(want))
    # and back down the ladder: another forced I
    assert enc.encode(zs[3], level=0)[1].intra


def test_session_without_temporal_capability_streams_i_only(plan_for):
    """A decode side that never negotiated the session profile still works —
    every frame is an I-frame (graceful fallback, not an error)."""
    cfg = SessionConfig(session_id=3, levels=(OP,))
    caps = Capabilities(session_profiles=(), downgrade=True)
    enc = SessionEncoder(cfg, plan_for, capabilities=caps)
    assert not enc.temporal
    assert all(enc.encode(z)[1].intra for z in _z_stream(4))
    with pytest.raises(NegotiationError):
        SessionEncoder(cfg, plan_for,
                       capabilities=Capabilities(session_profiles=(),
                                                 downgrade=False))


# ---------------------------------------------------------------------------
# Codec: desync + wire hardening
# ---------------------------------------------------------------------------

def test_p_frame_after_a_lost_frame_desyncs_never_restores(plan_for):
    enc, dec = _pair(plan_for)
    zs = _z_stream(3)
    dec.decode(enc.encode(zs[0])[0])
    enc.encode(zs[1])                      # lost in flight
    blob, _ = enc.encode(zs[2])
    with pytest.raises(SessionDesync):
        dec.decode(blob)
    assert dec.last_decoded_seq == 0       # nothing after frame 0 restored
    # the failed frame must not poison recovery: a fresh I resyncs
    enc.nack()
    decoded, frame = dec.decode(enc.encode(zs[2])[0])
    assert frame.intra and dec.synced
    want, _ = plan_for(OP)._quantize(zs[2])
    assert np.array_equal(decoded.codes, np.asarray(want))


def test_p_frame_into_fresh_decoder_desyncs(plan_for):
    enc, _ = _pair(plan_for)
    dec_late = SessionDecoder(SessionConfig(session_id=1, levels=(OP,)),
                              plan_for)
    zs = _z_stream(2)
    enc.encode(zs[0])
    blob, _ = enc.encode(zs[1])            # P, but dec_late joined late
    with pytest.raises(SessionDesync):
        dec_late.decode(blob)


def test_frame_for_wrong_session_is_rejected(plan_for):
    enc, _ = _pair(plan_for)
    other = SessionDecoder(SessionConfig(session_id=99, levels=(OP,)),
                           plan_for)
    blob, _ = enc.encode(_z_stream(1)[0])
    with pytest.raises(CorruptStream, match="session 1"):
        other.decode(blob)


def test_wire_format_rejects_damage_with_distinct_errors(plan_for):
    enc, _ = _pair(plan_for)
    blob = bytearray(enc.encode(_z_stream(1)[0])[0])

    def expect(msg, mutate):
        bad = bytearray(blob)
        mutate(bad)
        with pytest.raises(CorruptStream, match=msg):
            SessionFrame.parse(bytes(bad))

    expect("truncated session frame header", lambda b: b.__imul__(0))
    expect("bad session frame magic",
           lambda b: b.__setitem__(0, b[0] ^ 0xFF))
    expect("unsupported session wire version",
           lambda b: b.__setitem__(slice(4, 5), b"\x7f"))
    # flips inside the CRC-protected header (past magic/version, which fail
    # their own checks first)
    expect("header CRC mismatch", lambda b: b.__setitem__(9, b[9] ^ 0x01))
    expect("truncated session frame payload",
           lambda b: b.__delitem__(slice(len(b) // 2, len(b))))
    expect("trailing garbage", lambda b: b.extend(b"\x00"))
    expect("payload CRC mismatch",
           lambda b: b.__setitem__(30, b[30] ^ 0x10))


def test_unknown_frame_type_and_ladder_overflow_rejected(plan_for):
    import struct
    import zlib
    enc, dec = _pair(plan_for)
    blob = bytearray(enc.encode(_z_stream(1)[0])[0])

    def rewrite(offset, value):
        bad = bytearray(blob)
        bad[offset] = value
        bad[24:28] = struct.pack("<I", zlib.crc32(bytes(bad[:24])))
        return bytes(bad)

    with pytest.raises(CorruptStream, match="unknown session frame type"):
        SessionFrame.parse(rewrite(5, 7))
    with pytest.raises(CorruptStream, match="outside the agreed ladder"):
        dec.decode(rewrite(6, 200))        # level byte past the rung count


# ---------------------------------------------------------------------------
# Recovery primitives
# ---------------------------------------------------------------------------

def test_recovery_tracker_measures_episodes_not_events():
    tr = RecoveryTracker()
    assert tr.on_desync(1.0)               # opens the episode -> NACK
    assert not tr.on_desync(1.1)           # still down: no second NACK
    tr.on_resync(1.5)
    assert tr.episodes == 1 and tr.desync_events == 2
    assert tr.recovery_times == [pytest.approx(0.5)]
    tr.on_resync(2.0)                      # resync while up: no-op
    assert tr.max_recovery_s == pytest.approx(0.5)


def test_recovery_config_rejects_unrecoverable_sessions():
    with pytest.raises(ValueError, match="unrecoverable"):
        RecoveryConfig(nack=False, keyframe_interval=0)
    RecoveryConfig(nack=False, keyframe_interval=8)     # broadcast mode: ok


def test_recovery_bound_scales_with_frame_interval():
    tight = recovery_bound_s(fps=30, uplink_latency_s=0.01,
                             nack_latency_s=0.02)
    loose = recovery_bound_s(fps=10, uplink_latency_s=0.01,
                             nack_latency_s=0.02)
    assert loose > tight > 0.03


# ---------------------------------------------------------------------------
# Session manager on a real gateway
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gateway_parts():
    cnn_cfg = smoke_config()._replace(input_size=32)
    params = init_cnn(jax.random.PRNGKey(0), cnn_cfg)
    bank = {c: (init_baf_conv(jax.random.PRNGKey(c),
                              BaFConvConfig(c=c, q=cnn_cfg.split_q,
                                            hidden=8)),
                np.arange(c)) for c in (4, 8)}
    return params, bank


LADDER = (QosLevel(OperatingPoint(c=8, bits=6, backend="rans")),
          QosLevel(OperatingPoint(c=8, bits=4, backend="rans"),
                   keyframe_interval=8),
          QosLevel(OperatingPoint(c=4, bits=4, backend="rans"),
                   keyframe_interval=8, frame_stride=2))


def _gateway(params, bank, *, admission=None):
    tenants = [TenantSpec(name=f"cam{i}", priority=i % 2) for i in range(3)]
    return MultiTenantGateway(
        params, bank, tenants=tenants,
        executor=MultiQueueExecutor(2, cost=LinearCostModel(0.002, 0.0005)),
        admission=admission, max_batch=4, batch_window_s=0.01)


def _manager(gw, *, loss=0.0, corrupt=0.0, seed=3, fps=20.0):
    sessions = [SessionSpec(name=f"cam{i}", fps=fps, start_s=0.002 * i)
                for i in range(3)]
    cfg = ChannelConfig(bandwidth_bps=20e6, base_latency_s=0.005,
                        loss_p=loss, corrupt_p=corrupt, mtu_bytes=256)
    return SessionManager(gw, sessions, ladder=LADDER, channel_cfg=cfg,
                          recovery=RecoveryConfig(nack_latency_s=0.01),
                          seed=seed)


def _frames(n=24):
    return {f"cam{i}": correlated_frames(n, image_size=32, seed=10 + i)
            for i in range(3)}


def test_clean_channels_stream_every_frame_mostly_p(tiny_gateway_parts):
    params, bank = tiny_gateway_parts
    mgr = _manager(_gateway(params, bank))
    frames = _frames(16)
    responses, report = mgr.run(frames)
    for name in frames:
        assert report.counts(name) == {"served": 16}
        assert report.nacks[name] == 0
        assert report.recovery[name].episodes == 0
        # exactly one keyframe; the rest rode the temporal chain
        assert sum(f.intra for f in report.frames[name]) == 1
        assert set(responses[name]) == set(range(16))
        assert all(np.all(np.isfinite(v)) for v in responses[name].values())
    assert len(report.telemetry) == 48 and not report.telemetry.shed


def test_lossy_run_recovers_bounded_ends_in_sync_and_replays(
        tiny_gateway_parts):
    """The acceptance scenario: 5% loss + corruption; desyncs happen, every
    recovery is bounded, every session ends in sync (run() asserts it), and
    a second run is bit-identical under the deterministic cost model."""
    params, bank = tiny_gateway_parts
    mgr = _manager(_gateway(params, bank), loss=0.05, corrupt=0.02)
    frames = _frames(24)
    _, report = mgr.run(frames)
    impaired = sum(n for name in frames
                   for o, n in report.counts(name).items()
                   if o in ("lost", "corrupt", "desync"))
    assert impaired > 0, "seeded run must actually exercise loss"
    assert sum(report.nacks.values()) > 0
    bound = recovery_bound_s(fps=20.0, uplink_latency_s=0.02,
                             nack_latency_s=0.01, margin_frames=2)
    for name in frames:
        tr = report.recovery[name]
        assert not tr.in_desync
        # repeated loss can chain cycles; 2x single-cycle bound holds at 5%
        assert tr.max_recovery_s <= 2 * bound
    # the replay runs under the sanitizer: any wall-clock / global-RNG read
    # on the replay path would raise instead of silently skewing state
    with replay_sanitizer():
        _, report2 = mgr.run(frames)
    assert report.signature() == report2.signature()


def test_replay_sanitizer_clean_run_and_injected_leak(tiny_gateway_parts):
    """Dynamic coverage behind the static RA01/RA02 rules: a clean
    SessionManager run executes fully sanitized (and stays bit-identical),
    while a wall-clock read smuggled into the serving path raises
    ReplaySanitizerError instead of desynchronizing the replay."""
    params, bank = tiny_gateway_parts
    mgr = _manager(_gateway(params, bank))
    frames = _frames(8)
    _, report = mgr.run(frames)
    with replay_sanitizer():
        _, report2 = mgr.run(frames)
    assert report.signature() == report2.signature()

    gw = _gateway(params, bank)
    leaky_mgr = _manager(gw)
    inner = gw._cloud_fn

    def leaky_cloud_fn(params, z_tilde):
        time.time()                        # the smuggled wall-clock read
        return inner(params, z_tilde)

    gw._cloud_fn = leaky_cloud_fn
    with replay_sanitizer():
        with pytest.raises(ReplaySanitizerError, match="time.time"):
            leaky_mgr.run(frames)


def test_overload_degrades_down_the_ladder_before_shedding(
        tiny_gateway_parts):
    """Degrade-before-shed: with admission refusing everything, each session
    walks rung 0 -> 1 -> 2 (two DegradeRecords), and only frames already at
    the floor are shed. The floor rung's stride also thins offered load."""
    params, bank = tiny_gateway_parts

    class RefuseAll(AdmissionPolicy):
        def admit(self, *, tenant, priority, t, executor):
            return AdmissionDecision(False, reason="saturated")

    gw = _gateway(params, bank, admission=RefuseAll())
    mgr = _manager(gw)
    frames = _frames(12)
    _, report = mgr.run(frames)
    degrades = report.telemetry.degrade_by_tenant()
    for name in frames:
        assert degrades[name] == 2          # one step per rung below 0
        assert report.final_levels[name] == 2
        steps = [(d.from_level, d.to_level)
                 for d in report.telemetry.degraded if d.tenant == name]
        assert steps == [(0, 1), (1, 2)]
        counts = report.counts(name)
        assert counts.get("shed", 0) > 0
        assert counts.get("skipped", 0) > 0      # floor stride at work
        # shed only ever happens at the floor
        assert all(f.level == len(LADDER) - 1
                   for f in report.frames[name] if f.outcome == "shed")
    assert len(report.telemetry.degraded) == 6


def test_pressure_release_steps_back_up(tiny_gateway_parts):
    """Quality recovers: once admission stops refusing, a session climbs
    back toward rung 0 after upgrade_hold clean admissions."""
    params, bank = tiny_gateway_parts

    class PulsedAdmission:
        """Refuse the first two asks per tenant, admit everything after."""

        def __init__(self):
            self.asked = {}

        def reset(self):
            self.asked = {}

        def admit(self, *, tenant, priority, t, executor):
            n = self.asked.get(tenant, 0)
            self.asked[tenant] = n + 1
            if n < 2:
                return AdmissionDecision(False, reason="pulse")
            return AdmissionDecision(True)

    gw = _gateway(params, bank, admission=PulsedAdmission())
    sessions = [SessionSpec(name="cam0", fps=20.0)]
    mgr = SessionManager(
        gw, sessions, ladder=LADDER,
        channel_cfg=ChannelConfig(bandwidth_bps=20e6, base_latency_s=0.005),
        recovery=RecoveryConfig(nack_latency_s=0.01), upgrade_hold=4)
    _, report = mgr.run({"cam0": correlated_frames(20, image_size=32,
                                                   seed=11)})
    assert report.telemetry.degrade_by_tenant() == {"cam0": 2}
    assert report.final_levels["cam0"] < 2   # climbed back off the floor


# ---------------------------------------------------------------------------
# P-frame-aware initial level selection (rd_table + frame_budget_bits)
# ---------------------------------------------------------------------------

def _priced_table():
    """The test ladder's ops priced with measured P/I ratios.

    Per-frame session price (serve.session_bits_per_frame):
      level 0: k=0  all-P      -> 10_000 * 0.5          = 5_000
      level 1: k=8             ->  8_000 * (1+7/4)/8    = 2_750
      level 2: k=8, stride=2   ->  6_000 * (1+7/4)/8/2  ~= 1_031
    """
    from repro.serve import RDPoint
    return [RDPoint(LADDER[0].op, 10_000.0, 30.0, p_over_i=0.5),
            RDPoint(LADDER[1].op, 8_000.0, 26.0, p_over_i=0.25),
            RDPoint(LADDER[2].op, 6_000.0, 22.0, p_over_i=0.25)]


def test_manager_prices_initial_level_with_p_frame_savings(
        tiny_gateway_parts):
    params, bank = tiny_gateway_parts
    mgr = _manager(_gateway(params, bank))
    mgr_priced = SessionManager(
        _gateway(params, bank),
        [SessionSpec(name=f"cam{i}", fps=20.0, start_s=0.002 * i)
         for i in range(3)],
        ladder=LADDER,
        channel_cfg=ChannelConfig(bandwidth_bps=20e6, base_latency_s=0.005,
                                  mtu_bytes=256),
        recovery=RecoveryConfig(nack_latency_s=0.01), seed=3,
        rd_table=_priced_table(), frame_budget_bits=3_000.0)
    assert mgr._initial_level == 0          # default: best rung
    assert mgr_priced._initial_level == 1   # 5_000 > budget, 2_750 fits
    _, report = mgr_priced.run(_frames(8))
    for name in report.frames:
        assert report.frames[name][0].level == 1
    # I-only pricing would have sent sessions to the floor: rung 1's
    # I-frame price (8_000) busts the budget, its session price does not
    assert _priced_table()[1].bits_per_example > 3_000.0


def test_manager_priced_level_skips_unpriced_rungs_and_floors_out(
        tiny_gateway_parts):
    params, bank = tiny_gateway_parts

    def priced(table, budget):
        return SessionManager(
            _gateway(params, bank), [SessionSpec(name="cam0", fps=20.0)],
            ladder=LADDER,
            channel_cfg=ChannelConfig(bandwidth_bps=20e6,
                                      base_latency_s=0.005, mtu_bytes=256),
            recovery=RecoveryConfig(nack_latency_s=0.01),
            rd_table=table, frame_budget_bits=budget)

    # only the floor rung is priced; rungs without an entry are skipped
    assert priced(_priced_table()[2:], 2_000.0)._initial_level == 2
    # nothing fits the budget -> the floor rung, never an error
    assert priced(_priced_table(), 10.0)._initial_level == 2


def test_manager_pricing_with_ample_budget_replays_default_exactly(
        tiny_gateway_parts):
    """The satellite's regression gate: the priced path with a budget no
    rung busts starts at rung 0 and reproduces the committed default-path
    behaviour bit for bit."""
    params, bank = tiny_gateway_parts
    frames = _frames(12)
    _, base = _manager(_gateway(params, bank)).run(frames)
    priced = SessionManager(
        _gateway(params, bank),
        [SessionSpec(name=f"cam{i}", fps=20.0, start_s=0.002 * i)
         for i in range(3)],
        ladder=LADDER,
        channel_cfg=ChannelConfig(bandwidth_bps=20e6, base_latency_s=0.005,
                                  mtu_bytes=256),
        recovery=RecoveryConfig(nack_latency_s=0.01), seed=3,
        rd_table=_priced_table(), frame_budget_bits=1e9)
    assert priced._initial_level == 0
    _, rep = priced.run(frames)
    assert rep.signature() == base.signature()
