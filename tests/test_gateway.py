"""Serving gateway: channel model, rate control, micro-batcher, end to end."""
import jax
import numpy as np
import pytest

from repro.configs.yolo_baf import smoke_config, smoke_data_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.data.synthetic import shapes_batch_iterator
from repro.models.cnn import init_cnn
from repro.pipeline import DecodedBatch
from repro.serve import (Capabilities, ChannelConfig, DecodedRequest,
                         MicroBatcher, MultiTenantGateway, NegotiationError,
                         OperatingPoint, RateController, RDPoint,
                         ServingGateway, SimulatedChannel, TenantRequest,
                         TenantSpec, bucket_sizes)


# ---------------------------------------------------------------------------
# Channel model
# ---------------------------------------------------------------------------

def test_channel_latency_is_serialization_plus_propagation():
    ch = SimulatedChannel(ChannelConfig(bandwidth_bps=1000, base_latency_s=0.5))
    tx = ch.transmit(1000, t_submit=0.0)
    assert tx.t_start == 0.0
    assert tx.t_arrive == pytest.approx(1.0 + 0.5)


def test_channel_serializes_back_to_back_transmissions():
    ch = SimulatedChannel(ChannelConfig(bandwidth_bps=1000, base_latency_s=0.0))
    a = ch.transmit(1000, t_submit=0.0)     # occupies the wire until t=1
    b = ch.transmit(1000, t_submit=0.0)     # must wait for a
    assert b.t_start == pytest.approx(a.t_submit + 1.0)
    assert b.queue_wait_s == pytest.approx(1.0)


def test_channel_is_deterministic_under_seed():
    cfg = ChannelConfig(bandwidth_bps=5000, base_latency_s=0.01, jitter_s=0.02)
    runs = []
    for _ in range(2):
        ch = SimulatedChannel(cfg, seed=42)
        runs.append([ch.transmit(512).t_arrive for _ in range(5)])
    assert runs[0] == runs[1]
    ch = SimulatedChannel(cfg, seed=7)
    assert [ch.transmit(512).t_arrive for _ in range(5)] != runs[0]


def test_channel_tick_budget_defers_transmission():
    cfg = ChannelConfig(bandwidth_bps=1e9, base_latency_s=0.0, tick_s=1.0,
                        budget_bits_per_tick=1000)
    ch = SimulatedChannel(cfg)
    assert ch.budget_remaining() == 1000
    ch.transmit(900, t_submit=0.0)
    assert ch.budget_remaining(at=0.0) == 100
    late = ch.transmit(500, t_submit=0.0)   # does not fit tick 0's remainder
    assert late.t_start >= 1.0              # deferred to the next tick


def test_channel_spanning_packet_waits_for_budget_grants():
    """A packet bigger than a whole tick budget drains several ticks and can
    only finish once the tick granting its last bits opens — fast wires do
    not let it tunnel through the cap."""
    cfg = ChannelConfig(bandwidth_bps=1e9, base_latency_s=0.0, tick_s=1.0,
                        budget_bits_per_tick=1000)
    ch = SimulatedChannel(cfg)
    big = ch.transmit(2500, t_submit=0.0)   # spans ticks 0, 1, 2
    assert big.t_arrive >= 2.0
    # ticks 0-2 are spent: the next packet waits for tick 3
    nxt = ch.transmit(1000, t_submit=0.0)
    assert nxt.t_start >= 3.0


# ---------------------------------------------------------------------------
# Rate controller on a fixed, documented RD table
# ---------------------------------------------------------------------------

FIXED_TABLE = [
    RDPoint(OperatingPoint(c=4, bits=2), bits_per_example=1_000, psnr_db=12.0),
    RDPoint(OperatingPoint(c=8, bits=4), bits_per_example=4_000, psnr_db=20.0),
    RDPoint(OperatingPoint(c=8, bits=8), bits_per_example=8_000, psnr_db=26.0),
    RDPoint(OperatingPoint(c=16, bits=8), bits_per_example=16_000, psnr_db=30.0),
]


def test_controller_cheapest_meeting_floor():
    rc = RateController(FIXED_TABLE, quality_floor_db=19.0)
    assert rc.cheapest_meeting_floor().op == OperatingPoint(c=8, bits=4)
    # floor above every point -> best available quality
    rc = RateController(FIXED_TABLE, quality_floor_db=99.0)
    assert rc.cheapest_meeting_floor().op == OperatingPoint(c=16, bits=8)


def test_controller_spends_the_budget_for_quality():
    rc = RateController(FIXED_TABLE, quality_floor_db=19.0)
    # unmetered: best quality point overall
    assert rc.select(None).op == OperatingPoint(c=16, bits=8)
    # generous budget: same
    assert rc.select(20_000).op == OperatingPoint(c=16, bits=8)
    # halved budget: best floor-meeting point that still fits
    assert rc.select(10_000).op == OperatingPoint(c=8, bits=8)
    assert rc.select(5_000).op == OperatingPoint(c=8, bits=4)


def test_controller_degrades_below_floor_rather_than_dropping():
    rc = RateController(FIXED_TABLE, quality_floor_db=19.0)
    # only the sub-floor point fits -> serve it (flagged by its psnr)
    pick = rc.select(2_000)
    assert pick.op == OperatingPoint(c=4, bits=2)
    assert pick.psnr_db < rc.quality_floor_db
    # nothing fits at all -> cheapest overall, never a drop
    assert rc.select(10).op == OperatingPoint(c=4, bits=2)


# ---------------------------------------------------------------------------
# Micro-batcher
# ---------------------------------------------------------------------------

def _req(req_id, c=8, bits=8, h=4, w=4, fill=None):
    fill = req_id if fill is None else fill
    return DecodedRequest(
        req_id=req_id,
        codes=np.full((1, h, w, c), fill % 251, np.uint8),
        mins=np.zeros((1, 1, 1, c), np.float16),
        maxs=np.ones((1, 1, 1, c), np.float16),
        c=c, bits=bits)


def test_bucket_sizes_are_powers_of_two_up_to_cap():
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(6) == (1, 2, 4, 6)
    assert bucket_sizes(1) == (1,)


def test_batcher_flushes_full_groups_and_pads_remainders():
    mb = MicroBatcher(max_batch=4)
    flushed = []
    for i in range(6):
        flushed += mb.add(_req(i))
    assert len(flushed) == 1 and flushed[0].padded_size == 4
    assert flushed[0].pad == 0
    rest = mb.flush()
    assert len(rest) == 1
    assert [r.req_id for r in rest[0].requests] == [4, 5]
    assert rest[0].padded_size == 2 and rest[0].pad == 0
    assert len(mb) == 0


def test_batcher_pads_to_next_bucket():
    mb = MicroBatcher(max_batch=8)
    for i in range(3):
        mb.add(_req(i))
    (b,) = mb.flush()
    assert b.padded_size == 4 and b.pad == 1
    # padding repeats the last row, so restore shapes stay bucketed
    assert np.array_equal(b.codes[3], b.codes[2])


def test_batcher_groups_by_operating_point():
    mb = MicroBatcher(max_batch=8)
    mb.add(_req(0, c=8, bits=8))
    mb.add(_req(1, c=8, bits=4))
    mb.add(_req(2, c=4, bits=8))
    batches = mb.flush()
    assert len(batches) == 3
    assert {b.key.c for b in batches} == {4, 8}


def test_batcher_preserves_request_identity_under_shuffled_arrival(rng):
    mb = MicroBatcher(max_batch=4)
    order = rng.permutation(12)
    batches = []
    for i in order:
        batches += mb.add(_req(int(i)))
    batches += mb.flush()
    seen = {}
    for b in batches:
        for row, req in enumerate(b.requests):
            # each row of the batch is that request's own payload
            assert int(b.codes[row, 0, 0, 0]) == req.req_id % 251
            seen[req.req_id] = True
    assert sorted(seen) == list(range(12))


# ---------------------------------------------------------------------------
# Burst-aware batch windows (EWMA of per-bucket arrival rate)
# ---------------------------------------------------------------------------

def _feed(mb, n, gap, start=0.0):
    """Feed n same-bucket requests spaced ``gap`` apart; returns the open
    group's effective window (deadline - first arrival)."""
    t = start
    for i in range(n):
        mb.add(_req(i), now=t)
        t += gap
    key = _req(0).key
    due, _gen = mb.deadline(key)
    t_first, _ = mb._opened[key]
    return due - t_first


def test_adaptive_window_shrinks_for_bursty_traffic():
    fixed = 0.1
    bursty = MicroBatcher(max_batch=8, window_s=fixed, adaptive=True,
                          min_window_s=0.002)
    steady = MicroBatcher(max_batch=8, window_s=fixed, adaptive=True,
                          min_window_s=0.002)
    w_bursty = _feed(bursty, 3, gap=0.001)
    w_steady = _feed(steady, 3, gap=0.05)
    # burst: the remaining 5 slots are expected within ~5 ms, so the group
    # does not camp on the full 100 ms window
    assert w_bursty < w_steady
    assert w_bursty < fixed / 2
    # sparse-but-steady traffic can never exceed the configured cap
    assert w_steady <= fixed
    assert w_bursty >= 0.002


def test_adaptive_window_tracks_rate_changes_across_groups():
    mb = MicroBatcher(max_batch=8, window_s=1.0, adaptive=True)
    # slow phase: EWMA learns a 0.2 s gap
    w_slow = _feed(mb, 5, gap=0.2)
    mb.flush()
    # fast phase reuses the key's EWMA state and sharpens it downward; the
    # long idle stretch in between is clamped to the window cap, so it
    # cannot swamp the estimate
    w_fast = _feed(mb, 5, gap=0.001, start=10.0)
    assert w_fast < w_slow


def test_adaptive_window_deadline_can_drift_later_within_cap():
    """When traffic decelerates mid-group the deadline moves later (same
    generation) up to the window cap — the gateway re-pushes its flush event
    rather than flushing undersized."""
    mb = MicroBatcher(max_batch=8, window_s=1.0, adaptive=True)
    key = _req(0).key
    # fast opener: two arrivals 1 ms apart -> short expected fill time
    mb.add(_req(0), now=0.0)
    mb.add(_req(1), now=0.001)
    due_fast, gen = mb.deadline(key)
    # then the stream decelerates: 0.1 s gaps dominate the EWMA
    mb.add(_req(2), now=0.101)
    mb.add(_req(3), now=0.201)
    due_slow, gen2 = mb.deadline(key)
    assert gen2 == gen                     # same group, same generation
    assert due_slow > due_fast             # deadline drifted later
    t_first, _ = mb._opened[key]
    assert due_slow <= t_first + 1.0       # never past the hard cap


def test_adaptive_window_needs_cap_and_first_group_uses_it():
    with pytest.raises(ValueError, match="window_s"):
        MicroBatcher(max_batch=4, adaptive=True)
    mb = MicroBatcher(max_batch=4, window_s=0.05, adaptive=True)
    mb.add(_req(0), now=0.0)                   # no gap observed yet
    due, _ = mb.deadline(_req(0).key)
    assert due == pytest.approx(0.05)          # falls back to the fixed cap


def test_fixed_window_behaviour_unchanged():
    mb = MicroBatcher(max_batch=8, window_s=0.1)
    assert _feed(mb, 3, gap=0.001) == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# Gateway end to end (tiny system)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_bank():
    cnn_cfg = smoke_config()._replace(input_size=32)
    data_cfg = smoke_data_config()._replace(image_size=32, batch_size=8)
    params = init_cnn(jax.random.PRNGKey(0), cnn_cfg)
    bank = {}
    for c in (4, 8):
        baf = init_baf_conv(jax.random.PRNGKey(c),
                            BaFConvConfig(c=c, q=cnn_cfg.split_q, hidden=8))
        bank[c] = (baf, np.arange(c))
    imgs, _ = next(shapes_batch_iterator(data_cfg, seed=5))
    return params, bank, np.asarray(imgs)


def test_gateway_round_trips_and_orders_responses(tiny_bank):
    params, bank, imgs = tiny_bank
    gw = ServingGateway(params, bank, default_op=OperatingPoint(c=8, bits=8),
                        max_batch=4)
    responses, tel = gw.serve(imgs)
    assert [r.req_id for r in responses] == list(range(len(imgs)))
    assert all(np.isfinite(r.logits).all() for r in responses)
    assert len(tel) == len(imgs)
    assert tel.summary()["mean_batch_size"] == 4.0


def test_gateway_batched_matches_one_at_a_time(tiny_bank):
    """Micro-batching is an execution detail: logits must match naive serving."""
    params, bank, imgs = tiny_bank
    op = OperatingPoint(c=8, bits=8)
    batched = ServingGateway(params, bank, default_op=op, max_batch=4)
    naive = ServingGateway(params, bank, default_op=op, max_batch=1)
    r_b, _ = batched.serve(imgs)
    r_n, _ = naive.serve(imgs)
    for a, b in zip(r_b, r_n):
        np.testing.assert_allclose(a.logits, b.logits, atol=1e-5, rtol=1e-5)


def test_gateway_fused_restore_matches_reference(tiny_bank):
    params, bank, imgs = tiny_bank
    op = OperatingPoint(c=8, bits=4)
    fused = ServingGateway(params, bank, default_op=op, max_batch=4, fused=True)
    ref = ServingGateway(params, bank, default_op=op, max_batch=4, fused=False)
    r_f, _ = fused.serve(imgs)
    r_r, _ = ref.serve(imgs)
    for a, b in zip(r_f, r_r):
        np.testing.assert_allclose(a.logits, b.logits, atol=1e-5, rtol=1e-5)


def test_gateway_adapts_operating_point_to_channel_budget(tiny_bank):
    """Tight per-tick budget must push the controller to a cheaper (C, bits)."""
    params, bank, imgs = tiny_bank
    table = [
        RDPoint(OperatingPoint(c=4, bits=2), bits_per_example=600, psnr_db=12.0),
        RDPoint(OperatingPoint(c=8, bits=8), bits_per_example=3_000, psnr_db=25.0),
    ]
    rc = RateController(table, quality_floor_db=10.0)
    wide = ServingGateway(
        params, bank, controller=rc,
        channel=SimulatedChannel(ChannelConfig(budget_bits_per_tick=100_000)))
    tight = ServingGateway(
        params, bank, controller=rc,
        channel=SimulatedChannel(ChannelConfig(budget_bits_per_tick=2_000)))
    r_wide, _ = wide.serve(imgs[:2])
    r_tight, _ = tight.serve(imgs[:2])
    assert r_wide[0].op == OperatingPoint(c=8, bits=8)
    assert r_tight[0].op == OperatingPoint(c=4, bits=2)


def test_gateway_telemetry_accounts_wire_and_queue(tiny_bank):
    params, bank, imgs = tiny_bank
    ch = SimulatedChannel(ChannelConfig(bandwidth_bps=1e5, base_latency_s=0.01))
    gw = ServingGateway(params, bank, default_op=OperatingPoint(c=8, bits=8),
                        channel=ch, max_batch=4)
    _, tel = gw.serve(imgs[:4])
    for rec in tel.records:
        assert rec.wire_latency_s > 0.01          # serialization happened
        assert rec.queue_wait_s >= 0.0
        assert rec.total_latency_s >= rec.wire_latency_s + rec.compute_s
    # the shared uplink serializes: later requests waited longer on the wire
    lat = [r.wire_latency_s for r in sorted(tel.records, key=lambda r: r.req_id)]
    assert lat[-1] > lat[0]


# ---------------------------------------------------------------------------
# Entropy-coded serving (rANS) + true-byte accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket", [1, 2, 4, 8])
def test_gateway_rans_backend_matches_zlib_logits(tiny_bank, bucket):
    """The entropy coder is lossless: served logits equal the uncoded path
    (the plan's quantizer, then restore and the cloud forward, with no
    bytes in between) at every micro-batch bucket."""
    params, bank, imgs = tiny_bank
    op = OperatingPoint(c=8, bits=8)
    gw = ServingGateway(params, bank, default_op=op, max_batch=bucket)
    responses, tel = gw.serve(imgs[:bucket])
    assert [r.padded_size for r in tel.records] == [bucket] * bucket
    plan = gw.plan_for(op)
    refs = [plan.quantize(gw._edge_fn(params, imgs[i:i + 1]))
            for i in range(bucket)]
    uncoded = DecodedBatch(*(np.concatenate([r[k] for r in refs])
                             for k in range(3)))
    logits = np.asarray(gw._cloud_fn(params, plan.restore(uncoded)))
    for i, r in enumerate(responses):
        np.testing.assert_allclose(r.logits, logits[i], atol=1e-5,
                                   rtol=1e-5)


def test_gateway_downgrades_unsupported_backend(tiny_bank):
    """A gateway that decodes at most 6 bits re-bases an 8-bit operating
    point onto 6 bits at negotiation time — before any bytes are encoded."""
    params, bank, imgs = tiny_bank
    gw = ServingGateway(
        params, bank, default_op=OperatingPoint(c=8, bits=8),
        capabilities=Capabilities(max_bits=6), max_batch=2)
    responses, _ = gw.serve(imgs[:2])
    assert responses[0].op == OperatingPoint(c=8, bits=6)
    # and the served logits match a gateway built at 6 bits
    ref = ServingGateway(params, bank,
                         default_op=OperatingPoint(c=8, bits=6), max_batch=2)
    r_ref, _ = ref.serve(imgs[:2])
    for a, b in zip(responses, r_ref):
        np.testing.assert_allclose(a.logits, b.logits, atol=1e-5, rtol=1e-5)


def test_gateway_refuses_without_downgrade(tiny_bank):
    params, bank, imgs = tiny_bank
    with pytest.raises(NegotiationError):
        ServingGateway(
            params, bank, default_op=OperatingPoint(c=8, bits=8),
            capabilities=Capabilities(max_bits=6, downgrade=False))
    with pytest.raises(NegotiationError, match="profile"):
        ServingGateway(params, bank,
                       default_op=OperatingPoint(c=8, bits=8, profile=1),
                       capabilities=Capabilities())


def test_multi_tenant_adaptive_window_serves_bursts(tiny_bank):
    """Burst-aware windows must not drop or reorder anything; a bursty
    workload under adaptive windows serves bit-identically to fixed."""
    params, bank, imgs = tiny_bank

    def make(adaptive):
        return MultiTenantGateway(
            params, bank, tenants=[TenantSpec("a"), TenantSpec("b")],
            channel_cfg=ChannelConfig(bandwidth_bps=20e6,
                                      base_latency_s=0.002),
            default_op=OperatingPoint(c=8, bits=8), max_batch=4,
            tick_s=0.01, batch_window_s=0.05, adaptive_window=adaptive)

    # two bursts then a straggler
    work = [TenantRequest("ab"[i % 2], imgs[i % len(imgs)],
                          t_submit=0.0005 * i) for i in range(6)]
    work += [TenantRequest("a", imgs[0], t_submit=2.0)]
    r_ad, tel_ad = make(True).serve_tenants(work)
    r_fx, _ = make(False).serve_tenants(work)
    assert len(r_ad["a"]) == 4 and len(r_ad["b"]) == 3
    for t in ("a", "b"):
        for x, y in zip(r_ad[t], r_fx[t]):
            np.testing.assert_allclose(x.logits, y.logits,
                                       atol=1e-5, rtol=1e-5)


def test_gateway_meters_actual_container_bytes(tiny_bank):
    """Channel occupancy and telemetry must reflect the serialized container
    length exactly — not the payload+side-info estimate."""
    params, bank, imgs = tiny_bank
    ch = SimulatedChannel(ChannelConfig(bandwidth_bps=1e6))
    gw = ServingGateway(params, bank, default_op=OperatingPoint(c=8, bits=8),
                        channel=ch, max_batch=4)
    op, blob, stats, tx = gw.encode_request(imgs[:1], 0.0)
    assert tx.bits == 8 * blob.nbytes == stats.wire_bits
    assert stats.wire_bits > stats.total_bits      # header is on the wire too
    _, tel = gw.serve(imgs[:4])
    for rec in tel.records:
        assert rec.bits_on_wire > 0
        assert rec.bits_on_wire % 8 == 0
