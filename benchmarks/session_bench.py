"""Streaming session benchmark: temporal P-frame compression + lossy recovery.

    PYTHONPATH=src python benchmarks/session_bench.py [--smoke]

Part 1 (temporal coding) streams synthetic correlated camera frames through
the real edge network and the session codec twice — temporal (I+P) and
forced I-only — and measures wire bits. Both paths must decode to
*bit-identical* quantized codes (matched restore quality by construction;
the comparison is wire bits at equal output). Acceptance gates (ISSUE 8):

  * mean P-frame wire bits <= 0.7x mean I-frame wire bits,
  * whole-session I-only bits / (I+P) bits >= 1.4x.

Part 2 (lossy streaming) drives concurrent sessions through a
MultiTenantGateway via SessionManager over seeded 5%-loss channels with
corruption and reorder, on a deterministic LinearCostModel. Gates:

  * every session ends in sync (SessionManager.run asserts it),
  * max desync-to-resync recovery <= 2x the analytic single-cycle bound
    (recovery_bound_s; the 2x absorbs loss-chained NACK cycles at 5%),
  * a second run is bit-identical (StreamReport.signature equality) — the
    full loss + reorder + NACK + QoS pipeline replays deterministically.

Part 3 (QoS) repeats the workload under a tight admission policy and
reports degrade-before-shed behaviour: ladder step-downs happen (and are
metered separately from sheds), and no frame is shed above the floor rung.

Prints ``name,us_per_call,derived`` CSV rows like the other benchmarks and
writes a schema'd BENCH_session.json (repro.obs.bench) for compare.py.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.configs.yolo_baf import smoke_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.data.synthetic import correlated_frames
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import init_cnn
from repro.obs.bench import bench_record, metric, write_bench
from repro.pipeline import Capabilities, OperatingPoint
from repro.serve import (ChannelConfig, LinearCostModel, MultiQueueExecutor,
                         MultiTenantGateway, QueueDepthAdmission, TenantSpec)
from repro.session import (QosLevel, SessionConfig, SessionDecoder,
                           SessionEncoder, SessionManager, SessionSpec)
from repro.session.recovery import RecoveryConfig, recovery_bound_s

C = 8
OP = OperatingPoint(c=C, bits=6, backend="rans")
LADDER = (QosLevel(OP),
          QosLevel(OperatingPoint(c=C, bits=4, backend="rans"),
                   keyframe_interval=8),
          QosLevel(OperatingPoint(c=4, bits=4, backend="rans"),
                   keyframe_interval=8, frame_stride=2))
FPS = 20.0

_ROWS: list[str] = []


def _row(name: str, us: float, derived: str):
    line = f"{name},{us:.1f},{derived}"
    _ROWS.append(line)
    print(line, flush=True)


# Fixed-camera clip parameters: sub-pixel jitter (drift * SIZE ~ 0.13 px per
# frame) plus mild sensor noise. Whole-pixel motion decorrelates the conv
# latent badly (no motion compensation in the codec — see docs/STREAMING.md),
# so this is the workload temporal delta coding is built for; SIZE=64 keeps
# the latent large enough that per-frame container overhead is amortized.
SIZE = 64
DRIFT = 0.002
NOISE = 0.003


def _clip(n_frames: int, seed: int) -> np.ndarray:
    return correlated_frames(n_frames, image_size=SIZE, drift=DRIFT,
                             noise=NOISE, seed=seed)


def build_system(input_size: int = SIZE):
    cnn_cfg = smoke_config()._replace(input_size=input_size)
    params = init_cnn(jax.random.PRNGKey(0), cnn_cfg)
    bank = {c: (init_baf_conv(jax.random.PRNGKey(c),
                              BaFConvConfig(c=c, q=cnn_cfg.split_q,
                                            hidden=8)),
                np.arange(c)) for c in (4, C)}
    return params, bank


def mk_gateway(params, bank, *, n_sessions, admission=None, cost=None):
    tenants = [TenantSpec(name=f"cam{i}", priority=i % 2)
               for i in range(n_sessions)]
    return MultiTenantGateway(
        params, bank, tenants=tenants,
        executor=MultiQueueExecutor(
            2, cost=cost or LinearCostModel(0.002, 0.0005)),
        admission=admission, max_batch=8, batch_window_s=0.01)


# ---------------------------------------------------------------------------
# Part 1: temporal coding vs I-only at matched restore quality
# ---------------------------------------------------------------------------

def bench_temporal_coding(params, bank, gw, *, n_frames: int) -> dict:
    clip = _clip(n_frames, seed=77)
    cfg = SessionConfig(session_id=0, levels=(gw._fit_op(OP),))
    enc = SessionEncoder(cfg, gw.plan_for)
    enc_ionly = SessionEncoder(
        SessionConfig(session_id=0, levels=(gw._fit_op(OP),)), gw.plan_for,
        capabilities=Capabilities(session_profiles=(), downgrade=True))
    dec = SessionDecoder(cfg, gw.plan_for)
    i_bits, p_bits, ionly_bits = [], [], []
    t0 = time.perf_counter()
    for idx in range(n_frames):
        z = gw._edge_fn(gw.params, np.asarray(clip[idx])[None])
        blob, meta = enc.encode(z)
        blob_i, meta_i = enc_ionly.encode(z)
        assert meta_i.intra
        (i_bits if meta.intra else p_bits).append(meta.wire_bits)
        ionly_bits.append(meta_i.wire_bits)
        # matched restore quality: both paths must reconstruct the exact
        # same quantized codes (temporal prediction is lossless)
        decoded, _ = dec.decode(blob)
        dec_i = SessionDecoder(cfg, gw.plan_for)
        decoded_i, _ = dec_i.decode(blob_i)
        assert np.array_equal(decoded.codes, decoded_i.codes), idx
    wall = time.perf_counter() - t0
    p_over_i = float(np.mean(p_bits) / np.mean(i_bits))
    reduction = float(sum(ionly_bits) / (sum(i_bits) + sum(p_bits)))
    _row("session_temporal", 1e6 * wall / n_frames,
         f"p_over_i={p_over_i:.3f} reduction_vs_ionly={reduction:.2f}x "
         f"n_p={len(p_bits)}")
    assert p_over_i <= 0.7, (
        f"ACCEPTANCE FAIL: P-frame wire bits {p_over_i:.3f}x of I-frame, "
        f"above the 0.7x gate")
    assert reduction >= 1.4, (
        f"ACCEPTANCE FAIL: session wire-bit reduction {reduction:.2f}x vs "
        f"I-only, below the 1.4x gate")
    return {"p_over_i_wire_ratio": p_over_i,
            "reduction_vs_ionly": reduction,
            "i_frame_bits_mean": float(np.mean(i_bits)),
            "p_frame_bits_mean": float(np.mean(p_bits)),
            "frames": n_frames}


# ---------------------------------------------------------------------------
# Part 2: lossy streaming — bounded recovery + deterministic replay
# ---------------------------------------------------------------------------

def bench_lossy_streaming(params, bank, *, n_sessions: int,
                          n_frames: int) -> dict:
    gw = mk_gateway(params, bank, n_sessions=n_sessions)
    sessions = [SessionSpec(name=f"cam{i}", fps=FPS, start_s=0.002 * i)
                for i in range(n_sessions)]
    mgr = SessionManager(
        gw, sessions, ladder=LADDER,
        channel_cfg=ChannelConfig(bandwidth_bps=20e6, base_latency_s=0.005,
                                  loss_p=0.05, corrupt_p=0.02,
                                  reorder_p=0.02, reorder_delay_s=0.01,
                                  mtu_bytes=256),
        recovery=RecoveryConfig(nack_latency_s=0.01), seed=3)
    frames = {f"cam{i}": _clip(n_frames, seed=10 + i)
              for i in range(n_sessions)}
    t0 = time.perf_counter()
    _, report = mgr.run(frames)          # asserts every session ends in sync
    wall = time.perf_counter() - t0
    _, report2 = mgr.run(frames)
    replay_ok = report.signature() == report2.signature()

    total = n_sessions * n_frames
    outcomes: dict[str, int] = {}
    for name in frames:
        for k, v in report.counts(name).items():
            outcomes[k] = outcomes.get(k, 0) + v
    bound = recovery_bound_s(fps=FPS, uplink_latency_s=0.02,
                             nack_latency_s=0.01, margin_frames=2)
    max_rec = max(r.max_recovery_s for r in report.recovery.values())
    episodes = sum(r.episodes for r in report.recovery.values())
    nacks = sum(report.nacks.values())
    _row("session_lossy", 1e6 * wall / total,
         f"sessions={n_sessions} outcomes={outcomes} episodes={episodes} "
         f"nacks={nacks} max_recovery={max_rec * 1e3:.1f}ms "
         f"bound={bound * 1e3:.0f}ms replay={replay_ok}")
    assert outcomes.get("lost", 0) + outcomes.get("corrupt", 0) > 0, (
        "ACCEPTANCE FAIL: seeded lossy run exercised no impairment")
    assert max_rec <= 2 * bound, (
        f"ACCEPTANCE FAIL: recovery {max_rec:.3f}s exceeds 2x analytic "
        f"bound {bound:.3f}s")
    assert replay_ok, "ACCEPTANCE FAIL: lossy streaming replay diverged"
    return {"sessions": n_sessions, "frames_per_session": n_frames,
            "outcomes": outcomes, "desync_episodes": episodes,
            "nacks": nacks, "max_recovery_s": max_rec,
            "recovery_bound_s": bound,
            "served_fraction": outcomes.get("served", 0) / total,
            "replay_bit_identical": replay_ok, "wall_s": wall}


# ---------------------------------------------------------------------------
# Part 3: QoS — degrade before shed under pressure
# ---------------------------------------------------------------------------

def bench_qos_degrade(params, bank, *, n_sessions: int,
                      n_frames: int) -> dict:
    # a deliberately slow cloud (batches cost >> the 50 ms frame interval)
    # so the executor backlog trips the depth-1 admission gate and forces
    # the manager down the QoS ladder
    gw = mk_gateway(params, bank, n_sessions=n_sessions,
                    admission=QueueDepthAdmission(1),
                    cost=LinearCostModel(0.12, 0.01))
    sessions = [SessionSpec(name=f"cam{i}", fps=FPS, start_s=0.001 * i)
                for i in range(n_sessions)]
    mgr = SessionManager(
        gw, sessions, ladder=LADDER,
        channel_cfg=ChannelConfig(bandwidth_bps=20e6, base_latency_s=0.005),
        recovery=RecoveryConfig(nack_latency_s=0.01), seed=5)
    frames = {f"cam{i}": _clip(n_frames, seed=30 + i)
              for i in range(n_sessions)}
    _, report = mgr.run(frames)
    tel = report.telemetry
    floor = len(LADDER) - 1
    shed_above_floor = sum(
        1 for name in frames for f in report.frames[name]
        if f.outcome == "shed" and f.level < floor)
    degraded = len(tel.degraded)
    _row("session_qos", 0.0,
         f"degraded={degraded} shed={len(tel.shed)} served={len(tel)} "
         f"shed_above_floor={shed_above_floor}")
    assert degraded > 0, (
        "ACCEPTANCE FAIL: pressure run triggered no QoS degradation")
    assert shed_above_floor == 0, (
        f"ACCEPTANCE FAIL: {shed_above_floor} frames shed above the ladder "
        f"floor — degrade-before-shed violated")
    return {"degraded": degraded, "shed": len(tel.shed), "served": len(tel),
            "degrade_by_tenant": tel.degrade_by_tenant()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (< 60 s)")
    args = ap.parse_args()
    enable_compile_cache()
    n_sessions = 4 if args.smoke else 12
    n_frames = 24 if args.smoke else 60

    params, bank = build_system()
    gw = mk_gateway(params, bank, n_sessions=1)

    temporal = bench_temporal_coding(params, bank, gw,
                                     n_frames=n_frames)
    lossy = bench_lossy_streaming(params, bank, n_sessions=n_sessions,
                                  n_frames=n_frames)
    qos = bench_qos_degrade(params, bank, n_sessions=n_sessions,
                            n_frames=max(8, n_frames // 2))

    rec = bench_record(
        "session",
        config={"smoke": bool(args.smoke), "sessions": n_sessions,
                "frames": n_frames, "image_size": SIZE, "drift": DRIFT,
                "noise": NOISE},
        metrics={
            # trajectory gates: seeded + virtual-clocked, so these are
            # deterministic across runs of one commit
            "p_over_i_wire_ratio": metric(
                temporal["p_over_i_wire_ratio"], better="lower",
                tolerance=0.05),
            "reduction_vs_ionly": metric(
                temporal["reduction_vs_ionly"], better="higher",
                tolerance=0.05),
            "max_recovery_vs_bound": metric(
                lossy["max_recovery_s"] / lossy["recovery_bound_s"],
                better="lower", tolerance=0.25),
            "served_fraction_at_5pct_loss": metric(
                lossy["served_fraction"], better="higher", tolerance=0.1),
            "desync_episodes": metric(
                lossy["desync_episodes"], better="lower", tolerance=0.5),
            # wall time is runner-dependent: informational only
            "lossy_wall_s": metric(lossy["wall_s"], better="lower",
                                   tolerance=None),
        },
        raw={"temporal": temporal, "lossy": lossy, "qos": qos})
    out = os.path.join(os.path.dirname(__file__), "BENCH_session.json")
    write_bench(out, rec)
    print(f"wrote {out}")
    print("session gates OK")


if __name__ == "__main__":
    main()
