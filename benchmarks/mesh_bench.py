"""Sharded cloud tier benchmark: gateway federation on a MeshExecutor.

    PYTHONPATH=src python benchmarks/mesh_bench.py [--smoke]

Forces an 8-device host mesh (XLA_FLAGS, set before jax imports) and runs a
federated multi-gateway workload — smoke: 2 gateways x 32 tenants (64
tenants total), full: 4 gateways x 64 tenants (256 tenants) — through the
same shared cloud executor twice:

  serial : SerialExecutor, the single-core cloud of previous releases
  mesh   : MeshExecutor over make_dev_mesh(prefer="data") — batched decode
           on the host, restore + cloud forward under shard_map with
           batch-axis data parallelism

Both runs price virtual service time with ONE frozen CalibratedCostModel,
fit from measured warm compute on the serial tier (least squares over
(padded_size, wall_s) samples, seeded from the launch/hlo_cost roofline).
The mesh executor evaluates the same model at its per-shard row count, so
the speedup is the cost model's own prediction of data parallelism — and
because the model is frozen, every run replays bit for bit.

Acceptance gates (ISSUE 7):
  * calibration: fitted per-item cost within 25% of measured wall
    (mean relative error over the warm samples),
  * mesh logits bit-identical to serial, per tenant, per request,
  * mesh replay bit-identical (logits + telemetry),
  * mesh virtual-cloud throughput >= 1.8x serial at 64+ federated tenants,
  * overload: per-gateway admission on the shared mesh — every submission
    ends as exactly one response or one explicit shed, never silent.

Writes a schema'd BENCH_mesh.json (repro.obs.bench) for compare.py.
"""
from __future__ import annotations

import argparse
import math
import os
import time

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_FLAG}".strip()

import jax
import numpy as np

from repro.configs.yolo_baf import smoke_config, smoke_data_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.data.synthetic import shapes_batch_iterator
from repro.launch.chips import chip_peaks
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_dev_mesh
from repro.models.cnn import init_cnn
from repro.obs.bench import bench_record, metric, write_bench
from repro.serve import (CalibratedCostModel, ChannelConfig,
                         GatewayFederation, MeshExecutor, MultiTenantGateway,
                         OperatingPoint, QueueDepthAdmission, SerialExecutor,
                         TenantRequest, TenantSpec, seed_cost_from_hlo)

C = 8
OP = OperatingPoint(c=C, bits=8)
BUCKET = 64
# backlogged uplink: arrivals must not dominate the executor makespan, or
# the rps ratio measures the wire, not the mesh
CHANNEL = ChannelConfig(bandwidth_bps=1e9, base_latency_s=1e-3)

_ROWS: list[str] = []


def _row(name: str, us: float, derived: str):
    line = f"{name},{us:.1f},{derived}"
    _ROWS.append(line)
    print(line, flush=True)


def build_system(input_size: int = 32):
    cnn_cfg = smoke_config()._replace(input_size=input_size)
    data_cfg = smoke_data_config()._replace(image_size=input_size,
                                            batch_size=8)
    params = init_cnn(jax.random.PRNGKey(0), cnn_cfg)
    baf = init_baf_conv(jax.random.PRNGKey(1),
                        BaFConvConfig(c=C, q=cnn_cfg.split_q, hidden=8))
    return params, {C: (baf, np.arange(C))}, data_cfg


def image_pool(data_cfg, n: int = 16) -> np.ndarray:
    it = shapes_batch_iterator(data_cfg, seed=123)
    rows = []
    while len(rows) < n:
        img, _ = next(it)
        rows.append(np.asarray(img))
    return np.concatenate(rows, axis=0)[:n]


def mk_gateway(system, executor, *, seed, n_tenants, max_batch=BUCKET,
               admission=None, batch_window_s=None):
    params, bank, _ = system
    tenants = [TenantSpec(name=f"g{seed}t{i}") for i in range(n_tenants)]
    return MultiTenantGateway(params, bank, tenants=tenants, default_op=OP,
                              channel_cfg=CHANNEL, max_batch=max_batch,
                              batch_window_s=batch_window_s,
                              executor=executor, shared_executor=True,
                              seed=seed, admission=admission)


def workload(gw, imgs, per_tenant: int, *, dt=1e-5, t0=0.0):
    """Round-robin over the gateway's tenants, backlogged (dt apart)."""
    names = sorted(gw.specs)
    reqs = []
    for r in range(per_tenant):
        for i, name in enumerate(names):
            k = r * len(names) + i
            reqs.append(TenantRequest(tenant=name,
                                      img=imgs[k % len(imgs)][None],
                                      t_submit=t0 + k * dt))
    return reqs


# ---------------------------------------------------------------------------
# calibration: measure warm serial compute, fit, freeze
# ---------------------------------------------------------------------------

def calibrate(system, imgs) -> CalibratedCostModel:
    """Warm the serial tier across every bucket size, then fit an affine
    cost from warm (padded_size, wall_s) samples; seeded from the
    launch/hlo_cost roofline so even a degenerate sample set has a slope."""
    params, bank, _ = system
    warm_ex = SerialExecutor()                       # MeasuredCost
    gw = mk_gateway(system, warm_ex, seed=0, n_tenants=1,
                    batch_window_s=0.005)
    sizes = [1, 2, 4, 8, 16, 32, 64]
    bursts = []
    t = 0.0
    for s in sizes:                                   # one bucket per burst
        for i in range(s):
            bursts.append(TenantRequest(tenant="g0t0",
                                        img=imgs[i % len(imgs)][None],
                                        t_submit=t + i * 1e-5))
        t += 1.0
    gw.serve_tenants(bursts)                          # compile pass

    plan = gw.plan_for(gw.default_op)
    codes_hw = plan.decode_batch(
        [gw.encode_request(imgs[0][None])[1]]).codes.shape[1:3]
    # the seed prices the program on the chip the tier is deployed on, not
    # on the host devices this benchmark runs
    calib = seed_cost_from_hlo(plan, (BUCKET, *codes_hw, C),
                               peaks=chip_peaks("TPU v5 lite"))
    _row("hlo_roofline_seed", calib.seed_per_item_s * 1e6, "us_per_item")

    warm_ex.cost = calib                              # warm measured passes
    for _ in range(3):                                # 3x per size: average
        gw.serve_tenants(bursts)                      # out host timing noise
    calib.freeze()
    _row("calibrated_base", calib.base_s * 1e6, "us")
    _row("calibrated_per_item", calib.per_item_s * 1e6, "us")
    rel_err = calib.fit_rel_err()
    _row("calibration_fit_rel_err", rel_err * 1e6, f"{rel_err:.3f}")
    assert rel_err < 0.25, (
        f"ACCEPTANCE FAIL: calibrated cost {rel_err:.1%} off measured wall "
        f"(gate < 25%) over {len(calib.samples)} samples")
    return calib


# ---------------------------------------------------------------------------
# federated runs
# ---------------------------------------------------------------------------

def virtual_rps(executor, n_served: int) -> float:
    hist = executor.history
    span = max(t.t_done for t in hist) - min(t.t_start for t in hist)
    return n_served / span


def logit_rows(results):
    return [{t: [np.asarray(r.logits) for r in rs]
             for t, rs in out.items()} for out, _ in results]


def run_federation(system, imgs, executor, *, n_gateways, n_tenants,
                   per_tenant):
    gws = [mk_gateway(system, executor, seed=g, n_tenants=n_tenants)
           for g in range(n_gateways)]
    fed = GatewayFederation(gws)
    wls = [workload(gw, imgs, per_tenant) for gw in gws]
    t0 = time.perf_counter()
    results = fed.serve(wls)
    wall = time.perf_counter() - t0
    n = sum(len(w) for w in wls)
    assert all(not tel.shed for _, tel in results)
    return fed, wls, results, virtual_rps(executor, n), wall


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: 2 gateways x 32 tenants")
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    assert n_dev == 8, f"expected the forced 8-device host mesh, got {n_dev}"
    n_gateways, n_tenants = (2, 32) if args.smoke else (4, 64)
    per_tenant = 2 * BUCKET // n_tenants              # 2 full buckets/gateway
    n_requests = n_gateways * n_tenants * per_tenant
    print(f"mesh_bench: {n_gateways} gateways x {n_tenants} tenants x "
          f"{per_tenant} reqs = {n_requests} requests on {n_dev} devices",
          flush=True)

    system = build_system()
    imgs = image_pool(system[2])
    calib = calibrate(system, imgs)

    # -- serial baseline ----------------------------------------------------
    ser_ex = SerialExecutor(cost=calib)
    _, _, ser_results, ser_rps, ser_wall = run_federation(
        system, imgs, ser_ex, n_gateways=n_gateways, n_tenants=n_tenants,
        per_tenant=per_tenant)
    _row("serial_virtual_rps", 1e6 / ser_rps, f"{ser_rps:.0f}_rps")

    # -- mesh ---------------------------------------------------------------
    mesh_ex = MeshExecutor(make_dev_mesh(prefer="data"), cost=calib)
    fed_m, wls_m, mesh_results, mesh_rps, mesh_wall = run_federation(
        system, imgs, mesh_ex, n_gateways=n_gateways, n_tenants=n_tenants,
        per_tenant=per_tenant)
    _row("mesh_virtual_rps", 1e6 / mesh_rps, f"{mesh_rps:.0f}_rps")

    speedup = mesh_rps / ser_rps
    _row("mesh_speedup", speedup * 1e6, f"{speedup:.2f}x")
    assert speedup >= 1.8, (
        f"ACCEPTANCE FAIL: mesh {speedup:.2f}x serial virtual-cloud rps "
        f"at {n_gateways * n_tenants} tenants (gate >= 1.8x)")

    # -- bit-identity: mesh == serial, per tenant, per request --------------
    for gs, gm in zip(logit_rows(ser_results), logit_rows(mesh_results)):
        assert gs.keys() == gm.keys()
        for t in gs:
            assert len(gs[t]) == per_tenant
            for a, b in zip(gs[t], gm[t]):
                assert np.array_equal(a, b), (
                    f"ACCEPTANCE FAIL: tenant {t} mesh logits != serial")
    print("mesh logits bit-identical to serial: ok", flush=True)

    # -- deterministic replay under the frozen cost model -------------------
    replay = fed_m.serve(wls_m)
    for (o1, t1), (o2, t2) in zip(mesh_results, replay):
        assert t1.records == t2.records, "ACCEPTANCE FAIL: replay telemetry"
        r1, r2 = logit_rows([(o1, t1)])[0], logit_rows([(o2, t2)])[0]
        for t in r1:
            for a, b in zip(r1[t], r2[t]):
                assert np.array_equal(a, b), (
                    "ACCEPTANCE FAIL: replay logits drifted")
    print("mesh replay bit-identical: ok", flush=True)

    # -- overload: per-gateway admission against the shared mesh ------------
    # a bursty gateway fills the shared executor; a depth-limited gateway
    # sheds its own overflow while the burst gateway rides through
    over_ex = MeshExecutor(make_dev_mesh(prefer="data"), cost=calib)
    gw_burst = mk_gateway(system, over_ex, seed=0, n_tenants=4, max_batch=8)
    gw_lim = mk_gateway(system, over_ex, seed=1, n_tenants=4, max_batch=8,
                        admission=QueueDepthAdmission(1))
    wl_burst = workload(gw_burst, imgs, 8, dt=1e-4)
    wl_lim = workload(gw_lim, imgs, 8, dt=1e-4, t0=0.003)
    (out_b, tel_b), (out_l, tel_l) = GatewayFederation(
        [gw_burst, gw_lim]).serve([wl_burst, wl_lim])
    served = sum(len(t) for t in (tel_b, tel_l))
    shed = len(tel_b.shed) + len(tel_l.shed)
    assert served + shed == len(wl_burst) + len(wl_lim), (
        "ACCEPTANCE FAIL: silent drop under overload")
    assert not tel_b.shed, "burst gateway has no admission policy"
    assert tel_l.shed, ("expected the depth-limited gateway to shed against "
                        "the shared-mesh backlog")
    _row("overload_shed", shed * 1e6, f"{shed}_of_{len(wl_lim)}")
    print(f"overload: {served} served + {shed} shed, zero silent drops",
          flush=True)

    # -- record -------------------------------------------------------------
    rec = bench_record(
        "mesh_bench",
        config={"smoke": bool(args.smoke), "devices": n_dev,
                "gateways": n_gateways, "tenants_per_gateway": n_tenants,
                "per_tenant": per_tenant, "bucket": BUCKET, "c": C,
                "bits": 8},
        metrics={
            # the calibrated coefficients are measured, so run-to-run ratios
            # wobble; the hard >= 1.8x gate lives in this script, the
            # trajectory gate only catches collapses
            "mesh_speedup": metric(speedup, better="higher", tolerance=0.5),
            "mesh_virtual_rps": metric(mesh_rps, better="higher"),
            "serial_virtual_rps": metric(ser_rps, better="higher"),
            "calibration_fit_rel_err": metric(calib.fit_rel_err(),
                                              better="lower"),
            "calibrated_per_item_us": metric(calib.per_item_s * 1e6,
                                             better="lower"),
            "serial_wall_s": metric(ser_wall, better="lower"),
            "mesh_wall_s": metric(mesh_wall, better="lower"),
            "overload_shed": metric(shed, better="lower"),
        },
        raw={"rows": _ROWS})
    out = os.path.join(os.path.dirname(__file__), "BENCH_mesh.json")
    write_bench(out, rec)
    print(f"wrote {out}", flush=True)
    print("mesh_bench: all acceptance gates passed", flush=True)


if __name__ == "__main__":
    main()
