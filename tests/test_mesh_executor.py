"""MeshExecutor (the sharded cloud tier), CalibratedCostModel, and gateway
federation.

The bit-identity tests compare batch shapes within one XLA CPU float
equivalence class (per-row results are bit-identical within {1, 2, 4} and
within {8, 16, 32, 64} on the host backend); the gateway tests use full
64-row buckets so serial and per-shard shapes land in the same class for
any device count up to 8. CI runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on a single device
the mesh degenerates to (data=1, model=1) and still must agree.
"""
import math
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs.yolo_baf import smoke_config, smoke_data_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.data.synthetic import shapes_batch_iterator
from repro.launch.chips import CHIP_PEAKS, chip_peaks
from repro.launch.mesh import make_dev_mesh, make_mesh
from repro.models.cnn import init_cnn
from repro.serve import (CalibratedCostModel, GatewayFederation,
                         LinearCostModel, MeshExecutor, MultiTenantGateway,
                         OperatingPoint, QueueDepthAdmission, RequestShed,
                         SerialExecutor, ServingGateway, TenantRequest,
                         TenantSpec, seed_cost_from_hlo, serve_federated)

N_DEV = len(jax.devices())
too_many_devices = pytest.mark.skipif(
    N_DEV > 8, reason="batch-shape float classes validated for <= 8 devices")


# ---------------------------------------------------------------------------
# make_dev_mesh axis preference
# ---------------------------------------------------------------------------

def test_make_dev_mesh_data_preference():
    m = make_dev_mesh(prefer="data")
    assert m.shape["data"] == N_DEV
    assert m.shape["model"] == 1


def test_make_dev_mesh_default_shape_unchanged():
    m = make_dev_mesh()
    model = next(f for f in (4, 2, 1) if N_DEV % f == 0)
    assert dict(m.shape) == {"data": N_DEV // model, "model": model}


def test_make_dev_mesh_rejects_unknown_preference():
    with pytest.raises(ValueError, match="prefer"):
        make_dev_mesh(prefer="pod")


# ---------------------------------------------------------------------------
# CalibratedCostModel: calibrate -> freeze -> replay
# ---------------------------------------------------------------------------

def _b(n):
    return SimpleNamespace(padded_size=n, key=None)


def test_calibrating_model_passes_through_and_records():
    m = CalibratedCostModel()
    assert m.duration_s(_b(4), 0.125) == 0.125
    assert m.samples == [(4, 0.125)]
    assert not m.frozen


def test_freeze_fits_exact_affine():
    m = CalibratedCostModel()
    for n in (1, 2, 4, 8, 16):
        m.observe(n, 0.007 + 0.003 * n)
    m.freeze()
    assert m.base_s == pytest.approx(0.007)
    assert m.per_item_s == pytest.approx(0.003)
    assert m.fit_rel_err() == pytest.approx(0.0, abs=1e-9)
    # frozen: pure function of padded_size, measured wall is ignored
    assert m.duration_s(_b(10), 123.0) == pytest.approx(0.037)
    assert m.duration_s(_b(10), 456.0) == m.duration_s(_b(10), 0.0)


def test_freeze_is_idempotent_and_locks_observation():
    m = CalibratedCostModel()
    m.observe(4, 0.01)
    assert m.freeze() is m
    m.freeze()
    with pytest.raises(RuntimeError):
        m.observe(4, 0.01)
    n_samples = len(m.samples)
    m.duration_s(_b(4), 0.5)           # predicts, must not record
    assert len(m.samples) == n_samples


def test_degenerate_single_size_keeps_seed_slope():
    m = CalibratedCostModel(seed_per_item_s=0.001)
    for wall in (0.018, 0.020, 0.022):
        m.observe(8, wall)
    m.freeze()
    assert m.per_item_s == 0.001
    assert m.base_s == pytest.approx(0.020 - 0.008)


def test_fit_clamps_negative_slope():
    m = CalibratedCostModel()
    m.observe(1, 0.02)
    m.observe(16, 0.01)                # decreasing: slope would be negative
    m.freeze()
    assert m.per_item_s == 0.0
    assert m.base_s >= 0.0


def test_freeze_without_samples_keeps_seeds():
    m = CalibratedCostModel(seed_base_s=0.005, seed_per_item_s=0.002)
    m.freeze()
    assert (m.base_s, m.per_item_s) == (0.005, 0.002)


def test_negative_seeds_rejected():
    with pytest.raises(ValueError):
        CalibratedCostModel(seed_base_s=-1.0)


# ---------------------------------------------------------------------------
# MeshExecutor: construction + per-shard virtual clock
# ---------------------------------------------------------------------------

def test_mesh_executor_refuses_unfrozen_calibration():
    with pytest.raises(ValueError, match="frozen"):
        MeshExecutor(cost=CalibratedCostModel())


def test_mesh_executor_requires_data_axis():
    mesh = make_mesh((1, 1), ("pod", "model"))
    with pytest.raises(ValueError, match="data"):
        MeshExecutor(mesh=mesh)


def test_plan_duration_is_per_shard():
    cal = CalibratedCostModel(seed_base_s=0.005, seed_per_item_s=0.001)
    ex = MeshExecutor(cost=cal.freeze(), overhead_s=0.002)
    n = ex.n_data
    assert n == N_DEV
    assert ex.shard_rows(1) == 1
    assert ex.shard_rows(64) == math.ceil(64 / n)
    want = 0.002 + 0.005 + 0.001 * math.ceil(64 / n)
    assert ex._plan_duration(_b(64), 999.0) == pytest.approx(want)


def test_run_sharded_refuses_weightless_plan():
    ex = MeshExecutor(cost=LinearCostModel())
    plan = SimpleNamespace(spec=SimpleNamespace(params=None, baf_params=None))
    with pytest.raises(ValueError, match="weights"):
        ex.run_sharded(plan, None, 4)


# ---------------------------------------------------------------------------
# sharded compute: bit-identical to the serial path
# ---------------------------------------------------------------------------

C = 8
OP = OperatingPoint(c=C, bits=8)


@pytest.fixture(scope="module")
def system():
    cnn_cfg = smoke_config()._replace(input_size=32)
    params = init_cnn(jax.random.PRNGKey(0), cnn_cfg)
    baf = init_baf_conv(jax.random.PRNGKey(1),
                        BaFConvConfig(c=C, q=cnn_cfg.split_q, hidden=8))
    return params, {C: (baf, np.arange(C))}


@pytest.fixture(scope="module")
def imgs():
    data_cfg = smoke_data_config()._replace(image_size=32, batch_size=8)
    it = shapes_batch_iterator(data_cfg, seed=123)
    rows = []
    while len(rows) < 16:
        img, _ = next(it)
        rows.append(np.asarray(img))
    return np.concatenate(rows, axis=0)[:16]


@too_many_devices
@pytest.mark.parametrize("target", [4, 64])
def test_run_sharded_bit_identical_to_serial(system, imgs, target):
    """restore + cloud forward through the shard_map program returns the
    same logits, bit for bit, as the serial separate-jit path at the same
    bucket size (same float class on both sides)."""
    params, bank = system
    gw = ServingGateway(params, bank, default_op=OP, max_batch=64)
    plan = gw.plan_for(gw.default_op)
    blobs = [gw.encode_request(imgs[i % len(imgs)][None])[1]
             for i in range(min(target, 8))]
    decoded = plan.decode_batch(blobs)

    serial = np.asarray(jax.block_until_ready(
        gw._cloud_fn(params, plan.restore(decoded.pad_to(target)))))
    ex = MeshExecutor(cost=LinearCostModel())
    sharded = ex.run_sharded(plan, decoded, target)
    assert sharded.shape == (target,) + serial.shape[1:]
    assert np.array_equal(sharded, serial[:target])
    # program cache: one compile per (plan, padded shape)
    assert len(ex._fns) == 1
    ex.run_sharded(plan, decoded, target)
    assert len(ex._fns) == 1


def test_seed_cost_from_hlo_positive(system):
    params, bank = system
    gw = ServingGateway(params, bank, default_op=OP, max_batch=8)
    plan = gw.plan_for(gw.default_op)
    m = seed_cost_from_hlo(plan, (4, 4, 4, C),
                           peaks=chip_peaks("TPU v5 lite"))
    assert isinstance(m, CalibratedCostModel)
    assert not m.frozen
    assert m.seed_per_item_s > 0.0
    # the roofline seed carries an otherwise-degenerate single-size fit
    m.observe(8, 0.02)
    m.freeze()
    assert m.per_item_s == m.seed_per_item_s


def test_seed_cost_from_hlo_refuses_device_without_peaks(system):
    params, bank = system
    gw = ServingGateway(params, bank, default_op=OP, max_batch=8)
    plan = gw.plan_for(gw.default_op)
    kind = jax.devices()[0].device_kind
    if kind in CHIP_PEAKS:
        pytest.skip(f"{kind!r} has published peaks")
    with pytest.raises(ValueError, match="no published peaks"):
        seed_cost_from_hlo(plan, (4, 4, 4, C))


# ---------------------------------------------------------------------------
# gateway federation on the shared mesh
# ---------------------------------------------------------------------------

def _mk_gateway(system, executor, *, seed, n_tenants=8, admission=None,
                max_batch=64):
    params, bank = system
    tenants = [TenantSpec(name=f"g{seed}t{i}") for i in range(n_tenants)]
    return MultiTenantGateway(params, bank, tenants=tenants, default_op=OP,
                              max_batch=max_batch, batch_window_s=None,
                              executor=executor, shared_executor=True,
                              seed=seed, admission=admission)


def _workload(gw, imgs, per_tenant, *, dt=1e-4):
    reqs = []
    names = sorted(gw.specs)
    for r in range(per_tenant):
        for i, name in enumerate(names):
            k = r * len(names) + i
            reqs.append(TenantRequest(tenant=name,
                                      img=imgs[k % len(imgs)][None],
                                      t_submit=k * dt))
    return reqs


def _frozen_cal():
    return CalibratedCostModel(seed_base_s=2e-3, seed_per_item_s=1e-4).freeze()


def _logit_rows(outcomes):
    return {t: [np.asarray(r.logits) for r in rs]
            for t, rs in outcomes.items()}


@too_many_devices
def test_federated_mesh_bit_identical_to_serial_and_replays(system, imgs):
    """Two federated gateways (8 tenants each, one full 64-bucket per
    gateway) served from the mesh return logits bit-identical to the same
    federation on a SerialExecutor; under the shared frozen cost model the
    mesh run replays bit for bit (logits and telemetry)."""
    cal = _frozen_cal()

    ser = SerialExecutor(cost=cal)
    gws_s = [_mk_gateway(system, ser, seed=g) for g in range(2)]
    wls = [_workload(gw, imgs, 8) for gw in gws_s]
    got_s = GatewayFederation(gws_s).serve(wls)

    mesh_ex = MeshExecutor(make_dev_mesh(prefer="data"), cost=cal)
    gws_m = [_mk_gateway(system, mesh_ex, seed=g) for g in range(2)]
    fed_m = GatewayFederation(gws_m)
    got_m = fed_m.serve(wls)

    for (out_s, tel_s), (out_m, tel_m) in zip(got_s, got_m):
        assert not tel_s.shed and not tel_m.shed
        rows_s, rows_m = _logit_rows(out_s), _logit_rows(out_m)
        assert rows_s.keys() == rows_m.keys()
        for t in rows_s:
            assert len(rows_s[t]) == 8
            for a, b in zip(rows_s[t], rows_m[t]):
                assert np.array_equal(a, b)
        # same virtual clock: the frozen model prices a 64-bucket the same
        # serial and sharded (per-shard rows at per-shard cost is the mesh's
        # *speedup*, visible in exec history, not in request outcomes)
        assert [r.tenant for r in tel_s.records] == \
               [r.tenant for r in tel_m.records]

    got_m2 = fed_m.serve(wls)
    for (out_1, tel_1), (out_2, tel_2) in zip(got_m, got_m2):
        assert tel_1.records == tel_2.records
        rows_1, rows_2 = _logit_rows(out_1), _logit_rows(out_2)
        for t in rows_1:
            for a, b in zip(rows_1[t], rows_2[t]):
                assert np.array_equal(a, b)

    # mesh virtual service time per 64-bucket is the per-shard prediction
    n = mesh_ex.n_data
    for tk in mesh_ex.history:
        assert (tk.t_done - tk.t_start) == pytest.approx(
            cal.predict(math.ceil(64 / n)))
    assert fed_m.depth() == 0


def test_serve_federated_rejects_disjoint_executors(system):
    gw1 = _mk_gateway(system, SerialExecutor(cost=LinearCostModel()), seed=0)
    gw2 = _mk_gateway(system, SerialExecutor(cost=LinearCostModel()), seed=1)
    with pytest.raises(ValueError, match="share one executor"):
        serve_federated([(gw1, []), (gw2, [])])


def test_serve_federated_rejects_duplicate_gateway(system):
    gw = _mk_gateway(system, SerialExecutor(cost=LinearCostModel()), seed=0)
    with pytest.raises(ValueError, match="once per federation"):
        serve_federated([(gw, []), (gw, [])])


def test_federation_requires_shared_flag(system):
    params, bank = system
    ex = SerialExecutor(cost=LinearCostModel())
    gw1 = _mk_gateway(system, ex, seed=0)
    gw2 = MultiTenantGateway(params, bank,
                             tenants=[TenantSpec(name="solo")],
                             default_op=OP, executor=ex)   # exclusive owner
    with pytest.raises(ValueError, match="shared_executor=True"):
        GatewayFederation([gw1, gw2])


def test_exclusive_executor_cannot_be_bound_twice(system):
    params, bank = system
    ex = SerialExecutor(cost=LinearCostModel())
    MultiTenantGateway(params, bank, tenants=[TenantSpec(name="a")],
                       default_op=OP, executor=ex)
    with pytest.raises(ValueError, match="already bound"):
        MultiTenantGateway(params, bank, tenants=[TenantSpec(name="b")],
                           default_op=OP, executor=ex)


def test_shared_depth_sheds_across_gateways(system, imgs):
    """One gateway's burst fills the shared executor; the *other* gateway's
    queue-depth admission reads that shared backlog and sheds, even though
    its own traffic is tiny."""
    ex = SerialExecutor(cost=LinearCostModel(base_s=0.5, per_item_s=0.01))
    gw_burst = _mk_gateway(system, ex, seed=0, n_tenants=1, max_batch=1)
    gw_meek = _mk_gateway(system, ex, seed=1, n_tenants=1, max_batch=1,
                          admission=QueueDepthAdmission(1))
    wl_burst = [TenantRequest(tenant="g0t0", img=imgs[i][None],
                              t_submit=0.001 * i) for i in range(4)]
    wl_meek = [TenantRequest(tenant="g1t0", img=imgs[i][None],
                             t_submit=0.25 + 0.001 * i) for i in range(2)]
    (out_b, tel_b), (out_m, tel_m) = GatewayFederation(
        [gw_burst, gw_meek]).serve([wl_burst, wl_meek])

    assert not tel_b.shed
    assert len(tel_m.shed) == 2
    assert all(isinstance(r, RequestShed) for r in out_m["g1t0"])
    assert all("queue-depth" in r.reason for r in out_m["g1t0"])
    # nothing silently dropped on either side
    assert len(out_b["g0t0"]) == 4
    assert all(not r.shed for r in out_b["g0t0"])
