"""Deterministic virtual-clock tracing through the serving gateway
(repro.obs.trace + the tracer/metrics wiring in repro.serve.gateway)."""
import json

import jax
import numpy as np
import pytest

from repro.configs.yolo_baf import smoke_config, smoke_data_config
from repro.core.baf import BaFConvConfig, init_baf_conv
from repro.data.synthetic import shapes_batch_iterator
from repro.models.cnn import init_cnn
from repro.obs import (MetricsRegistry, Tracer, hooks, reconcile_trace,
                       validate_chrome_trace)
from repro.pipeline import OperatingPoint
from repro.serve import (ChannelConfig, LinearCostModel, MultiQueueExecutor,
                         MultiTenantGateway, QueueDepthAdmission,
                         ServingGateway, SimulatedChannel, TenantRequest,
                         TenantSpec)


@pytest.fixture(scope="module")
def tiny_system():
    cnn_cfg = smoke_config()._replace(input_size=32)
    data_cfg = smoke_data_config()._replace(image_size=32, batch_size=8)
    params = init_cnn(jax.random.PRNGKey(0), cnn_cfg)
    bank = {c: (init_baf_conv(jax.random.PRNGKey(c),
                              BaFConvConfig(c=c, q=cnn_cfg.split_q, hidden=8)),
                np.arange(c)) for c in (4, 8)}
    imgs, _ = next(shapes_batch_iterator(data_cfg, seed=5))
    return params, bank, np.asarray(imgs)


def _make_mt(params, bank, *, tracer=None, metrics=None, n_tenants=4):
    return MultiTenantGateway(
        params, bank, tenants=[TenantSpec(f"t{i}") for i in range(n_tenants)],
        channel_cfg=ChannelConfig(bandwidth_bps=50e6, base_latency_s=0.001),
        default_op=OperatingPoint(c=8, bits=8), max_batch=4,
        batch_window_s=0.002,
        executor=MultiQueueExecutor(2, cost=LinearCostModel(0.004, 0.001)),
        admission=QueueDepthAdmission(max_depth=3),
        tracer=tracer, metrics=metrics)


def _workload(imgs, n=24, n_tenants=4):
    return [TenantRequest(f"t{i % n_tenants}", imgs[i % len(imgs)],
                          t_submit=0.0005 * i) for i in range(n)]


# ---------------------------------------------------------------------------
# Tracer unit behaviour
# ---------------------------------------------------------------------------

def test_tracer_validate_nesting():
    tr = Tracer()
    root = tr.span("request", 0.0, 1.0, track="tenant:a")
    tr.span("child", 0.2, 0.8, track="tenant:a", parent=root)
    tr.validate()
    # a child escaping its parent's interval fails validation
    tr.span("bad", 0.5, 1.5, track="tenant:a", parent=root)
    with pytest.raises(ValueError, match="escapes parent"):
        tr.validate()


def test_tracer_rejects_backwards_span():
    tr = Tracer()
    tr.span("x", 1.0, 0.5, track="t")
    with pytest.raises(ValueError):
        tr.validate()


def test_chrome_export_structure():
    tr = Tracer()
    s = tr.span("request", 0.0, 0.001, track="tenant:a", attrs={"op": "8/8"})
    tr.span("part", 0.0, 0.0005, track="tenant:a", parent=s)
    tr.instant("submit", 0.0, track="tenant:a")
    obj = tr.to_chrome()
    n = validate_chrome_trace(obj)
    assert n == len(obj["traceEvents"])
    kinds = {e["ph"] for e in obj["traceEvents"]}
    assert {"X", "i", "M"} <= kinds
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and "args" in e for e in xs)
    # microsecond conversion
    root = next(e for e in xs if e["name"] == "request")
    assert root["dur"] == pytest.approx(1000.0)


def test_validate_chrome_trace_rejects_garbage():
    with pytest.raises(ValueError):
        validate_chrome_trace({"no_events": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X"}]})  # missing keys
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": "not a list"})


# ---------------------------------------------------------------------------
# Gateway integration: determinism, reconciliation, invariance
# ---------------------------------------------------------------------------

def test_multi_tenant_trace_deterministic_and_reconciles(tiny_system):
    params, bank, imgs = tiny_system
    work = _workload(imgs)

    def run():
        m = MetricsRegistry()
        gw = _make_mt(params, bank, tracer=Tracer(), metrics=m)
        with hooks.active(m):
            _, tel = gw.serve_tenants(work)
        return gw.tracer, tel, m

    tr1, tel1, m1 = run()
    tr2, tel2, _ = run()
    # byte-identical canonical JSON across two fresh runs
    j1, j2 = tr1.to_json(), tr2.to_json()
    assert j1 == j2
    json.loads(j1)                                     # well-formed
    tr1.validate()
    validate_chrome_trace(tr1.to_chrome())
    # span sums reconcile to telemetry total latency within 1e-9 s
    assert reconcile_trace(tr1, tel1) < 1e-9
    # each request root has exactly the four phase children
    roots = tr1.roots("request")
    assert len(roots) == len(tel1.records)
    for root in roots:
        names = sorted(c.name for c in tr1.children(root.span_id))
        assert names == ["channel.transmit", "cloud.compute", "exec.queue",
                         "sched.wait"]
    # shed requests appear as admission.shed instants, not request spans
    assert tel1.shed
    sheds = [i for i in tr1.instants if i.name == "admission.shed"]
    assert len(sheds) == len(tel1.shed)
    # wall-clock stage timers landed in metrics, never in the trace
    assert m1.get("stage_seconds", stage="pipeline.encode") is not None


def test_tracing_does_not_perturb_virtual_clock(tiny_system):
    params, bank, imgs = tiny_system
    work = _workload(imgs)
    _, tel_plain = _make_mt(params, bank).serve_tenants(work)
    m = MetricsRegistry()
    gw = _make_mt(params, bank, tracer=Tracer(), metrics=m)
    with hooks.active(m):
        _, tel_traced = gw.serve_tenants(work)
    assert tel_plain.records == tel_traced.records
    assert tel_plain.shed == tel_traced.shed


def test_single_tenant_serve_traces(tiny_system):
    params, bank, imgs = tiny_system
    tr = Tracer()
    gw = ServingGateway(
        params, bank, default_op=OperatingPoint(c=8, bits=8), max_batch=4,
        channel=SimulatedChannel(ChannelConfig(bandwidth_bps=20e6,
                                               base_latency_s=0.005)),
        tracer=tr, metrics=MetricsRegistry())
    _, tel = gw.serve(imgs[:6])
    tr.validate()
    validate_chrome_trace(tr.to_chrome())
    assert len(tr.roots("request")) == len(tel.records) == 6
    assert reconcile_trace(tr, tel) < 1e-9
    # executor gauges exported at end of serve
    assert gw.metrics.get("executor_utilization") is not None


def test_serve_records_every_stage_of_the_request_path(tiny_system):
    params, bank, imgs = tiny_system
    gw = ServingGateway(params, bank,
                        default_op=OperatingPoint(c=8, bits=8,
                                                  backend="rans"),
                        max_batch=4)
    m, spans = MetricsRegistry(), []

    class _Span:
        def __init__(self, name, **labels):
            self.name = name

        def __enter__(self):
            spans.append(self.name)

        def __exit__(self, *exc):
            return False

    with hooks.active(m, annotate=_Span):
        gw.serve(imgs[:5])
    stages = {labels["stage"] for name, labels, _ in m.collect()
              if name == "stage_seconds"}
    path = {"gateway.edge", "pipeline.quantize", "pipeline.encode",
            "codec.histogram", "gateway.batch", "pipeline.decode_batch",
            "pipeline.restore", "gateway.cloud"}
    assert path <= stages
    assert path <= set(spans)
    # one edge, quantize and histogram per request; one cloud per batch
    assert spans.count("gateway.edge") == 5
    assert spans.count("pipeline.quantize") == 5
    assert spans.count("codec.histogram") == 5
    assert spans.count("gateway.cloud") == spans.count("gateway.batch") >= 2


def test_reconcile_requires_span_per_record(tiny_system):
    params, bank, imgs = tiny_system
    gw = _make_mt(params, bank, tracer=Tracer(), metrics=None)
    _, tel = gw.serve_tenants(_workload(imgs, n=8))
    # a fresh empty tracer cannot reconcile a populated telemetry
    with pytest.raises(ValueError, match="no request span"):
        reconcile_trace(Tracer(), tel)
