"""Metrics registry, log-bucket histograms, instrumentation hooks, and the
Telemetry rebuild on top of them (repro.obs.metrics / repro.obs.hooks /
repro.serve.telemetry)."""
import math

import numpy as np
import pytest

from repro.obs import GROWTH, Counter, Gauge, LogHistogram, MetricsRegistry
from repro.obs import hooks
from repro.serve.telemetry import RequestRecord, ShedRecord, Telemetry


def _rec(i, *, tenant="", latency=None, compute=0.002, queue=0.001,
         wire=0.004, sched=0.0, bits=1000):
    if latency is not None:
        # place the whole latency in compute so total_latency_s == latency
        compute, queue, wire, sched = latency, 0.0, 0.0, 0.0
    return RequestRecord(req_id=i, c=8, bits=8, bits_on_wire=bits,
                         wire_latency_s=wire, queue_wait_s=queue,
                         compute_s=compute, batch_size=1, padded_size=1,
                         tenant=tenant, sched_wait_s=sched)


# ---------------------------------------------------------------------------
# counters / gauges / registry
# ---------------------------------------------------------------------------

def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match=">= 0"):
        c.inc(-1)


def test_gauge_set_and_inc():
    g = Gauge()
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0


def test_registry_series_identity_and_labels():
    m = MetricsRegistry()
    a = m.counter("reqs", tenant="a")
    assert m.counter("reqs", tenant="a") is a          # get-or-create
    assert m.counter("reqs", tenant="b") is not a      # labels split series
    # label order must not matter for series identity
    h1 = m.histogram("h", x="1", y="2")
    h2 = m.histogram("h", y="2", x="1")
    assert h1 is h2
    assert m.get("reqs", tenant="a") is a
    assert m.get("nope") is None                       # never creates
    assert len(m) == 3


def test_registry_kind_conflict():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        m.gauge("x")


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c").inc(2)
    b.counter("c").inc(3)
    a.gauge("g").set(1)
    b.gauge("g").set(9)
    a.histogram("h").observe(1.0)
    b.histogram("h").observe(4.0)
    b.histogram("only_b").observe(2.0)
    a.merge(b)
    assert a.counter("c").value == 5.0          # counters add
    assert a.gauge("g").value == 9.0            # gauges take the other's
    assert a.histogram("h").count == 2          # histograms union
    assert a.histogram("only_b").count == 1     # missing series created


# ---------------------------------------------------------------------------
# log-bucket histogram
# ---------------------------------------------------------------------------

def test_histogram_percentile_within_bucket_tolerance(rng):
    h = LogHistogram()
    vals = np.exp(rng.normal(size=5000))        # lognormal spans many octaves
    for v in vals:
        h.observe(float(v))
    for p in (1, 25, 50, 75, 90, 99, 99.9):
        exact = float(np.percentile(vals, p, method="higher"))
        got = h.percentile(p)
        # one bucket of relative error at most (plus min/max clamping)
        assert exact / GROWTH <= got <= exact * GROWTH, (p, exact, got)


def test_histogram_single_observation_exact():
    h = LogHistogram()
    h.observe(0.1234)
    for p in (0, 50, 99, 100):
        assert h.percentile(p) == pytest.approx(0.1234)
    assert h.mean == pytest.approx(0.1234)


def test_histogram_zero_bucket_and_rejects():
    h = LogHistogram()
    for _ in range(9):
        h.observe(0.0)
    h.observe(5.0)
    assert h.percentile(50) == 0.0
    assert h.percentile(99) == pytest.approx(5.0)   # vmax clamp: exact
    with pytest.raises(ValueError, match=">= 0"):
        h.observe(-1e-9)
    with pytest.raises(ValueError, match=">= 0"):
        h.observe(float("nan"))
    with pytest.raises(ValueError, match="no observations"):
        LogHistogram().percentile(50)


def test_histogram_merge_equals_union(rng):
    a, b, u = LogHistogram(), LogHistogram(), LogHistogram()
    for i, v in enumerate(np.abs(rng.normal(size=400)) + 1e-6):
        (a if i % 2 else b).observe(float(v))
        u.observe(float(v))
    m = LogHistogram.merged([a, b])
    assert m.count == u.count
    assert m.total == pytest.approx(u.total)
    assert m.buckets == u.buckets
    assert m.vmin == u.vmin and m.vmax == u.vmax
    for p in (10, 50, 95):
        assert m.percentile(p) == u.percentile(p)


def test_histogram_merge_growth_mismatch():
    with pytest.raises(ValueError, match="growth"):
        LogHistogram(growth=2.0).merge(LogHistogram(growth=4.0))


def test_histogram_bucket_boundaries():
    h = LogHistogram(growth=2.0)
    # exact powers of growth land in their own bucket despite log rounding
    for v, want in ((1.0, 0), (2.0, 1), (4.0, 2), (0.5, -1)):
        assert h.bucket_index(v) == want, v


# ---------------------------------------------------------------------------
# Prometheus text dump
# ---------------------------------------------------------------------------

def test_prometheus_dump_cumulative_and_deterministic():
    m = MetricsRegistry()
    m.counter("reqs_total", tenant="a").inc(3)
    h = m.histogram("lat_seconds", tenant="a")
    for v in (0.0, 0.01, 0.02, 0.02):
        h.observe(v)
    text = m.to_prometheus_text()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{tenant="a"} 3' in text
    assert '# TYPE lat_seconds histogram' in text
    assert 'lat_seconds_bucket{le="0",tenant="a"} 1' in text   # zero bucket
    assert 'lat_seconds_bucket{le="+Inf",tenant="a"} 4' in text
    assert 'lat_seconds_count{tenant="a"} 4' in text
    # cumulative bucket counts must be non-decreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("lat_seconds_bucket")]
    assert cums == sorted(cums)
    assert m.to_prometheus_text() == text              # deterministic
    # label values escape quotes/backslashes
    m2 = MetricsRegistry()
    m2.counter("c", path='a"b\\c').inc()
    assert r'{path="a\"b\\c"}' in m2.to_prometheus_text()


# ---------------------------------------------------------------------------
# hooks: zero-cost when disabled, scoped install
# ---------------------------------------------------------------------------

def test_hooks_disabled_are_noops():
    assert not hooks.enabled()
    # one shared null timer, regardless of stage/labels
    assert hooks.timed("a") is hooks.timed("b", backend="rans")
    with hooks.timed("a"):
        pass
    hooks.observe("x", 1.0)       # no registry: swallowed
    hooks.count("y")
    assert hooks.installed() is None


def test_hooks_active_scoping():
    m = MetricsRegistry()
    with hooks.active(m) as got:
        assert got is m and hooks.enabled()
        with hooks.timed("stage_x", backend="rans"):
            pass
        hooks.observe("width", 16.0, mode="static")
        hooks.count("events", 2.0)
    assert not hooks.enabled()                        # uninstalled on exit
    hist = m.get("stage_seconds", stage="stage_x", backend="rans")
    assert hist is not None and hist.count == 1
    assert m.get("width", mode="static").count == 1
    assert m.get("events").value == 2.0


def test_hooks_active_uninstalls_on_exception():
    m = MetricsRegistry()
    with pytest.raises(RuntimeError):
        with hooks.active(m):
            raise RuntimeError("boom")
    assert not hooks.enabled()


class _RecordingAnnotate:
    """A fake ``annotate`` factory: logs each span's enter and exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **labels):
        log = self.log

        class _Span:
            def __enter__(self):
                log.append(("enter", name, labels))

            def __exit__(self, *exc):
                log.append(("exit", name, exc[0]))
                return False
        return _Span()


def test_hooks_annotate_wraps_each_timed_stage():
    m, ann = MetricsRegistry(), _RecordingAnnotate()
    with hooks.active(m, annotate=ann):
        with hooks.timed("outer", backend="rans"):
            with hooks.timed("inner", padded=8):
                assert ann.log[-1] == ("enter", "inner", {"padded": 8})
    assert ann.log == [("enter", "outer", {"backend": "rans"}),
                       ("enter", "inner", {"padded": 8}),
                       ("exit", "inner", None),
                       ("exit", "outer", None)]
    # the span wraps the same interval the histogram times
    assert m.get("stage_seconds", stage="outer", backend="rans").count == 1
    assert m.get("stage_seconds", stage="inner", padded=8).count == 1


def test_hooks_annotate_exits_when_the_body_raises():
    m, ann = MetricsRegistry(), _RecordingAnnotate()
    with hooks.active(m, annotate=ann):
        with pytest.raises(ValueError):
            with hooks.timed("stage_x"):
                raise ValueError("boom")
    assert ann.log == [("enter", "stage_x", {}),
                       ("exit", "stage_x", ValueError)]
    assert m.get("stage_seconds", stage="stage_x").count == 1


def test_hooks_annotate_unused_when_not_installed():
    ann = _RecordingAnnotate()
    with hooks.active(MetricsRegistry(), annotate=ann):
        pass
    assert hooks.timed("a") is hooks._NULL       # uninstall drops annotate
    with hooks.timed("a", backend="rans"):
        pass
    assert ann.log == []
    # a registry without annotate times, and writes no span
    m = MetricsRegistry()
    with hooks.active(m):
        with hooks.timed("b"):
            pass
    assert ann.log == [] and m.get("stage_seconds", stage="b").count == 1


# ---------------------------------------------------------------------------
# Telemetry on the registry
# ---------------------------------------------------------------------------

def test_telemetry_single_record_percentile_is_the_record():
    tel = Telemetry()
    tel.record(_rec(0, latency=0.6))
    for p in (0, 50, 99, 100):
        assert tel.percentile("total_latency_s", p) == pytest.approx(0.6)


def test_telemetry_empty_served_nonempty_shed():
    tel = Telemetry()
    tel.record_shed(ShedRecord(req_id=0, tenant="a", t_submit=0.0,
                               reason="queue full"))
    s = tel.summary()
    assert s["count"] == 0 and s["shed"] == 1 and s["shed_rate"] == 1.0
    assert "shed" in tel.format_summary()
    with pytest.raises(ValueError, match="1 shed"):
        tel.percentile("total_latency_s", 99)
    # the shed-only tenant still appears in per_tenant, latencies None
    row = tel.per_tenant()["a"]
    assert row["count"] == 0 and row["shed"] == 1
    assert row["p50_latency_s"] is None


def test_telemetry_percentiles_match_numpy():
    tel = Telemetry()
    lats = [0.01 * (i + 1) for i in range(40)]
    for i, lat in enumerate(lats):
        tel.record(_rec(i, latency=lat))
    assert tel.percentile("total_latency_s", 99) == pytest.approx(
        float(np.percentile(lats, 99)))


def test_telemetry_bounded_mode_keeps_aggregates(rng):
    tel = Telemetry(max_records=8)
    lats = np.abs(rng.normal(size=200)) + 1e-3
    for i, lat in enumerate(lats):
        tel.record(_rec(i, latency=float(lat), tenant=f"t{i % 3}"))
    assert len(tel) == 200                  # true count survives the cap
    assert len(tel.records) == 8
    assert tel.truncated
    exact = float(np.percentile(lats, 90))
    got = tel.percentile("total_latency_s", 90)
    assert exact / GROWTH ** 2 <= got <= exact * GROWTH ** 2
    # per-tenant percentile off the tenant's own histogram
    t0 = [float(l) for i, l in enumerate(lats) if i % 3 == 0]
    got0 = tel.percentile("total_latency_s", 50, tenant="t0")
    ex0 = float(np.percentile(t0, 50))
    assert ex0 / GROWTH ** 2 <= got0 <= ex0 * GROWTH ** 2
    # fields without a histogram series are an explicit error when truncated
    with pytest.raises(ValueError, match="truncated"):
        tel.percentile("sched_wait_s", 99)
    # fairness over bits stays exact through aggregates
    assert 0.9 <= tel.fairness("bits_on_wire") <= 1.0
    with pytest.raises(ValueError, match="truncated"):
        tel.fairness("compute_s")
    s = tel.summary()
    assert s["count"] == 200
    assert s["mean_bits_on_wire"] == pytest.approx(1000.0)


def test_telemetry_registry_counters():
    m = MetricsRegistry()
    tel = Telemetry(registry=m)
    for i in range(5):
        tel.record(_rec(i, tenant="a"))
    tel.record_shed(ShedRecord(req_id=5, tenant="a", t_submit=0.0,
                               reason="depth"))
    assert m.counter("gateway_requests_total", tenant="a").value == 5
    assert m.counter("gateway_wire_bits_total", tenant="a").value == 5000
    assert m.counter("gateway_shed_total", tenant="a").value == 1
    assert m.get("gateway_request_latency_seconds", tenant="a").count == 5
    assert tel.metrics is m


def test_telemetry_max_records_validation():
    with pytest.raises(ValueError, match="max_records"):
        Telemetry(max_records=0)
