"""The drain loop, percentiles and rates on a fake clock: a stall in the
window must move ``p95_latency_ms`` and ``throughput_rps``."""
import statistics
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchlib import drain, manifest, stats, traffic  # noqa: E402


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def served(clock, cost):
    """serve(first, n) that takes cost(first, n) seconds of fake time."""
    log = []

    def serve(first, n):
        log.append((first, n))
        clock.t += cost(first, n)
    return serve, log


def run_metric(name, drained):
    return manifest.reader(name)(SimpleNamespace(drained=drained))


def test_percentile_matches_numpy_linear():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100):
        x = rng.exponential(size=n)
        for p in (0, 50, 95, 100):
            assert stats.percentile(x, p) == pytest.approx(
                float(np.percentile(x, p)))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_counts_to_the_last_completion():
    assert stats.rate(10, 5.0, 7.0) == 5.0
    with pytest.raises(ValueError):
        stats.rate(0, 5.0, 7.0)


def test_open_loop_takes_every_due_request_in_one_call():
    clock = FakeClock()
    serve, log = served(clock, lambda first, n: 0.25)
    offsets = np.array([0.0, 0.1, 0.2, 0.3, 1.5])
    d = drain.drain(serve, clock=clock, sleep=clock.sleep, window_s=2.0,
                    offsets=offsets, take=None)
    # t=0: request 0 alone; it ends at 0.25, by when 1 and 2 are due; ...
    assert log == [(0, 1), (1, 2), (3, 1), (4, 1)]
    assert d.n == 5
    np.testing.assert_allclose(d.latency_s, [0.25, 0.4, 0.3, 0.45, 0.25])
    np.testing.assert_allclose(d.queue_wait_s, [0.0, 0.15, 0.05, 0.2, 0.0])


def test_requests_due_in_the_window_are_all_served_past_its_end():
    clock = FakeClock()
    serve, log = served(clock, lambda first, n: 1.0)
    d = drain.drain(serve, clock=clock, sleep=clock.sleep, window_s=1.0,
                    offsets=np.array([0.0, 0.5, 0.9, 1.2]), take=None)
    assert d.n == 3                    # 1.2 is not due inside the window
    assert sum(n for _, n in log) == 3
    assert d.last_end - d.t0 == pytest.approx(2.0)


def test_take_caps_a_call():
    clock = FakeClock()
    serve, log = served(clock, lambda first, n: 0.0)
    drain.drain(serve, clock=clock, sleep=clock.sleep, window_s=1.0,
                offsets=np.zeros(5), take=2)
    assert log == [(0, 2), (2, 2), (4, 1)]


def test_backlog_takes_a_batch_per_call_until_the_window_ends():
    clock = FakeClock()
    serve, log = served(clock, lambda first, n: 0.3)
    d = drain.drain(serve, clock=clock, sleep=clock.sleep, window_s=1.0,
                    offsets=None, take=8)
    assert log == [(0, 8), (8, 8), (16, 8), (24, 8)]   # starts 0 .. 0.9
    assert run_metric("throughput_rps", d) == pytest.approx(32 / 1.2)


def test_a_stall_moves_p95_latency_and_throughput():
    offsets = traffic.poisson_offsets(20.0, 10.0, traffic.rng(3, 2))

    def window(offsets, take, base, per_request, stall_first):
        clock = FakeClock()

        def cost(first, n):
            stalled = stall_first is not None and first <= stall_first < \
                first + n
            return base + per_request * n + (1.0 if stalled else 0.0)
        serve, _ = served(clock, cost)
        return drain.drain(serve, clock=clock, sleep=clock.sleep,
                           window_s=10.0, offsets=offsets, take=take)

    calm = window(offsets, None, 0.01, 0.03, None)
    stalled = window(offsets, None, 0.01, 0.03, 100)
    assert calm.n == stalled.n == 200
    assert run_metric("p95_latency_ms", stalled) > \
        2 * run_metric("p95_latency_ms", calm)
    assert run_metric("p50_latency_ms", stalled) >= \
        run_metric("p50_latency_ms", calm)

    calm = window(None, 8, 0.5, 0.0, None)
    stalled = window(None, 8, 0.5, 0.0, 40)
    assert run_metric("throughput_rps", stalled) < \
        0.95 * run_metric("throughput_rps", calm)


def test_poisson_gaps_are_exponential_and_fit_the_window():
    a = traffic.poisson_offsets(16.0, 20.0, traffic.rng(1, 2))
    b = traffic.poisson_offsets(16.0, 20.0, traffic.rng(2**33 + 5, 2))
    assert len(a) == len(b) == 320
    assert a[-1] == pytest.approx(b[-1]) and a[-1] < 20.0
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
    gaps = np.diff(a, prepend=0)
    # exponential: the coefficient of variation is about 1
    assert statistics.pstdev(gaps) / statistics.fmean(gaps) == \
        pytest.approx(1.0, abs=0.1)


def test_every_seed_gets_the_same_arrivals_and_its_own_images():
    mix = manifest.traffic({"traffic": "steady"})
    a = traffic.plan(mix, 20.0, 1)
    b = traffic.plan(mix, 20.0, 2**33 + 5)
    np.testing.assert_array_equal(a.offsets, b.offsets)
    assert not np.array_equal(a.image_of, b.image_of)
    other = traffic.plan({**mix, "arrival_seed": mix["arrival_seed"] + 1},
                         20.0, 1)
    assert not np.allclose(other.offsets, a.offsets)


def test_plan_reads_the_mix_file():
    mix = manifest.traffic({"traffic": "backlog"})
    p = traffic.plan(mix, 20.0, 7)
    assert p.offsets is None and p.take == 8 and p.warm == (8,)
    assert sorted(p.image_of) == list(range(mix["pool"]))
    mix = manifest.traffic({"traffic": "steady"})
    p = traffic.plan(mix, 20.0, 7)
    assert p.take is None and len(p.offsets) == round(mix["rate_rps"] * 20)
