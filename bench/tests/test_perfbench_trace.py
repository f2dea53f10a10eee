"""Trace reduction on hand-built events: busy union, idle share, kernel
time by name, the breakdown's top ops and idle gaps."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from types import SimpleNamespace  # noqa: E402

import pytest  # noqa: E402

from benchlib import manifest, peaks, trace  # noqa: E402
from benchlib.trace import Event  # noqa: E402

DEV = "/device:TPU:0"
OPS = "XLA Ops"
HOST = "/host:CPU"


def dev(name, start, dur, plane=DEV):
    return Event(plane, OPS, name, float(start), float(dur))


def host(name, start, dur):
    return Event(HOST, "python", name, float(start), float(dur))


# op names as a TPU trace gives them: the op's HLO text
CONS = ('%tpu_custom_call = f32[8,4096,128]{2,1,0:T(8,128)} custom-call('
        'f32[8,4096,128]{2,1,0:T(8,128)} %fusion.3, u8[8,4096,128]{2,1,0:'
        'T(32,128)(4,1)} %bitcast.5, f32[8,1,128]{2,1,0:T(1,128)} %x, '
        'f32[8,1,128]{2,1,0:T(1,128)} %y), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={f32[8,4096,128]'
        '{2,1,0}}')
HIST = ('%tpu_custom_call.1 = s32[256,128]{1,0:T(8,128)} custom-call('
        's32[4096,128]{1,0:T(8,128)} %args_0_.1), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={s32[4096,128]{1,0}}'
        ', frontend_attributes={kernel_metadata={}}')
HIST_VMEM = ('%tpu_custom_call.1 = s32[256,8]{1,0:T(8,128)S(1)} custom-call('
             's32[4096,8]{1,0:T(8,128)S(1)} %copy), custom_call_target='
             '"tpu_custom_call", operand_layout_constraints={s32[4096,8]{1,0}}'
             ', frontend_attributes={kernel_metadata={}}')
FUSION = ('%fusion.1 = bf16[512,8,65,32]{3,1,2,0:T(8,128)(2,1)S(1)} fusion('
          'f32[3,3,3,32]{3,2,1,0:T(4,128)S(1)} %copy-done.8), kind=kOutput, '
          'calls=%fused_computation.3')


@pytest.fixture
def events():
    # window 0..1000 ns: serve 0..600, wait 600..1000
    return [
        host("bench.serve", 0, 600), host("bench.wait", 600, 400),
        dev(FUSION, 100, 100),                # 100..200
        dev(CONS, 150, 100),                  # overlaps: 150..250
        dev("%convolution.3 = f32[8]{0} convolution(f32[8]{0} %a)",
            400, 50),                         # 400..450
        dev(HIST, 900, 200),                  # runs past the window end
        Event(DEV, "Steps", "step 0", 0.0, 1000.0),   # not an op line
        Event(HOST, "python", "other.span", 0.0, 1000.0),
    ]


def test_union_merges_overlaps_and_sorts():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.union([]) == []


def test_window_is_the_benchmark_spans(events):
    assert trace.window(events) == (0.0, 1000.0)
    with pytest.raises(ValueError):
        trace.window([dev("x", 0, 1)])


def test_busy_is_the_union_clipped_to_the_window(events):
    # 100..250 (150) + 400..450 (50) + 900..1000 (100, clipped)
    assert trace.busy_ns(events, 0, 1000) == pytest.approx(300.0)
    assert trace.busy_ns(events, 0, 300) == pytest.approx(150.0)


def test_busy_averages_over_devices(events):
    two = events + [dev("fusion.9", 0, 1000, plane="/device:TPU:1")]
    assert trace.busy_ns(two, 0, 1000) == pytest.approx((300 + 1000) / 2)


def test_idle_share_from_busy_and_window(events):
    run = SimpleNamespace(events=events, window_ns=(0.0, 1000.0))
    read = manifest.reader("device_idle_share.backlog")
    assert read(run) == pytest.approx(70.0)
    run.events = [e for e in events if e.plane == HOST]
    assert read(run) is None           # no device op: nothing to read


def test_kernels_are_known_by_their_signature():
    assert trace.kernel_call(HIST) == (("s32", (256, 128), 0),
                                       [("s32", (4096, 128), 0)])
    out, ops = trace.kernel_call(CONS)
    assert out == ("f32", (8, 4096, 128), 0)
    assert [d for d, _, _ in ops] == ["f32", "u8", "f32", "f32"]
    assert trace.kernel_call(FUSION) is None
    assert trace.short_name(HIST) == "%tpu_custom_call.1 s32[256,128] " \
        "custom-call"
    assert trace.short_name(FUSION) == "%fusion.1 bf16[512,8,65,32] fusion"


def test_a_kernel_operand_in_vmem_is_read_as_memory_space_1():
    out, ops = trace.kernel_call(HIST_VMEM)
    assert out == ("s32", (256, 8), 1) and ops == [("s32", (4096, 8), 1)]


def test_kernel_time_and_bytes(events):
    hist = manifest.reader("histogram_roofline.backlog")
    hist_of = manifest.reader_module("histogram_roofline").bytes_of
    # started inside the window: counted whole
    assert trace.kernel_ns(events, hist_of(128, 8), 0, 1000) == (
        200.0, 4 * 4096 * 128 + 4 * 256 * 128, 1)
    assert trace.kernel_ns(events, hist_of(128, 8), 0, 800)[2] == 0
    assert trace.kernel_ns(events, hist_of(128, 7), 0, 1000)[2] == 0
    assert trace.kernel_ns(events, hist_of(8, 8), 0, 1000)[2] == 0

    run = SimpleNamespace(events=events, window_ns=(0.0, 1000.0),
                          peaks=peaks.peaks("TPU v5 lite"),
                          cfg={"c": 128, "bits": 8})
    want = 100 * (4 * 4096 * 128 + 4 * 256 * 128) / 819e9 / 200e-9
    assert hist(run) == pytest.approx(want)
    run.cfg["c"] = 64
    assert hist(run) is None           # no such kernel: nothing to read
    run.peaks = None
    run.cfg["c"] = 128
    assert hist(run) is None           # no published peak: nothing to read


def test_a_kernel_held_in_vmem_has_no_hbm_roofline():
    hist_of = manifest.reader_module("histogram_roofline").bytes_of
    assert hist_of(8, 8)(HIST_VMEM) is None
    run = SimpleNamespace(events=[host("bench.serve", 0, 1000),
                                  dev(HIST_VMEM, 10, 20)],
                          window_ns=(0.0, 1000.0),
                          peaks=peaks.peaks("TPU v5 lite"),
                          cfg={"c": 8, "bits": 8})
    assert manifest.reader("histogram_roofline.backlog")(run) is None


def test_top_ops_rank_device_time_by_short_name(events):
    top = trace.top_ops(events, 0, 1000)
    assert top[0] == ["%tpu_custom_call.1 s32[256,128] custom-call",
                      pytest.approx(200e-9)]
    assert [name for name, _ in top][1:] == [
        "%fusion.1 bf16[512,8,65,32] fusion",
        "%tpu_custom_call f32[8,4096,128] custom-call",
        "%convolution.3 f32[8] convolution"]
    assert trace.top_ops(events, 0, 1000, k=1) == top[:1]
    assert trace.top_ops(events, 0, 120) == [
        ["%fusion.1 bf16[512,8,65,32] fusion", pytest.approx(100e-9)]]


def test_idle_gaps_named_by_the_host_span(events):
    gaps = trace.idle_gaps(events, 0, 1000)
    # 450..900 (wait covers 675), 250..400 (serve), 0..100 (serve)
    assert gaps == [["bench.wait", pytest.approx(450e-9)],
                    ["bench.serve", pytest.approx(150e-9)],
                    ["bench.serve", pytest.approx(100e-9)]]
    assert trace.idle_gaps(events, 0, 1000, k=1)[0][0] == "bench.wait"
