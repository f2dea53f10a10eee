"""Attribution by the program's own names, on hand-built events: device time
per program, ops qualified by their program, idle gaps named by the path of
the spans over them; the benchmark's readers unmoved by the added events;
and a CPU rehearsal of ``stage_profile.py``'s window."""
import importlib.util
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchlib import drain, manifest, peaks, stages, trace  # noqa: E402
from benchlib.trace import Event  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def op(name, start, dur, plane=DEV):
    return Event(plane, "XLA Ops", name, float(start), float(dur))


def prog(name, start, dur, plane=DEV):
    return Event(plane, "XLA Modules", name, float(start), float(dur))


def host(name, start, dur):
    return Event(HOST, "python", name, float(start), float(dur))


EDGE = ('%fusion.1 = bf16[512,8,65,32]{3,1,2,0:T(8,128)(2,1)S(1)} fusion('
        'f32[3,3,3,32]{3,2,1,0:T(4,128)S(1)} %copy-done.8), kind=kOutput, '
        'calls=%fused_computation.3')
HIST = ('%tpu_custom_call.1 = s32[256,128]{1,0:T(8,128)} custom-call('
        's32[4096,128]{1,0:T(8,128)} %args_0_.1), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={s32[4096,128]{1,0}}'
        ', frontend_attributes={kernel_metadata={}}')
CONS = ('%tpu_custom_call = f32[8,4096,128]{2,1,0:T(8,128)} custom-call('
        'f32[8,4096,128]{2,1,0:T(8,128)} %fusion.3, u8[8,4096,128]{2,1,0:'
        'T(32,128)(4,1)} %bitcast.5, f32[8,1,128]{2,1,0:T(1,128)} %x, '
        'f32[8,1,128]{2,1,0:T(1,128)} %y), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={f32[8,4096,128]'
        '{2,1,0}}')
FUSION2 = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"

# window 0..1000 ns: serve 0..600, wait 600..1000
BENCH_SPANS = [host("bench.serve", 0, 600), host("bench.wait", 600, 400)]
PROGRAM = [
    host("gateway.edge", 5, 15), host("pipeline.quantize", 20, 70),
    host("pipeline.encode", 90, 205), host("codec.encode", 95, 195),
    host("codec.histogram", 100, 30), host("gateway.batch", 300, 290),
    host("pipeline.decode_batch", 305, 175),
    host("pipeline.restore", 480, 10), host("gateway.cloud", 490, 100),
]
MODULES = [
    prog("jit_edge_forward(11035964930768719401)", 30, 50),
    prog("jit_gather(7)", 85, 3),
    prog("jit_histogram_kernel(2)", 110, 15),
    prog("jit_restore_codes_fused(3)", 495, 45),
    prog("jit_cnn_cloud(4)", 545, 40),
]
OPS = [
    op(EDGE, 30, 40), op(FUSION2, 70, 5),                   # edge: 45
    op("%gather.1 = f32[1,8]{1,0} gather(f32[8]{0} %a)", 85, 3),
    op(HIST, 110, 15),                                      # histogram: 15
    op(CONS, 495, 40), op(FUSION2, 535, 5),                 # restore: 45
    op("%convolution.3 = f32[8]{0} convolution(f32[8]{0} %a)", 545, 40),
    op("%copy.5 = f32[8]{0} copy(f32[8]{0} %a)", 900, 50),  # no program
]


@pytest.fixture
def base():
    """What ``trace.load`` keeps: ops and the benchmark's spans."""
    return BENCH_SPANS + OPS + [Event(DEV, "Steps", "step 0", 0.0, 1000.0)]


@pytest.fixture
def full(base):
    """What ``stages.load`` keeps besides."""
    return base + PROGRAM + MODULES


def test_program_names_drop_the_fingerprint():
    assert stages.module_name("jit_edge_forward(1103596)") == \
        "jit_edge_forward"
    assert stages.module_name("jit_cnn_cloud") == "jit_cnn_cloud"


def test_module_ns_sums_the_ops_inside_the_programs_events(full):
    assert stages.module_ns(full, "jit_edge_forward", 0, 1000) == 45.0
    assert stages.module_ns(full, "jit_histogram_kernel", 0, 1000) == 15.0
    # a prefix takes the fused and the reference restore alike
    assert stages.module_ns(full, "jit_restore_codes", 0, 1000) == 45.0
    assert stages.module_ns(full, "jit_cnn_cloud", 0, 1000) == 40.0
    # ops are counted where they start, as trace.kernel_ns counts them
    assert stages.module_ns(full, "jit_restore_codes", 0, 500) == 40.0
    assert stages.module_ns(full, "jit_nothing", 0, 1000) == 0.0
    # without program events no op is inside a program
    base = [e for e in full if e.line != "XLA Modules"]
    assert stages.module_ns(base, "jit_edge_forward", 0, 1000) == 0.0


def test_module_ns_keeps_each_plane_to_its_own_programs(full):
    other = "/device:TPU:1"
    two = full + [prog("jit_edge_forward(1)", 100, 50, plane=other),
                  op(FUSION2, 600, 10, plane=other)]
    # the second chip's op runs outside its plane's program event
    assert stages.module_ns(two, "jit_edge_forward", 0, 1000) == 45.0


def test_split_parts_add_up_to_the_op_time(full):
    split = stages.split_ns(full, 0, 1000)
    assert split == {"edge": 45.0, "histogram": 15.0, "restore": 45.0,
                     "cloud": 40.0, "other": 53.0}
    # the ops do not overlap here, so the parts add up to the busy time
    assert sum(split.values()) == trace.busy_ns(full, 0, 1000) == 198.0


def test_top_ops_carry_their_program(full):
    top = dict(stages.top_ops(full, 0, 1000, k=20))
    # same-numbered ops of two programs are no longer added together
    assert top["jit_edge_forward/%fusion.2 f32[8] fusion"] == \
        pytest.approx(5e-9)
    assert top["jit_restore_codes_fused/%fusion.2 f32[8] fusion"] == \
        pytest.approx(5e-9)
    assert top["jit_edge_forward/%fusion.1 bf16[512,8,65,32] fusion"] == \
        pytest.approx(40e-9)
    # an op outside any program keeps trace.top_ops's name
    assert top["%copy.5 f32[8] copy"] == pytest.approx(50e-9)
    assert stages.top_ops(full, 0, 1000, k=1) == [
        ["%copy.5 f32[8] copy", pytest.approx(50e-9)]]
    # with no program events the names are trace.top_ops's own
    base = [e for e in full if e.line != "XLA Modules"]
    assert stages.top_ops(base, 0, 1000) == trace.top_ops(base, 0, 1000)


def test_idle_gaps_are_named_by_the_path_of_spans_over_them(full):
    gaps = stages.idle_gaps(full, 0, 1000)
    assert gaps == [
        ["bench.serve/gateway.batch/pipeline.decode_batch",
         pytest.approx(370e-9)],                           # 125..495
        ["bench.wait", pytest.approx(315e-9)],             # 585..900
        ["bench.wait", pytest.approx(50e-9)],              # 950..1000
        ["bench.serve/gateway.edge", pytest.approx(30e-9)],
        ["bench.serve/pipeline.encode/codec.encode",
         pytest.approx(22e-9)],                            # 88..110
        ["bench.serve/pipeline.quantize", pytest.approx(10e-9)],
        ["bench.serve/gateway.batch/gateway.cloud", pytest.approx(5e-9)],
    ]
    # the same gaps as trace.idle_gaps, which names them by its own spans
    assert [s for _, s in gaps] == [
        s for _, s in trace.idle_gaps(full, 0, 1000, k=20)]
    assert [p.split("/")[0] for p, _ in gaps] == [
        n for n, _ in trace.idle_gaps(full, 0, 1000, k=20)]
    assert stages.idle_gaps(full, 0, 1000, k=1) == gaps[:1]
    assert stages.idle_gaps(full, 0, 1000, k=None) == gaps
    assert stages.idle_gaps(BENCH_SPANS + PROGRAM, 0, 1000) == []


def test_span_paths_name_each_time_by_the_spans_over_it():
    spans = BENCH_SPANS + PROGRAM
    # in any order; a time no span covers is "other"
    assert stages.span_paths(spans, [1000.0, 99.0, 600.0, 589.0, 0.0]) == [
        "other", "bench.serve/pipeline.encode/codec.encode", "bench.wait",
        "bench.serve/gateway.batch/gateway.cloud", "bench.serve"]
    assert stages.span_paths(spans, []) == []


def test_trace_selects_the_same_from_the_added_events(base, full):
    for lo, hi in ((0, 1000), (0, 500), (100, 900)):
        assert trace.device_ops(full) == trace.device_ops(base)
        assert trace.spans(full) == trace.spans(base)
        assert trace.busy_ns(full, lo, hi) == trace.busy_ns(base, lo, hi)
        assert trace.top_ops(full, lo, hi) == trace.top_ops(base, lo, hi)
        assert trace.idle_gaps(full, lo, hi) == trace.idle_gaps(base, lo, hi)
        of = manifest.reader_module("histogram_roofline").bytes_of(128, 8)
        assert trace.kernel_ns(full, of, lo, hi) == \
            trace.kernel_ns(base, of, lo, hi)
    assert trace.window(full) == trace.window(base) == (0.0, 1000.0)


@pytest.mark.parametrize("metric", [
    "device_idle_share.backlog", "device_idle_share.steady",
    "histogram_roofline.backlog", "encode_ms_per_req.backlog",
    "decode_ms_per_req.steady", "mfu.backlog", "mfu.steady"])
def test_existing_readers_read_the_same_with_the_added_events(
        metric, base, full):
    man = manifest.load()
    cfg = manifest.config(man, manifest.cell(man, "yolo3-c128.backlog"))
    d = drain.Drained(t0=0.0, due=np.zeros(8),
                      calls=[drain.Call(0, 8, 0.0, 4.0)], wake_late_s=0.0)

    def run(events):
        return SimpleNamespace(
            events=events, window_ns=trace.window(events), drained=d,
            peaks=peaks.peaks("TPU v5 lite"), chips=1, cfg=cfg,
            model=lambda part: manifest.model(cfg, part),
            stage={"pipeline.encode": (2.4, 8),
                   "pipeline.decode_batch": (1.6, 1)})
    read = manifest.reader(metric)
    got, want = read(run(full)), read(run(base))
    assert want is not None
    assert got == want


# -- a CPU rehearsal of the tool's window, at smoke_config() size ---------

SMOKE = {"width_mult": 0.25, "input_size": 128, "num_classes": 8,
         "tail_res_blocks": 1, "baf_hidden": 16}
PATH_STAGES = {"gateway.edge", "pipeline.quantize", "pipeline.encode",
               "codec.histogram", "gateway.batch", "pipeline.decode_batch",
               "pipeline.restore", "gateway.cloud"}


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "bench_stage_profile", BENCH / "stage_profile.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_tool_window_reads_every_stage_of_the_path(tool):
    man = manifest.load()
    c = manifest.cell(man, "yolo3-c128.backlog")
    cfg = {**manifest.config(man, c), **SMOKE, "c": 16}
    mix = {**manifest.traffic(c), "pool": 5}
    t0 = time.perf_counter()
    sut, plan, pool = tool.setup(cfg, mix, seed=2**31 + 5, seconds=1.0)
    assert time.perf_counter() - t0 < 120
    plain = tool.window(sut, plan, pool, 1.0, annotate=False)
    spans = tool.window(sut, plan, pool, 1.0, annotate=True)
    for row in (plain, spans):
        assert row["requests"] % 8 == 0 and row["requests"] >= 8
        assert row["compiles_in_window"] == 0
        assert PATH_STAGES <= set(row["stage_ms_per_req"])
        assert row["stage_ms_per_req"]["pipeline.encode"] > 0
    # the profiler holds the program's spans only when annotate is passed
    assert plain["program_spans"] == {}
    assert PATH_STAGES <= set(spans["program_spans"])
    assert spans["program_spans"]["gateway.edge"] == spans["requests"]
    assert spans["program_spans"]["gateway.batch"] == spans["requests"] // 8
