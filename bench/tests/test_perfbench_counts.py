"""The yardsticks against hand counts at the paper's shapes: the model's
FLOPs per request, the kernels' bytes per call, the peak table."""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import pytest  # noqa: E402

from benchlib import manifest, peaks  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.fixture
def flops():
    return manifest.model(cfg("yolo3-l12-c8"), "flops")


def test_edge_flops_by_hand(flops):
    # 2 * H * W * k * k * cin * cout per conv, at 512 px
    l1 = 2 * 512 * 512 * 9 * 3 * 32
    three_by_three = 2 * 256 * 256 * 9 * 32 * 64        # l2, res1.b
    assert three_by_three == 2 * 128 * 128 * 9 * 64 * 128   # l5, res2/3.b
    assert three_by_three == 2 * 64 * 64 * 9 * 128 * 256    # split conv
    one_by_one = 2 * 256 * 256 * 64 * 32                 # res1.a, res2/3.a
    want = l1 + 6 * three_by_three + 3 * one_by_one
    assert want == 15_753_805_824
    assert flops.edge_flops(cfg("yolo3-l12-c8")) == want


def test_restore_flops_by_hand(flops):
    up8 = 2 * 64 * 64 * 9 * 8 * 64            # transposed conv, input side
    mid = 2 * 128 * 128 * 9 * 64 * 64         # c2, c3
    c4 = 2 * 128 * 128 * 9 * 64 * 128
    fwd = 2 * 64 * 64 * 9 * 128 * 256         # split conv again
    assert flops.restore_flops(cfg("yolo3-l12-c8")) == \
        up8 + 2 * mid + c4 + fwd == 7_285_506_048
    assert flops.restore_flops(cfg("yolo3-l12-c128")) == \
        16 * up8 + 2 * mid + c4 + fwd == 7_851_737_088


def test_cloud_and_request_flops_by_hand(flops):
    block = 2 * 64 * 64 * 256 * 128 + 2 * 64 * 64 * 9 * 128 * 256
    cloud = 2 * block + 2 * 256 * 80
    assert flops.cloud_flops(cfg("yolo3-l12-c8")) == cloud == 5_368_750_080
    assert flops.request_flops(cfg("yolo3-l12-c8")) == \
        15_753_805_824 + 7_285_506_048 + cloud


def test_kernel_bytes_by_hand():
    hist = manifest.reader_module("histogram_roofline")
    # int32 codes in, 2^8 int32 counts per channel out
    assert hist.call_bytes(4096, 8, 8) == 4 * 4096 * 8 + 4 * 256 * 8 == \
        139_264
    assert hist.call_bytes(4096, 128, 8) == 4 * 4096 * 128 + 4 * 256 * 128 \
        == 2_228_224


def test_peak_table_has_its_source_and_refuses_unknown_devices():
    v5e = peaks.peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")
