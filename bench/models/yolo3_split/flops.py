"""Operations one request needs, from the model's shapes.

Counts the multiply-adds of every conv and of the dense head, two FLOPs
each; elementwise work (BN, activations, quantize, consolidation) is left
out, as MFU conventionally does. The transposed conv is counted by its
input pixels (each of H x W inputs feeds a 3x3 window of the upsampled
output), not by a dilated-input lowering's zeros. The count is of the
algorithm, so it reads the same whatever implements it.
"""
from __future__ import annotations

STEM = ((3, 32, 3, 1), (32, 64, 3, 2), (64, 32, 1, 1), (32, 64, 3, 1),
        (64, 128, 3, 2), (128, 64, 1, 1), (64, 128, 3, 1), (128, 64, 1, 1),
        (64, 128, 3, 1))


def _ch(cfg: dict, c: int) -> int:
    return max(4, int(round(c * cfg["width_mult"])))


def conv_flops(h_out: int, w_out: int, k: int, cin: int, cout: int) -> int:
    return 2 * h_out * w_out * k * k * cin * cout


def edge_flops(cfg: dict) -> int:
    s, total = cfg["input_size"], 0
    for i, (cin, cout, k, stride) in enumerate(STEM):
        s //= stride
        total += conv_flops(s, s, k, 3 if i == 0 else _ch(cfg, cin),
                            _ch(cfg, cout))
    s //= 2
    return total + conv_flops(s, s, 3, _ch(cfg, 128), _ch(cfg, 256))


def restore_flops(cfg: dict) -> int:
    s = cfg["input_size"] // 8          # split tensor side
    hid, q, p = cfg["baf_hidden"], _ch(cfg, 128), _ch(cfg, 256)
    return (conv_flops(s, s, 3, cfg["c"], hid)          # transposed, x2
            + 2 * conv_flops(2 * s, 2 * s, 3, hid, hid)
            + conv_flops(2 * s, 2 * s, 3, hid, q)
            + conv_flops(s, s, 3, q, p))                # forward: split conv


def cloud_flops(cfg: dict) -> int:
    s = cfg["input_size"] // 8
    p, half = _ch(cfg, 256), _ch(cfg, 128)
    block = conv_flops(s, s, 1, p, half) + conv_flops(s, s, 3, half, p)
    return cfg["tail_res_blocks"] * block + 2 * p * cfg["num_classes"]


def request_flops(cfg: dict) -> int:
    """Edge + BaF restore + cloud of one request."""
    return edge_flops(cfg) + restore_flops(cfg) + cloud_flops(cfg)
