"""Share of its roofline that the symbol-histogram kernel
(``kernels/histogram.py``, on the encode path, one call per request)
reaches in the window.

The kernel is known in the trace by its signature: a TPU custom call that
takes (R, C) int32 codes and returns (2^bits, C) int32 counts. Bytes per
call from those logical shapes: the codes in, the counts out. Its
compare-and-count is VPU work with no published peak to bound it, so the
roofline is the HBM bound: bytes / peak bandwidth, over the summed device
time of the kernel's events. That bound holds only while the codes and the
counts live in HBM: where the compiler has placed either in VMEM (memory
space ``S(1)`` in the op's layout), the kernel reads no HBM and the reader
finds nothing to read.
"""
from benchlib import trace


def call_bytes(r: int, c: int, bits: int) -> int:
    return 4 * r * c + 4 * (1 << bits) * c


def bytes_of(c: int, bits: int):
    def of(name: str):
        sig = trace.kernel_call(name)
        if sig is None:
            return None
        out, ops = sig
        if (out[:2] != ("s32", (1 << bits, c)) or len(ops) != 1
                or ops[0][0] != "s32" or ops[0][1][1:] != (c,)):
            return None
        if out[2] or ops[0][2]:
            return None             # held in VMEM: no HBM bound
        return call_bytes(ops[0][1][0], c, bits)
    return of


def read(run):
    if run.events is None or run.peaks is None:
        return None
    lo, hi = run.window_ns
    ns, nbytes, calls = trace.kernel_ns(
        run.events, bytes_of(run.cfg["c"], run.cfg["bits"]), lo, hi)
    if calls == 0 or ns <= 0:
        return None
    return 100.0 * nbytes / run.peaks.hbm_bytes_per_s / (ns * 1e-9)
