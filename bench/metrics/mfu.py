"""Whole request step's share of the chip's peak: the operations the
model's shapes need per request (edge + BaF restore + cloud, the model's
own FLOP function) times requests completed per second, over chips times
the bf16 peak. The configuration serves f32 at default precision, one bf16
pass per matmul, so the bf16 peak is its ceiling."""
from benchlib.stats import rate


def read(run):
    if run.peaks is None:
        return None
    d = run.drained
    per_s = rate(d.n, d.t0, d.last_end)
    flops = run.model("flops").request_flops(run.cfg)
    return 100.0 * flops * per_s / (run.chips * run.peaks.bf16_flops)
