"""Where a cell's traced window goes, by program stage and device program.

    python3 bench/stage_profile.py --workload yolo3-c128.backlog --seed 8111 \
        --seconds 51 --annotate 0,1

Sets the cell up as a benchmark run does, then runs its window under the
profiler once per value of ``--annotate``, in turn, with the program's
stage timers installed: with ``1`` they also write profiler spans
(``repro.obs.hooks`` with ``annotate=jax.profiler.TraceAnnotation``). Per
window it prints one JSON line: the requests served, the rate and the
latency tail, each stage's host time per request, the device time of each
request-path program per request and of the rest, the device's busy and
idle time, the longest idle gaps named by the spans that cover them and
all idle time summed by that name, and the ops that took most device
time, by program. No check against the
reference. Not part of a benchmark run: ``PERF.md`` records what it read.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def setup(cfg: dict, mix: dict, seed: int, seconds: float):
    """The cell's set-up, as a benchmark run makes it: (system, traffic
    plan, image pool), every batch size the mix uses served once."""
    from benchlib import manifest, traffic
    inputs = manifest.model(cfg, "inputs")
    weights = inputs.make_weights(cfg, seed)
    plan = traffic.plan(mix, seconds, seed)
    pool = inputs.make_images(cfg, seed, int(mix["pool"]))
    sut = manifest.model(cfg, "system").System(cfg, weights)
    for b in plan.warm:
        sut.serve(pool[:b])
    gc.collect()
    gc.freeze()
    return sut, plan, pool


def window(sut, plan, pool, seconds: float, annotate: bool) -> dict:
    """One traced window; returns its readings."""
    import jax
    import numpy as np

    from benchlib import drain, stages, stats, trace
    from benchlib.compiles import CompileCounter
    from repro.obs import hooks
    from repro.obs.metrics import MetricsRegistry

    def serve(first: int, n: int) -> None:
        k = np.arange(first, first + n) % len(plan.image_of)
        sut.serve(pool[plan.image_of[k]])

    registry, counter = MetricsRegistry(), CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-stages-")
    with hooks.active(registry, annotate=(jax.profiler.TraceAnnotation
                                          if annotate else None)):
        jax.profiler.start_trace(trace_dir)
        low0, _ = counter.snapshot()
        d = drain.drain(serve, clock=time.perf_counter, sleep=time.sleep,
                        window_s=seconds, offsets=plan.offsets,
                        take=plan.take, span=jax.profiler.TraceAnnotation)
        low1, _ = counter.snapshot()
        jax.profiler.stop_trace()
    events, _ = stages.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)

    stage_s: dict[str, float] = {}
    for name, labels, m in registry.collect():
        if name == "stage_seconds":
            stage = labels["stage"]
            stage_s[stage] = stage_s.get(stage, 0.0) + m.total
    spans: dict[str, int] = {}
    for e in events:
        if e.name in stages.PROGRAM_SPANS:
            spans[e.name] = spans.get(e.name, 0) + 1
    lo, hi = trace.window(events)
    busy = trace.busy_ns(events, lo, hi)
    split = stages.split_ns(events, lo, hi)
    gap_s: dict[str, float] = {}
    for path, sec in stages.idle_gaps(events, lo, hi, k=None):
        gap_s[path] = gap_s.get(path, 0.0) + sec
    return {
        "annotate": annotate, "requests": d.n,
        "compiles_in_window": low1 - low0,
        "throughput_rps": stats.rate(d.n, d.t0, d.last_end),
        "p50_latency_ms": stats.percentile(d.latency_s, 50) * 1e3,
        "p95_latency_ms": stats.percentile(d.latency_s, 95) * 1e3,
        "stage_ms_per_req": {k: v / d.n * 1e3
                             for k, v in sorted(stage_s.items())},
        "program_spans": dict(sorted(spans.items())),
        "device_ms_per_req": {k: v * 1e-6 / d.n for k, v in split.items()},
        "device_s": {k: v * 1e-9 for k, v in split.items()},
        "op_s": sum(split.values()) * 1e-9,
        "busy_s": busy * 1e-9, "window_s": (hi - lo) * 1e-9,
        "idle_share": 100.0 * (1.0 - busy / (hi - lo)),
        "idle_gaps": stages.idle_gaps(events, lo, hi),
        "idle_s_by_path": dict(sorted(gap_s.items(), key=lambda kv: -kv[1])),
        "device_ops": stages.top_ops(events, lo, hi, k=15),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--annotate", default="0,1",
                    help="comma-separated 0/1, one window each, in turn")
    args = ap.parse_args(argv)

    from benchlib import cell, manifest
    man = manifest.load()
    c = manifest.cell(man, args.workload)
    cell.require_chips(c["chips"])
    cell.enable_compile_cache(HERE / ".jax_cache")
    sut, plan, pool = setup(manifest.config(man, c), manifest.traffic(c),
                            args.seed, args.seconds)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "setup_s": time.perf_counter() - T_START}), flush=True)
    for a in args.annotate.split(","):
        row = window(sut, plan, pool, args.seconds, bool(int(a)))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
