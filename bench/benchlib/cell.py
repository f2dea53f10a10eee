"""One run of one cell: set-up, the measured window, the check, the result.

    set-up   seeded weights and image pool on the device, the system built
             from them, one serve call per batch size the mix uses
    window   the drain loop for ``--seconds``; with ``--trace 1`` under the
             profiler, with the program's stage timers installed
    check    once the window has closed, the device's peak memory read and
             the program's state freed: every served request against the
             plain reference on its image
    result   the cell's end-to-end metrics (``--trace 0``) or per-layer
             metrics (``--trace 1``), each from its own reader file
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any

import numpy as np

from benchlib import compare, drain, manifest, peaks, trace, traffic
from benchlib.compiles import CompileCounter


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def enable_compile_cache(path) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    # cache every program: the serving path's kernels compile in well under
    # JAX's default threshold, and a cold start would recompile them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@dataclass
class Run:
    """What a metric reader reads."""
    cell: dict
    cfg: dict
    mix: dict
    seconds: float
    chips: int
    setup_s: float
    drained: drain.Drained
    batches: list               # [(requests, padded size)] in the window
    wire_bytes: int
    peaks: Any                  # benchlib.peaks.ChipPeaks | None
    model: Any                  # part name -> module of the cell's model
    stage: Any = None           # stage -> (seconds, calls); traced runs
    events: Any = None          # trace events; traced runs
    window_ns: Any = None       # (start, end) of the traced window


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def execute(man: dict, cell: dict, cfg: dict, mix: dict, *, seed: int,
            seconds: float, trace_on: bool, t_start: float, devices,
            control: bool = False):
    """Runs the cell once at configuration ``cfg`` under traffic mix
    ``mix``; returns (result line dict, checks dict, extra readings)."""
    import jax

    inputs = manifest.model(cfg, "inputs")
    system = manifest.model(cfg, "system")
    counter = CompileCounter()

    marks = [("start", time.perf_counter() - t_start)]
    weights = inputs.make_weights(cfg, seed)
    marks.append(("weights", time.perf_counter() - t_start))
    plan = traffic.plan(mix, seconds, seed)
    pool = inputs.make_images(cfg, seed, int(mix["pool"]))
    marks.append(("images", time.perf_counter() - t_start))
    sut = system.System(cfg, weights)
    for b in plan.warm:
        sut.serve(pool[:b])
        marks.append((f"warm {b}", time.perf_counter() - t_start))
    sut.meter.reset()
    # Keep the set-up's objects (the imported modules, weights, compiled
    # programs) out of the window's cyclic collections, as latency-minded
    # Python servers do: a full collection over them stalls the process
    # for tens of milliseconds, at random points of the window.
    gc.collect()
    gc.freeze()
    log("set-up at " + ", ".join(f"{k} {v:.2f} s" for k, v in marks)
        + f": {counter.lowerings} lowerings, {counter.backend_compiles} "
        f"backend compiles ({counter.compile_s:.1f} s), cache hits "
        f"{counter.cache_hits}, misses {counter.cache_misses}")

    logits: dict[int, np.ndarray] = {}
    block: dict[int, int] = {}          # request -> padded size of its batch
    batches: list = []

    def serve(first: int, n: int) -> None:
        idx = plan.image_of[np.arange(first, first + n) % len(plan.image_of)]
        out = sut.serve(pool[idx])
        for j in range(n):
            logits[first + j] = out.logits[j]
            block[first + j] = int(out.padded[j])
        batches.extend(out.batches)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace_on else None
    span = (jax.profiler.TraceAnnotation if trace_on
            else lambda name: contextlib.nullcontext())
    timers = system.stage_timers() if trace_on else contextlib.nullcontext()
    with timers as stage_get:
        if trace_on:
            jax.profiler.start_trace(trace_dir)
        low0, comp0 = counter.snapshot()
        drained = drain.drain(serve, clock=time.perf_counter,
                              sleep=time.sleep,
                              window_s=seconds, offsets=plan.offsets,
                              take=plan.take, span=span)
        low1, comp1 = counter.snapshot()
        if trace_on:
            jax.profiler.stop_trace()
        stage = ({s: stage_get(s) for s in
                  ("pipeline.encode", "pipeline.decode_batch")}
                 if trace_on else None)
    setup_s = drained.t0 - t_start
    compiles_in_window = low1 - low0
    print(f"compiles_in_window={compiles_in_window} "
          f"backend_compiles_in_window={comp1 - comp0}", flush=True)
    log(f"window: {drained.n} requests in {len(drained.calls)} serve calls, "
        f"{len(batches)} micro-batches, last completion "
        f"{drained.last_end - drained.t0:.3f} s after the start; "
        f"worst wake-up lateness {drained.wake_late_s * 1e3:.3f} ms; "
        f"compiles in window {compiles_in_window}")

    stats = devices[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    wire_bytes = sut.meter.bytes
    del sut
    gc.collect()

    # -- the check: every served request against the reference ------------
    # The reference runs each request's cloud half in a block of the size
    # of the micro-batch that served it: at the default matmul precision
    # the compiler's choice of conv algorithm depends on the batch, and two
    # sound programs of different batch then differ by more than rounding.
    t_check = time.perf_counter()
    reference = manifest.model(cfg, "reference")
    pool_idx = plan.image_of[np.arange(drained.n) % len(plan.image_of)]
    classes = cfg["num_classes"]
    rows = [logits.get(k) for k in range(drained.n)]
    missing = compare.unanswered(rows, drained.n, classes)
    answered = [k for k in range(drained.n) if rows[k] is not None
                and np.shape(rows[k]) == (classes,)]
    keys = sorted({(int(pool_idx[k]), block[k]) for k in answered})

    def reference_rows(dtype: str) -> dict:
        out = {}
        for b in sorted({b for _, b in keys}):
            imgs = [i for i, bb in keys if bb == b]
            got = reference.logits(cfg, weights, pool[imgs], dtype=dtype,
                                   block=b)
            out.update(((i, b), row) for i, row in zip(imgs, got))
        return out
    ref = reference_rows("float32")
    err = compare.row_rel_err(
        np.stack([rows[k] for k in answered]),
        np.stack([ref[(int(pool_idx[k]), block[k])] for k in answered])
    ) if answered else np.array([])
    readings = {"logits_rel_err": float(err.max()) if len(err) else np.inf,
                "unanswered": missing}
    correct, checks = compare.judge(readings, cfg["check"])
    extra = {"drained": drained, "batches": batches,
             "compiles_in_window": compiles_in_window}
    if control:
        ctl = reference_rows(cfg["control_dtype"])
        extra["control_logits_rel_err"] = float(compare.row_rel_err(
            np.stack([ctl[key] for key in keys]),
            np.stack([ref[key] for key in keys])).max())
    log(f"check: {len(answered)} served requests ({len(keys)} pairs of "
        f"image and batch size) against the reference in "
        f"{time.perf_counter() - t_check:.2f} s")

    # -- metrics -----------------------------------------------------------
    kind = devices[0].device_kind
    run = Run(cell=cell, cfg=cfg, mix=mix, seconds=seconds,
              chips=len(devices), setup_s=setup_s, drained=drained,
              batches=batches, wire_bytes=wire_bytes,
              # an unlisted chip is an error; the CPU of a rehearsal has none
              peaks=(peaks.peaks(kind) if devices[0].platform == "tpu"
                     else None),
              model=lambda part: manifest.model(cfg, part), stage=stage)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace_on:
        run.events, layout = trace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for line in layout:
            log(f"trace plane {line}")
        lo, hi = trace.window(run.events)
        run.window_ns = (lo, hi)
        for name, sec in trace.top_ops(run.events, lo, hi, k=30):
            log(f"trace op {sec:.6f} s: {name}")
        device["busy_s"] = trace.busy_ns(run.events, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        breakdown = {"device_ops": trace.top_ops(run.events, lo, hi),
                     "idle_gaps": trace.idle_gaps(run.events, lo, hi)}
    kind_key = "per_layer" if trace_on else "end_to_end"
    metrics = {}
    for m in manifest.metrics_for(man, cell["name"], kind_key):
        value = manifest.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": drained.n,
              "failed": missing, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, checks, extra


def main(args, *, t_start: float, cache_dir, require=require_chips) -> int:
    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    try:
        devices = require(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if cache_dir is not None:
        enable_compile_cache(cache_dir)
    result, checks, _ = execute(man, cell, manifest.config(man, cell),
                                manifest.traffic(cell), seed=args.seed,
                                seconds=args.seconds, trace_on=args.trace,
                                t_start=t_start, devices=devices)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
