"""Where a traced window went, by the program's own names: its stage spans
on the host and its device programs.

:mod:`benchlib.trace` keeps the device planes' op lines and the benchmark's
own spans (``bench.serve``, ``bench.wait``). This module reads the rest of
what a profile of the serving path holds:

    program spans   the stage timers of ``repro.obs.hooks``, which write
                    profiler spans when installed with ``annotate``
    device programs the device planes' ``XLA Modules`` line: one event per
                    executed program, named ``jit_<function>(<fingerprint>)``

Its :func:`load` returns a superset of ``trace.load``'s events, and every
function of :mod:`benchlib.trace` selects from it exactly what it selects
from those, so the benchmark's readers read the same on either.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from benchlib import trace
from benchlib.trace import DEVICE_PLANE_PREFIX, DEVICE_OP_LINES, Event

# the line of a TPU device plane that holds one event per executed program
MODULE_LINES = ("XLA Modules",)
# the stage timers on the request path (repro.obs.hooks.timed)
PROGRAM_SPANS = ("gateway.edge", "pipeline.quantize", "pipeline.encode",
                 "codec.encode", "codec.histogram", "gateway.batch",
                 "pipeline.decode_batch", "codec.decode_batch",
                 "pipeline.restore", "gateway.cloud")
# the request path's device programs; the eager quantizer's ops run as
# programs of their own and fall outside these
MODULES = {"edge": "jit_edge_forward", "histogram": "jit_histogram_kernel",
           "restore": "jit_restore_codes", "cloud": "jit_cnn_cloud"}


def load(trace_dir: str) -> tuple[list[Event], list[str]]:
    """``trace.load``'s events, plus the device planes' program events and
    the program's stage spans, from the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    keep_lines = DEVICE_OP_LINES + MODULE_LINES
    keep_spans = trace.HOST_SPANS + PROGRAM_SPANS
    events, layout = [], []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = list(plane.lines)
        layout.append(f"{plane.name}: " + ", ".join(ln.name for ln in lines))
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for ln in lines:
            if device and ln.name not in keep_lines:
                continue
            for e in ln.events:
                if device or e.name in keep_spans:
                    events.append(Event(plane.name, ln.name, e.name,
                                        float(e.start_ns),
                                        float(e.duration_ns)))
    return events, layout


def module_name(name: str) -> str:
    """A program event's name without its fingerprint."""
    return re.sub(r"\(\d+\)$", "", name)


def op_modules(events, lo: float, hi: float):
    """Each device op that starts inside [lo, hi], with the name of the
    program whose event on the same plane covers its start (None where no
    program event does)."""
    progs: dict[str, list[Event]] = {}
    for e in events:
        if (e.plane.startswith(DEVICE_PLANE_PREFIX)
                and e.line in MODULE_LINES):
            progs.setdefault(e.plane, []).append(e)
    for p in progs.values():
        p.sort(key=lambda e: e.start_ns)
    starts = {k: [e.start_ns for e in p] for k, p in progs.items()}
    for op in trace.device_ops(events):
        if not lo <= op.start_ns < hi:
            continue
        p = progs.get(op.plane, [])
        i = bisect.bisect_right(starts.get(op.plane, []), op.start_ns) - 1
        mod = (module_name(p[i].name)
               if i >= 0 and op.start_ns < p[i].end_ns else None)
        yield op, mod


def module_ns(events, prefix: str, lo: float, hi: float) -> float:
    """Summed device time of the ops that start inside [lo, hi] within the
    events of the programs whose name starts with ``prefix``."""
    return sum(op.dur_ns for op, mod in op_modules(events, lo, hi)
               if mod is not None and mod.startswith(prefix))


def split_ns(events, lo: float, hi: float) -> dict[str, float]:
    """Device op time in [lo, hi] per request-path program of
    :data:`MODULES`, and ``other`` for ops of every other program or of
    none: the parts sum to all the op time that starts in the window."""
    out = dict.fromkeys([*MODULES, "other"], 0.0)
    for op, mod in op_modules(events, lo, hi):
        part = next((k for k, prefix in MODULES.items()
                     if mod is not None and mod.startswith(prefix)), "other")
        out[part] += op.dur_ns
    return out


def top_ops(events, lo: float, hi: float, k: int = 10):
    """``trace.top_ops`` with each op qualified by its program
    (``jit_edge_forward/%fusion.2 bf16[512,8,65,32] fusion``), so that
    same-numbered ops of different programs are not added together. An op
    outside any program event keeps its short name."""
    tot: dict[str, float] = {}
    for op, mod in op_modules(events, lo, hi):
        name = trace.short_name(op.name)
        if mod is not None:
            name = f"{mod}/{name}"
        tot[name] = tot.get(name, 0.0) + op.dur_ns
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


def span_paths(host_spans, ts) -> list[str]:
    """For each time in ``ts``, the host spans that cover it, outermost
    first, joined by ``/`` (``bench.serve/pipeline.encode/codec.encode``),
    or ``other``. One sweep over the spans in order of start."""
    spans = sorted(host_spans, key=lambda s: (s.start_ns, -s.end_ns))
    out = [""] * len(ts)
    active, j = [], 0
    for i in sorted(range(len(ts)), key=lambda i: ts[i]):
        t = ts[i]
        while j < len(spans) and spans[j].start_ns <= t:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s.end_ns > t]
        out[i] = "/".join(s.name for s in active) or "other"
    return out


def idle_gaps(events, lo: float, hi: float, k: int | None = 10):
    """``trace.idle_gaps``, each gap named by the path of the benchmark's
    and the program's spans that cover its middle; every gap where ``k``
    is None."""
    planes = sorted({e.plane for e in trace.device_ops(events)})
    if not planes:
        return []
    busy = trace.union(
        (max(e.start_ns, lo), min(e.end_ns, hi))
        for e in trace.device_ops(events)
        if e.plane == planes[0] and e.end_ns > lo and e.start_ns < hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    host = [e for e in events if e.name in trace.HOST_SPANS + PROGRAM_SPANS
            and not e.plane.startswith(DEVICE_PLANE_PREFIX)]
    paths = span_paths(host, [(a + b) / 2 for a, b in gaps])
    return [[path, (b - a) * 1e-9] for path, (a, b) in zip(paths, gaps)]
