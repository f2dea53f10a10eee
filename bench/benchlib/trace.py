"""Reduction of a profiler trace to device busy time, kernel time and the
breakdown of where the window went.

A trace is reduced from a flat list of :class:`Event` (plane, line, name,
start and duration in ns), so the arithmetic is checked on hand-built
events without a chip. :func:`load` reads the ``.xplane.pb`` that
``jax.profiler`` writes into that form, keeping the device planes' op lines
and the benchmark's own host spans.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE_PREFIX = "/device:"
# the line of a TPU device plane that holds one event per executed HLO op
DEVICE_OP_LINES = ("XLA Ops",)
HOST_SPANS = ("bench.serve", "bench.wait")


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> tuple[list[Event], list[str]]:
    """Device op events and benchmark host spans of the newest trace under
    ``trace_dir``, with a one-line listing of each plane's lines."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events, layout = [], []
    for plane in data.planes:
        lines = list(plane.lines)
        layout.append(f"{plane.name}: " + ", ".join(
            f"{ln.name}" for ln in lines))
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for ln in lines:
            if device and ln.name not in DEVICE_OP_LINES:
                continue
            for e in ln.events:
                if device or e.name in HOST_SPANS:
                    events.append(Event(plane.name, ln.name, e.name,
                                        float(e.start_ns),
                                        float(e.duration_ns)))
    return events, layout


def device_ops(events) -> list[Event]:
    return [e for e in events if e.plane.startswith(DEVICE_PLANE_PREFIX)
            and e.line in DEVICE_OP_LINES]


def spans(events) -> list[Event]:
    return [e for e in events if e.name in HOST_SPANS
            and not e.plane.startswith(DEVICE_PLANE_PREFIX)]


def window(events) -> tuple[float, float]:
    """The traced window: first benchmark span's start to last one's end."""
    s = spans(events)
    if not s:
        raise ValueError("trace holds no benchmark spans")
    return min(e.start_ns for e in s), max(e.end_ns for e in s)


def _clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of the intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which some op ran, averaged over the devices."""
    per_plane: dict[str, list] = {}
    for e in device_ops(events):
        per_plane.setdefault(e.plane, []).append((e.start_ns, e.end_ns))
    if not per_plane:
        return 0.0
    total = sum(sum(b - a for a, b in union(_clip(iv, lo, hi)))
                for iv in per_plane.values())
    return total / len(per_plane)


def kernel_ns(events, bytes_of, lo: float, hi: float
              ) -> tuple[float, float, int]:
    """Summed device time, summed bytes and count of the ops that start
    inside [lo, hi] and that ``bytes_of(name)`` recognizes (it returns the
    op's bytes, or None for an op that is not the kernel)."""
    t, nbytes, n = 0.0, 0.0, 0
    for e in device_ops(events):
        if lo <= e.start_ns < hi:
            b = bytes_of(e.name)
            if b is not None:
                t += e.dur_ns
                nbytes += b
                n += 1
    return t, nbytes, n


_TYPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\](\{[^}]*\})?")
_CUSTOM = re.compile(r"^\S+ = ([a-z]+[0-9]*\[[0-9,]*\]\{[^}]*\}) "
                     r"custom-call\((.*?)\), custom_call_target="
                     r"\"tpu_custom_call\"")


def _types(text: str) -> list[tuple[str, tuple[int, ...], int]]:
    """Each ``dtype[dims]{layout}`` in ``text`` as (dtype, shape, memory
    space): the layout's ``S(n)``, 0 (HBM) where it names none."""
    out = []
    for dtype, dims, layout in _TYPE.findall(text):
        space = re.search(r"S\((\d+)\)", layout or "")
        out.append((dtype, tuple(int(d) for d in dims.split(",") if d),
                    int(space.group(1)) if space else 0))
    return out


def kernel_call(name: str):
    """(output type, [operand types]) of a Pallas kernel's op, each type a
    (dtype, shape, memory space) triple, read from the HLO text the TPU
    trace names its ops by; None for any other op. A kernel is known by its
    signature, since the trace does not carry the kernel's own name."""
    m = _CUSTOM.match(name)
    if m is None:
        return None
    return _types(m.group(1))[0], _types(m.group(2))


def short_name(name: str) -> str:
    """An op's HLO text cut to its name, output type and opcode."""
    m = re.match(r"^(\S+) = (\S+?)(?:\{[^}]*\})? ([a-z-]+)\(", name)
    return " ".join(m.groups()) if m else name[:120]


def top_ops(events, lo: float, hi: float, k: int = 10):
    """The ``k`` ops (by short name) that took most device time, as
    [name, seconds]."""
    tot: dict[str, float] = {}
    for e in device_ops(events):
        if lo <= e.start_ns < hi:
            name = short_name(e.name)
            tot[name] = tot.get(name, 0.0) + e.dur_ns
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_gaps(events, lo: float, hi: float, k: int = 10):
    """The ``k`` longest stretches of [lo, hi] with no op on the first
    device, as [what the host was doing, seconds]: the benchmark span that
    covers the gap's middle, or "other"."""
    planes = sorted({e.plane for e in device_ops(events)})
    if not planes:
        return []
    busy = union(_clip(((e.start_ns, e.end_ns) for e in device_ops(events)
                        if e.plane == planes[0]), lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = spans(events)
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) / 2
        name = next((s.name for s in host
                     if s.start_ns <= mid < s.end_ns), "other")
        out.append([name, (b - a) * 1e-9])
    return out
