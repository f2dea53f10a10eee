"""Zero-cost-when-disabled instrumentation hooks for deep library code.

The gateway takes explicit ``tracer=`` / ``metrics=`` arguments, but stages
buried under it — ``pipeline.plan`` encode/decode/restore, the rANS codec's
encode/decode loops — cannot thread a registry through every call site
without polluting the pipeline API. This module gives them a process-global
hook instead:

    from repro.obs import hooks
    with hooks.timed("pipeline.encode"):
        ...body...

When no registry is installed (the default), ``timed`` returns one shared
no-op context manager and ``observe``/``count`` return immediately after a
single ``is None`` check — the hot path stays untouched, which is what lets
the tracing-enabled gateway hold >=0.95x untraced throughput (the CI obs job
gates this).

Wall-clock durations recorded here go into metrics histograms, never into
the virtual-clock trace — traces stay byte-identical under replay (see
repro.obs.trace). An installer that also passes ``annotate`` (a context
manager factory ``annotate(name, **labels)``, e.g.
``jax.profiler.TraceAnnotation``) gets each timed stage as a profiler span
over the same interval, on the clock the profiler's device planes share.
This module imports no JAX: the installer brings the annotation.
"""
from __future__ import annotations

import contextlib
import time
from collections.abc import Callable

from repro.obs.metrics import MetricsRegistry

Annotate = Callable[..., contextlib.AbstractContextManager]

_REGISTRY: MetricsRegistry | None = None
_ANNOTATE: Annotate | None = None


class _NullTimer:
    """Shared no-op timer handed out when instrumentation is disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullTimer()


class _StageTimer:
    __slots__ = ("_hist", "_t0", "_span")

    def __init__(self, hist, span=None):
        self._hist = hist
        self._t0 = 0.0
        self._span = span

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._hist.observe(time.perf_counter() - self._t0)
        if self._span is not None:
            self._span.__exit__(*exc)
        return False


def install(registry: MetricsRegistry,
            annotate: Annotate | None = None) -> None:
    """Route stage timers/observations into ``registry`` until uninstall;
    with ``annotate``, each timed stage also enters ``annotate(stage,
    **labels)`` around the interval it times."""
    global _REGISTRY, _ANNOTATE
    _REGISTRY = registry
    _ANNOTATE = annotate


def uninstall() -> None:
    global _REGISTRY, _ANNOTATE
    _REGISTRY = None
    _ANNOTATE = None


def installed() -> MetricsRegistry | None:
    return _REGISTRY


def enabled() -> bool:
    return _REGISTRY is not None


@contextlib.contextmanager
def active(registry: MetricsRegistry, annotate: Annotate | None = None):
    """Scoped install (benchmarks, tests): uninstalls on exit, always."""
    install(registry, annotate)
    try:
        yield registry
    finally:
        uninstall()


def timed(stage: str, **labels):
    """Context manager timing its body into the ``stage_seconds`` histogram
    labeled ``stage=...`` (wall clock), and into a profiler span of the
    same name and labels when the installer passed ``annotate``. No-op when
    disabled."""
    r = _REGISTRY
    if r is None:
        return _NULL
    a = _ANNOTATE
    return _StageTimer(r.histogram("stage_seconds", stage=stage, **labels),
                       None if a is None else a(stage, **labels))


def observe(name: str, value: float, **labels) -> None:
    """Record one histogram observation. No-op when disabled."""
    r = _REGISTRY
    if r is not None:
        r.histogram(name, **labels).observe(value)


def count(name: str, value: float = 1.0, **labels) -> None:
    """Bump a counter. No-op when disabled."""
    r = _REGISTRY
    if r is not None:
        r.counter(name, **labels).inc(value)
