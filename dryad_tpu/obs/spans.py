"""Lightweight trace spans over the registry: per-stage wall/count series.

    with span("train.chunk_dispatch"):
        ... host-side work ...

Each exit adds the span's wall seconds to ``dryad_span_seconds_total`` and
1 to ``dryad_span_count_total``, labeled with the span's PATH.  Spans nest
per thread: a span opened inside another records under
``parent_path/name`` (tree -> level -> stage reads as
``tree/level/stage``), so per-stage series decompose their parent's wall
(children sum <= parent wall — test-pinned).

The timing here is HOST wall around work the caller already performs —
wrapping an existing fetch measures that fetch; no span ever ADDS a
device fetch or sync (the registry's host-side contract).  What the device
did meanwhile is the profiler's to say: with an ANNOTATOR installed
(``set_annotator``, below) every span is also an interval on the
profiler's own clock, beside the device's operations.

Zero-cost when disabled: ``span()`` returns one shared null context
manager before touching the clock, and ``record()`` returns after the
enabled check — both allocation-free (test-pinned with tracemalloc).

``record(name, seconds)`` feeds the same series without a ``with`` block,
for loop bodies where a context manager would force a reindent across
``break`` edges (both trainers use it for their per-iteration series).

r13: an optional TRACE SINK (``set_trace_sink``) receives every completed
span as ``(path, t0_s, dur_s)`` — ``obs/trace_export.py`` installs a ring
buffer there and renders Chrome trace_event JSON from it.  The sink fires
only on the registry-enabled path (the disabled fast path is untouched)
and a sink exception never propagates into the instrumented caller.

An optional ANNOTATOR (``set_annotator(factory)``) puts every span on a
second clock: ``factory(path)`` must return a context manager, entered at
the span's ``__enter__`` and left at its ``__exit__``.  This package stays
jax-free; ``dryad_tpu.engine`` installs ``jax.profiler.TraceAnnotation``,
so under ``dryad.train(profile_dir=...)`` each span shows by its path on
the host plane of the jax profile, on the time axis of the device's
operations (with no profiler session an annotation is one atomic load).
``annotation(path)`` gives the same interval alone, for a loop body that
is entered and left by hand and recorded with ``record_at``.  Like the
sink, the annotator is consulted only on the registry-enabled path and a
factory or context manager that raises never reaches the caller.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from dryad_tpu.obs.registry import Registry, default_registry

SECONDS = "dryad_span_seconds_total"
COUNT = "dryad_span_count_total"

_TLS = threading.local()

#: trace sink: None, or a callable(path, t0_s, dur_s) — see module doc
_TRACE_SINK = None


def set_trace_sink(sink) -> None:
    """Install (or clear, with ``None``) the span trace sink.  The sink
    must be cheap and non-raising and accept ``(path, t0_s, dur_s)``
    plus an optional keyword-able 4th ``trace`` argument (r17);
    trace_export.SpanTrace.record is the intended one."""
    global _TRACE_SINK
    _TRACE_SINK = sink


#: annotator: None, or a callable(path) -> context manager — see module doc
_ANNOTATOR = None


def set_annotator(factory) -> None:
    """Install (or clear, with ``None``) the span annotator: every span
    enters ``factory(path)`` when it starts and leaves it when it ends."""
    global _ANNOTATOR
    _ANNOTATOR = factory


class _Annotation:
    """One annotator interval that cannot raise into its caller."""

    __slots__ = ("_cm",)

    def __init__(self, factory, path: str):
        try:
            self._cm = factory(path)
            self._cm.__enter__()
        except Exception:   # noqa: BLE001 — tracing must never break
            self._cm = None  # the instrumented caller

    def close(self) -> None:
        cm, self._cm = self._cm, None
        if cm is not None:
            try:
                cm.__exit__(None, None, None)
            except Exception:   # noqa: BLE001 — as above
                pass


def annotation(path: str, registry: Optional[Registry] = None):
    """Open the annotator's interval for ``path`` by hand; ``close()`` the
    result to end it.  ``None`` (nothing to close) when the registry is
    disabled or no annotator is installed."""
    factory = _ANNOTATOR
    if factory is None:
        return None
    reg = registry if registry is not None else default_registry()
    if not reg.enabled:
        return None
    return _Annotation(factory, path)


def sink_active() -> bool:
    """Whether a span trace sink is installed (the ring is listening)."""
    return _TRACE_SINK is not None


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def _emit(reg: Registry, path: str, seconds: float) -> None:
    # count BEFORE seconds (and snapshot() reads seconds before counts): a
    # scrape tearing between the two families then at worst sees a span
    # with count=1 and a not-yet-summed wall (benign), never the
    # self-contradictory total_s > 0 with count 0
    reg.counter(COUNT, "Completions per span path").labels(span=path).inc()
    reg.counter(SECONDS, "Aggregate wall seconds per span path").labels(
        span=path).inc(seconds)


class _Span:
    __slots__ = ("_reg", "name", "path", "_t0", "_ann")

    def __init__(self, reg: Registry, name: str):
        self._reg = reg
        self.name = name
        self.path = name
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        if stack:
            self.path = stack[-1].path + "/" + self.name
        stack.append(self)
        factory = _ANNOTATOR
        if factory is not None:
            self._ann = _Annotation(factory, self.path)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.close()
        stack = _TLS.stack
        if stack and stack[-1] is self:
            stack.pop()
        _emit(self._reg, self.path, dt)
        sink = _TRACE_SINK
        if sink is not None:
            try:
                sink(self.path, self._t0, dt)
            except Exception:   # noqa: BLE001 — tracing must never break
                pass            # the instrumented caller
        return False


def span(name: str, registry: Optional[Registry] = None):
    """A context manager timing one stage into the span series (nested
    under the thread's enclosing span, if any)."""
    reg = registry if registry is not None else default_registry()
    if not reg.enabled:
        return _NULL
    return _Span(reg, name)


def record(name: str, seconds: float,
           registry: Optional[Registry] = None) -> None:
    """Record one completed stage without a ``with`` block.  The name is
    taken as a FULL path (no nesting prefix) — callers timing a loop body
    manually own their naming."""
    reg = registry if registry is not None else default_registry()
    if not reg.enabled:
        return
    _emit(reg, name, seconds)
    sink = _TRACE_SINK
    if sink is not None:
        try:
            # the stage just ENDED; back-date its start by its duration
            sink(name, time.perf_counter() - seconds, seconds)
        except Exception:   # noqa: BLE001 — tracing must never break callers
            pass


def record_at(name: str, t0_s: float, seconds: float,
              trace: Optional[str] = None,
              registry: Optional[Registry] = None) -> None:
    """Record a completed stage with an EXPLICIT start time and an
    optional request trace id (r17: the serve/fleet request path stamps
    its per-request stage spans after the fact, from timestamps carried
    across the batcher hand-off — back-dating via ``record`` would lie
    about when the stage ran)."""
    reg = registry if registry is not None else default_registry()
    if not reg.enabled:
        return
    _emit(reg, name, seconds)
    sink = _TRACE_SINK
    if sink is not None:
        try:
            sink(name, t0_s, seconds, trace)
        except Exception:   # noqa: BLE001 — tracing must never break callers
            pass


def snapshot(registry: Optional[Registry] = None) -> dict:
    """``{path: {"count": n, "total_s": s, "mean_ms": m}}`` — the span
    slice of the registry, shaped for the ``/stats`` endpoint."""
    reg = registry if registry is not None else default_registry()
    walls = reg.counter(SECONDS).series()     # seconds first — see _emit
    counts = reg.counter(COUNT).series()

    def path_of(lbl: str) -> str:
        # label block is span="<path>"
        return lbl.split('"', 2)[1] if '"' in lbl else lbl

    out = {}
    for lbl, total in walls.items():
        n = counts.get(lbl, 0.0)
        out[path_of(lbl)] = {
            "count": int(n),
            "total_s": round(total, 6),
            "mean_ms": round(total / n * 1e3, 3) if n else 0.0,
        }
    return out
