"""Span tracer: nested, thread-local timing regions.

``with telemetry.span("fwdbwd", step=n):`` stamps one region. Spans

- nest per thread (a thread-local stack tracks the enclosing span, so
  every record knows its parent and depth),
- aggregate into the ``mxtpu.span_seconds`` histogram (labelled by span
  name) — the per-phase totals ``tools/trace_summary.py`` and the
  Prometheus dump report,
- enter a ``jax.profiler.TraceAnnotation`` of the same name, so that
  while a ``jax.profiler`` trace is being taken every span lands in the
  host plane of the same ``.xplane.pb`` as the device ops, on the
  profiler's clock (bench/reduce_trace.py names idle gaps by them),
- carry the step number as the shared identifier: a span opened with
  ``step=`` hands it to every span opened inside it,
- emit a complete chrome-trace ``"X"`` event into the profiler's event
  buffer when the profiler is running, so one ``profile.json`` shows
  framework spans alongside jax.profiler device traces,
- append a JSONL record when ``MXTPU_TELEMETRY_FILE`` export is active.

When telemetry is disabled ``span()`` returns a shared no-op context
manager — no allocation, no clock read, no annotation constructed.
"""
from __future__ import annotations

import threading
import time

from . import registry as _reg

_tls = threading.local()

SPAN_SECONDS = _reg.histogram(
    "mxtpu.span_seconds", "time spent inside telemetry spans, by name")


class _NullSpan:
    """Shared disabled-path span: every method is a no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attrs(self, **attrs):
        pass

    def discard(self):
        pass


_NULL = NULL_SPAN = _NullSpan()

# what an open span reports to, resolved at the first enabled span and
# not at import: profiler pulls in jax at call sites and must never
# become a hard dependency of the metrics layer
_sinks = None


def _resolve_sinks():
    global _sinks
    from jax.profiler import TraceAnnotation

    from .. import profiler as _profiler
    from . import export as _export

    _sinks = (TraceAnnotation, _profiler.record_event_complete,
              _export.emit_span)
    return _sinks


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class Span:
    __slots__ = ("name", "attrs", "parent", "depth", "_t0", "_ts_us",
                 "duration", "_annotation", "_discarded")

    def __init__(self, name, attrs):
        self.name = name
        self.attrs = attrs
        self.parent = None
        self.depth = 0
        self.duration = None
        self._discarded = False

    def set_attrs(self, **attrs):
        self.attrs.update(attrs)

    def discard(self):
        """Close without a record (the fit loop opens ``fit.step`` before
        it knows whether the iterator holds another batch)."""
        self._discarded = True

    def __enter__(self):
        st = _stack()
        if st:
            self.parent = st[-1]
            self.depth = self.parent.depth + 1
            step = self.parent.attrs.get("step")
            if step is not None:
                self.attrs.setdefault("step", step)
        st.append(self)
        # the same region on the profiler's clock (costs a flag test
        # while no jax.profiler trace is being taken)
        annotate = (_sinks or _resolve_sinks())[0]
        step = self.attrs.get("step")
        self._annotation = (annotate(self.name) if step is None
                            else annotate(self.name, step=step))
        self._annotation.__enter__()
        # wall clock for the trace timeline, monotonic for the duration
        self._ts_us = time.time() * 1e6
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        self.duration = dur
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        if self._discarded:
            return False
        SPAN_SECONDS.observe(dur, span=self.name)
        args = dict(self.attrs)
        if self.parent is not None:
            args["parent"] = self.parent.name
        if exc_type is not None:
            args["error"] = exc_type.__name__
        _, record_event_complete, emit_span = _sinks
        # profiler buffer (no-op unless profiler_set_state("run"))
        record_event_complete(
            self.name, self._ts_us, dur * 1e6, category="framework",
            args=args or None)
        emit_span({
            "type": "span", "name": self.name, "ts": self._ts_us / 1e6,
            "dur": dur, "depth": self.depth,
            "thread": threading.get_ident() % 10000, "attrs": args,
        })
        return False


def span(name, **attrs):
    """Open a timing region. Usage::

        with telemetry.span("fwdbwd", step=n):
            ...
    """
    if not _reg._enabled:
        return _NULL
    return Span(name, attrs)


def current_span():
    """The innermost active span on this thread, or None."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


# the span round what telemetry itself does in set-up when it is on
COST_CAPTURE = "telemetry.cost_capture"


def under():
    """Name of the OUTERMOST span open on this thread, ``-`` outside
    any: the label by which ``jit.seconds``, ``jit.cache`` and
    ``device.h2d_bytes`` say whose work they were (telemetry/setup.py).
    ``telemetry.cost_capture`` is charged to itself wherever it nests,
    so that no root's share holds the tracing's own cost."""
    st = getattr(_tls, "stack", None)
    if not st:
        return "-"
    for sp in st:
        if sp.name == COST_CAPTURE:
            return COST_CAPTURE
    return st[0].name
