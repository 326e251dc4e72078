"""What a process's set-up is made of besides the spans round it: jax's
own seconds by phase, the host-to-device bytes, and the package's import
stamps. Each stream carries ``under``, the outermost span open on the
calling thread (``tracer.under``): a reader takes one root's share
(``module.bind``, ``fit.step``, ...) and an operator sees which step
recompiled. See docs/observability.md, "Set-up".

One set of ``jax.monitoring`` callbacks, registered by the first
``telemetry.enable()``; a flag test each while collection is off.
"""
from __future__ import annotations

import sys
import threading
import time

from . import registry as _reg
from .tracer import NULL_SPAN, under

JIT_SECONDS = _reg.counter(
    "jit.seconds",
    "seconds inside jax's own pipeline, by phase (trace, lower, compile, "
    "cache_load), by the jitted function's name and by the outermost "
    "span open on the calling thread; each phase less what ran nested in "
    "it, so the streams add up to wall time")
JIT_CACHE = _reg.counter(
    "jit.cache",
    "persistent compile cache lookups by result (hit, miss), by the "
    "function being compiled and by the outermost span open on the "
    "calling thread")
TRACE_SECONDS = _reg.counter(
    "jit.trace_seconds",
    "host seconds of the fused step's trace by part of its body "
    "(forward, backward, update) and by the outermost span open on the "
    "calling thread; a part less the parts nested in it and less "
    "what jax timed inside it as a phase other than trace, so the parts "
    "add up with jit.seconds{phase=trace}")
NODE_TRACE_SECONDS = _reg.histogram(
    "jit.node_trace_seconds",
    "host seconds of one symbol node's fcompute while jax traces it, by "
    "the node's op class and by the outermost span open on the calling "
    "thread; on the same clock as jit.trace_seconds")
H2D_BYTES = _reg.counter(
    "device.h2d_bytes",
    "bytes of host memory handed to jax.device_put (the numpy array's "
    "nbytes, once however many devices receive it), by the outermost "
    "span open on the calling thread")
CONST_BYTES = _reg.counter(
    "device.const_bytes",
    "bytes of constants made on a device by a program, without a host "
    "array or a crossing (a deferred buffer's first read, fresh optimizer "
    "state), by the outermost span open on the calling thread: with "
    "device.h2d_bytes and device.drawn_bytes, what a span allocated")
DRAWN_BYTES = _reg.counter(
    "device.drawn_bytes",
    "bytes of normal draws made on a device by a program, without a host "
    "array or a crossing (a deferred draw's first read, or the fused "
    "step's placement of a parameter nobody had read), by the outermost "
    "span open on the calling thread and by whether the device was the "
    "host's (host=1, a cpu device) or not: the share with host=0 is how "
    "often a parameter was made where the step holds it")
IMPORT_T0 = _reg.gauge(
    "process.import_t0",
    "time.perf_counter() at the first statement of mxnet_tpu/__init__.py")
IMPORT_SECONDS = _reg.gauge(
    "process.import_seconds",
    "seconds from the first to the last statement of mxnet_tpu/__init__.py")

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

_tls = threading.local()
_installed = False
_accelerated = None


def _open_phases():
    """Per thread, ``[seconds nested in it, fun]`` of each open phase.
    jax times a phase from enter to exit, and phases nest: a jit inside
    a traced function is traced inside the outer trace, a constant
    folded during tracing compiles inside it."""
    st = getattr(_tls, "open", None)
    if st is None:
        st = _tls.open = []
    return st


def _fun(fun_name):
    """One function under one name in every phase: jax names the trace
    ``step`` and the lowering and the compile ``jit(step)``."""
    if not fun_name:
        return "-"
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _on_phase_begin(event, _value, fun_name=None, **_):
    # jax records a scalar (the start time) on entering a timed phase
    if _reg._enabled and event in _PHASES:
        _open_phases().append([0.0, _fun(fun_name)])


def _on_duration(event, seconds, fun_name=None, **_):
    if not _reg._enabled:
        return
    phase = _PHASES.get(event)
    if phase is None and event != _CACHE_LOAD:
        return
    st = _open_phases()
    fun = _fun(fun_name)
    if phase is None:
        # timed inside backend_compile, with no begin and no name of
        # its own
        phase, nested = "cache_load", 0.0
        if st:
            fun = st[-1][1]
    else:
        nested = st.pop()[0] if st else 0.0
    if st:
        st[-1][0] += seconds
    own = max(seconds - nested, 0.0)
    if phase != "trace":
        _tls.untraced = getattr(_tls, "untraced", 0.0) + own
    JIT_SECONDS.inc(own, phase=phase, fun=fun, under=under())


def _on_event(event, **_):
    if _reg._enabled and event in _CACHE_RESULTS:
        st = _open_phases()
        JIT_CACHE.inc(result=_CACHE_RESULTS[event],
                      fun=st[-1][1] if st else "-", under=under())


def tracing():
    """Whether jax is inside a timed phase on this thread, which for a
    caller that is Python run by jax means a trace; False while
    collection is off (the phases are then not followed)."""
    return _reg._enabled and bool(getattr(_tls, "open", None))


def trace_clock():
    """``perf_counter`` less the seconds jax has timed on this thread as
    a phase other than trace (a constant compiled or loaded while
    tracing, an eager op lowered): an interval on this clock is what
    ``jit.seconds{phase=trace}`` holds of it, nested traces included."""
    return time.perf_counter() - getattr(_tls, "untraced", 0.0)


class _TracePart:
    """One part of a traced body (``jit.trace_seconds``): parts nest,
    and each holds its interval on ``trace_clock`` less the parts
    nested in it, so a body's parts partition it."""

    __slots__ = ("part", "t0", "nested")

    def __init__(self, part):
        self.part = part

    def __enter__(self):
        st = getattr(_tls, "parts", None)
        if st is None:
            st = _tls.parts = []
        st.append(self)
        self.nested = 0.0
        self.t0 = trace_clock()
        return self

    def __exit__(self, *exc):
        seconds = trace_clock() - self.t0
        st = _tls.parts
        st.pop()
        if st:
            st[-1].nested += seconds
        TRACE_SECONDS.inc(max(seconds - self.nested, 0.0), part=self.part,
                          under=under())
        return False


def trace_part(part):
    """Context manager round one part of a function's body that runs
    only while jax traces it; the shared null while collection is off
    (one flag test a part at trace time)."""
    return _TracePart(part) if _reg._enabled else NULL_SPAN


def note_node_trace(op_class, t0):
    """One node's ``fcompute`` from ``t0`` (a ``trace_clock`` reading)
    to now. Callers guard with ``tracing()``."""
    NODE_TRACE_SECONDS.observe(
        trace_clock() - t0, **{"class": op_class, "under": under()})


def install():
    """Register the callbacks (once a process) and publish the import
    stamps; ``telemetry.enable()`` and the env-driven enablement call
    it."""
    global _installed
    publish_import()
    if _installed:
        return
    _installed = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_phase_begin)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def publish_import():
    """The two stamps mxnet_tpu/__init__.py takes unconditionally, under
    public names (telemetry is not yet on while the package imports)."""
    pkg = sys.modules.get(__name__.split(".")[0])
    t0 = getattr(pkg, "_IMPORT_T0", None)
    seconds = getattr(pkg, "_IMPORT_SECONDS", None)
    if t0 is not None and seconds is not None:
        IMPORT_T0.set(t0)
        IMPORT_SECONDS.set(seconds)


def note_h2d(nbytes, device):
    """Count ``nbytes`` of host memory handed to ``jax.device_put`` for
    ``device``. A put to a host (cpu) device while an accelerator is the
    default backend crosses nothing and is not counted. Callers guard
    with ``telemetry.enabled()``: one flag test a call when off."""
    global _accelerated
    if _accelerated is None:
        import jax

        _accelerated = jax.default_backend() != "cpu"
    if _accelerated and device.platform == "cpu":
        return
    H2D_BYTES.inc(int(nbytes), under=under())


def note_const(nbytes):
    """Count ``nbytes`` of a constant a program made on a device. Same
    guard (the caller's) and same ``under`` as ``note_h2d`` and
    ``note_drawn``, so a span's allocations are the sum of the three."""
    CONST_BYTES.inc(int(nbytes), under=under())


def note_drawn(nbytes, platform):
    """Count ``nbytes`` of a normal draw a program made on a device of
    ``platform``. Same guard and same ``under`` as ``note_const``."""
    DRAWN_BYTES.inc(int(nbytes), under=under(),
                    host="1" if platform == "cpu" else "0")
