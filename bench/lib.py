"""Shared pieces of the benchmark harness: where files live, how they are
found by name, compile accounting, telemetry deltas and the profiler
session. Nothing here lists a configuration, a cell, a traffic kind or
a metric: every one of those is a file found by the name the manifest
gives (see bench/README.md)."""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil

import reduce_trace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class BenchError(Exception):
    """The benchmark cannot run as asked (bad name, bad file, no chip)."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find(kind, name, ext):
    """Path of the file that holds ``name`` of ``kind`` (a directory of
    bench/). The name is the whole lookup: no table in code."""
    path = os.path.join(BENCH, kind, name + ext)
    if not os.path.isfile(path):
        raise BenchError("no %s named %r (looked for %s)" % (kind, name, path))
    return path


def load_module(kind, name):
    """Import bench/<kind>/<name>.py as a module of its own."""
    path = find(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (kind, name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base, over):
    """``over`` laid on ``base``, dictionaries merged key by key."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = v
    return out


def resolve(dotted):
    """``package.module:function`` -> the function."""
    mod_name, _, fn_name = dotted.partition(":")
    mod = importlib.import_module(mod_name)
    return getattr(mod, fn_name)


class Printer:
    """Every line names the platform it ran on, so that a rehearsal's
    line can never be read as a chip's."""

    def __init__(self, platform, workload):
        self.platform = platform
        self.workload = workload

    def __call__(self, what, **fields):
        parts = ["platform=%s" % self.platform, "cell=%s" % self.workload,
                 what]
        parts += ["%s=%s" % (k, v) for k, v in fields.items()]
        print(" ".join(parts), flush=True)


class CompileLog:
    """jax.monitoring's compile events, split by the phase they fell in
    (``setup`` until the window opens, then ``window``, then ``after``)."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.by_phase = {}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _slot(self):
        return self.by_phase.setdefault(
            self.phase, {"compiles": 0, "seconds": 0.0, "hits": 0,
                         "misses": 0})

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self._slot()["hits"] += 1
        elif event == CACHE_MISS:
            self._slot()["misses"] += 1

    def _on_duration(self, event, seconds, **_):
        if event == COMPILE_EVENT:
            slot = self._slot()
            slot["compiles"] += 1
            slot["seconds"] += seconds

    def of(self, phase):
        return self.by_phase.get(
            phase, {"compiles": 0, "seconds": 0.0, "hits": 0, "misses": 0})


def telemetry_delta(before, after):
    """Difference of two ``telemetry.snapshot()`` dumps, label sets summed:
    name -> {"value"} for counters and gauges, {"sum", "count", "counts",
    "buckets"} for histograms."""
    def flat(snap):
        out = {}
        for name, m in snap.items():
            if m["kind"] == "histogram":
                agg = {"sum": 0.0, "count": 0, "counts": None, "buckets": None}
                for s in m["streams"]:
                    agg["sum"] += s["sum"]
                    agg["count"] += s["count"]
                    agg["buckets"] = s["buckets"]
                    agg["counts"] = (list(s["counts"]) if agg["counts"] is None
                                     else [a + b for a, b in
                                           zip(agg["counts"], s["counts"])])
                out[name] = agg
            else:
                out[name] = {"value": sum(s["value"] for s in m["streams"])}
        return out

    a, b = flat(before), flat(after)
    delta = {}
    for name, now in b.items():
        was = a.get(name)
        if "value" in now:
            delta[name] = {"value": now["value"] - (was["value"] if was else 0)}
            continue
        if now["counts"] is None:
            continue
        old = was["counts"] if was and was["counts"] else [0] * len(now["counts"])
        delta[name] = {
            "sum": now["sum"] - (was["sum"] if was else 0.0),
            "count": now["count"] - (was["count"] if was else 0),
            "counts": [x - y for x, y in zip(now["counts"], old)],
            "buckets": now["buckets"],
        }
    return delta


def bucket_percentile(hist, q):
    """q-th percentile (0..100) of a histogram delta, interpolated inside
    the bucket; None when it holds no sample. The top bucket is open, so
    a sample there reads as the last finite edge."""
    count = hist["count"]
    if count <= 0:
        return None
    target = q / 100.0 * count
    cum, lo = 0, 0.0
    for edge, c in zip(hist["buckets"], hist["counts"]):
        if c > 0 and cum + c >= target:
            return lo + (float(edge) - lo) * ((target - cum) / c)
        cum += c
        lo = float(edge)
    return float(hist["buckets"][-1])


class Session:
    """What a traffic kind is handed as ``trace``: the profiler session
    over a short slice of the run (``--trace 1`` only), the annotations
    the benchmark puts round its own calls, and the two marks that open
    and close the measured window. The benchmark marks the slice's ends
    with annotations of its own, so that the reduction can lay the
    device's time against exactly that interval on the profiler's clock.
    """

    def __init__(self, tracing, out_dir, compile_log):
        self.tracing = tracing
        self.dir = os.path.join(out_dir, "trace")
        self.compile_log = compile_log
        self.xplane = None
        self.snap_open = self.snap_close = None

    def annotate(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start_slice(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)  # one trace per cell
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the runtime's own host events only
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with self.annotate(reduce_trace.SLICE_BEGIN):
            pass

    def stop_slice(self):
        import jax

        with self.annotate(reduce_trace.SLICE_END):
            pass
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not found:
            raise BenchError("the profiler wrote no .xplane.pb under %s"
                             % self.dir)
        self.xplane = found[-1]

    def window_open(self):
        self.compile_log.phase = "window"
        if self.tracing:
            from mxnet_tpu import telemetry

            self.snap_open = telemetry.snapshot()

    def window_close(self):
        if self.tracing:
            from mxnet_tpu import telemetry

            self.snap_close = telemetry.snapshot()
        self.compile_log.phase = "after"


def memory_peak_bytes(devices):
    """Peak bytes held on the fullest device (0 where the backend reports
    nothing). The TPU runtime counts live buffers under
    ``peak_bytes_in_use`` and the executables' scratch (XLA's temp
    buffer) under ``peak_bytes_reserved``; the two peaks need not fall
    together, so their sum is an upper bound of the true peak, high by
    at most the buffers freed before the largest program ran."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak
