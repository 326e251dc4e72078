"""Profiler API.

Parity: reference ``python/mxnet/profiler.py`` + ``src/engine/profiler.*``
(chrome trace-event output, SURVEY.md §5.1). TPU-native: per-op device
timing comes from jax.profiler (XPlane/TensorBoard); this module both
drives jax.profiler and keeps a host-side chrome-trace of framework-level
events (forward/backward/update calls), which is what the reference's
OprExecStat records amounted to.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time

_state = {
    "mode": "symbolic",
    "filename": "profile.json",
    "running": False,
    "events": [],
    "dirty": False,  # events recorded since the last dump
    "jax_tracing": False,
    "jax_dir": None,
}
_lock = threading.Lock()


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Parity MXSetProfilerConfig. mode: 'symbolic' | 'all'."""
    _state["mode"] = mode
    _state["filename"] = filename


def profiler_set_state(state="stop"):
    """Parity MXSetProfilerState. state: 'run' | 'stop'."""
    import jax

    if state == "run":
        _state["running"] = True
        _state["t0"] = time.time()
        with _lock:
            _state["events"] = []  # fresh session
            _state["dirty"] = False
        # device-side trace via jax profiler when a trace dir is configured
        trace_dir = os.environ.get("MXNET_TPU_JAX_TRACE_DIR")
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
            _state["jax_tracing"] = True
            _state["jax_dir"] = trace_dir
    elif state == "stop":
        _state["running"] = False
        if _state.get("jax_tracing"):
            jax.profiler.stop_trace()
            _state["jax_tracing"] = False
        # auto-flush: stopping a session writes the trace without a
        # separate dump_profile() call (which stays available and
        # idempotent — events are only cleared when a new run starts)
        if _state["dirty"]:
            dump_profile()
    else:
        raise ValueError("state must be 'run' or 'stop'")


def record_event_complete(name, ts_us, dur_us, category="operator", pid=0,
                          args=None):
    """Record one complete chrome-trace ``"X"`` event (ts + dur), the
    form every consumer (chrome://tracing, perfetto, trace_summary)
    pairs for free — unpaired B/E records break on dropped ends."""
    if not _state["running"]:
        return
    event = {
        "name": name,
        "cat": category,
        "ph": "X",
        "ts": ts_us,
        "dur": dur_us,
        "pid": pid,
        "tid": threading.get_ident() % 10000,
    }
    if args:
        event["args"] = {k: str(v) for k, v in args.items()}
    with _lock:
        _state["events"].append(event)
        _state["dirty"] = True


def record_event(name, begin_us, end_us, category="operator", pid=0):
    """Host-side event recording hook (OprExecStat equivalent)."""
    record_event_complete(name, begin_us, end_us - begin_us,
                          category=category, pid=pid)


class scope:
    """Context manager stamping one chrome-trace event."""

    def __init__(self, name, category="operator"):
        self.name = name
        self.category = category

    def __enter__(self):
        self.t0 = time.time() * 1e6
        return self

    def __exit__(self, *a):
        record_event(self.name, self.t0, time.time() * 1e6, self.category)


def dump_profile():
    """Parity MXDumpProfile — writes chrome trace-event JSON.

    Idempotent: events persist until the next profiler_set_state("run")
    starts a fresh session, so stop's auto-flush and an explicit dump
    write the same file."""
    with _lock:
        events = sorted(_state["events"], key=lambda e: e["ts"])
        _state["dirty"] = False
    trace = {
        "traceEvents": [
            {
                "name": "process_name",
                "ph": "M",
                "pid": 0,
                "args": {"name": "mxnet_tpu host"},
            }
        ]
        + events,
        "displayTimeUnit": "ms",
    }
    with open(_state["filename"], "w") as f:
        json.dump(trace, f)


@atexit.register
def _dump_at_exit():
    """Flush undumped events at interpreter exit so a run that never
    reached profiler_set_state("stop") still leaves its trace."""
    if _state["dirty"]:
        try:
            dump_profile()
        except OSError:
            pass  # target dir may be gone during teardown


# jax passthroughs for device-side profiling
def hlo_metadata_map(hlo_text):
    """Instruction name -> (op_name, source_file, source_line) from an
    optimized-HLO dump (``compiled.as_text()``).

    XLA kernel names in a device trace (``fusion.1761``,
    ``convolution_reduce_fusion`` ...) are meaningless on their own; the
    HLO metadata carries the jax op and the framework source line each
    fusion descends from. This map is the join key."""
    import re

    meta = {}
    pat = re.compile(r'%([\w.\-]+) = [^\n]*?metadata=\{([^}]*)\}')
    for m in pat.finditer(hlo_text):
        name, blob = m.groups()
        op = re.search(r'op_name="([^"]+)"', blob)
        sf = re.search(r'source_file="([^"]+)"', blob)
        sl = re.search(r'source_line=(\d+)', blob)
        if op is None:
            continue
        meta.setdefault(name, (op.group(1),
                               sf.group(1) if sf else "?",
                               int(sl.group(1)) if sl else 0))
    return meta


def attribute_trace(trace_dir, hlo_text, top=30):
    """Aggregate device-kernel time by framework source line.

    trace_dir: a directory previously passed to jax.profiler.trace /
    start_jax_trace. hlo_text: ``jit(f).lower(...).compile().as_text()``
    of the program that ran inside the trace. Returns rows
    ``{"ms", "op", "source"}`` sorted by total device time, descending —
    the view that located the 25%-of-step BatchNorm cost the ResNet step
    shed (tests/test_profiler.py::test_attribute_trace_end_to_end pins it).

    Device lanes are preferred (pid named '/device:...'); if none exist
    (cpu backend) any trace event whose name appears in the HLO is
    counted instead."""
    import glob
    import gzip
    import re

    meta = hlo_metadata_map(hlo_text)
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not paths:
        raise FileNotFoundError("no *.trace.json.gz under %r" % trace_dir)
    # jax.profiler can split device and host planes (or multiple hosts)
    # across several files in one run directory; aggregate every file
    # that shares the newest run's directory, not just the newest file.
    run_dir = os.path.dirname(paths[-1])
    # Chrome-trace pids are a PER-FILE namespace: key both the events and
    # the device-plane metadata by (file_index, pid) so one file's device
    # pid can't admit another file's host plane (or vice versa).
    events = []
    fi = 0
    for p in paths:
        if os.path.dirname(p) != run_dir:
            continue
        with gzip.open(p, "rt") as f:
            for e in json.load(f).get("traceEvents", []):
                e["pid"] = (fi, e.get("pid"))
                events.append(e)
        fi += 1
    device_pids = {
        e["pid"] for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and "/device:" in str(e.get("args", {}).get("name", ""))
    }
    umbrella = re.compile(r"^(jit_|\d+$)")  # whole-program + step markers
    agg = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        if device_pids and e.get("pid") not in device_pids:
            continue
        name = e.get("name", "")
        if umbrella.match(name) or name not in meta:
            continue
        op, sf, sl = meta[name]
        key = ("/".join(op.split("/")[-2:]),
               "%s:%d" % (os.path.basename(sf), sl))
        agg[key] = agg.get(key, 0.0) + e.get("dur", 0)
    rows = [{"ms": us / 1000.0, "op": op, "source": src}
            for (op, src), us in agg.items()]
    rows.sort(key=lambda r: -r["ms"])
    return rows[:top] if top else rows


def start_jax_trace(log_dir):
    import jax

    jax.profiler.start_trace(log_dir)


def stop_jax_trace():
    import jax

    jax.profiler.stop_trace()
