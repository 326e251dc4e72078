"""Profiler: chrome-trace events + device-trace source attribution.

Parity: reference python/mxnet/profiler.py (MXSetProfilerConfig/State,
chrome trace-event dump). The attribution half is TPU-native surface:
jax.profiler device traces joined back to framework source lines via
optimized-HLO metadata — the workflow that located the 25%-of-step
BatchNorm cost in the ResNet step.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import profiler


def test_chrome_trace_roundtrip(tmp_path):
    fn = str(tmp_path / "prof.json")
    profiler.profiler_set_config(mode="all", filename=fn)
    profiler.profiler_set_state("run")
    with profiler.scope("unit_op"):
        pass
    profiler.profiler_set_state("stop")
    profiler.dump_profile()
    events = json.load(open(fn))["traceEvents"]
    assert any(e.get("name") == "unit_op" for e in events)


def test_chrome_trace_complete_events(tmp_path):
    # events are complete "X" records (ts + dur), not unpaired B/E —
    # every consumer pairs them for free, dropped ends can't corrupt
    fn = str(tmp_path / "prof.json")
    profiler.profiler_set_config(mode="all", filename=fn)
    profiler.profiler_set_state("run")
    profiler.record_event_complete("op_a", 1000.0, 250.0,
                                   args={"step": 3})
    with profiler.scope("op_b"):
        pass
    profiler.profiler_set_state("stop")
    events = json.load(open(fn))["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert {e["name"] for e in xs} == {"op_a", "op_b"}
    a = next(e for e in xs if e["name"] == "op_a")
    assert a["ts"] == 1000.0 and a["dur"] == 250.0
    assert a["args"] == {"step": "3"}
    assert not any(e.get("ph") in ("B", "E") for e in events)
    # ts monotonic non-decreasing (dump sorts)
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)


def test_profiler_auto_flush_on_stop(tmp_path):
    # stop writes the trace without an explicit dump_profile() call
    fn = str(tmp_path / "auto.json")
    profiler.profiler_set_config(mode="all", filename=fn)
    profiler.profiler_set_state("run")
    profiler.record_event("auto_op", 0.0, 10.0)
    profiler.profiler_set_state("stop")
    events = json.load(open(fn))["traceEvents"]
    assert any(e.get("name") == "auto_op" for e in events)
    # a fresh run session clears the previous events
    profiler.profiler_set_state("run")
    profiler.record_event("second_op", 0.0, 5.0)
    profiler.profiler_set_state("stop")
    names = {e.get("name")
             for e in json.load(open(fn))["traceEvents"]}
    assert "second_op" in names and "auto_op" not in names


def test_hlo_metadata_map_parses_both_layouts():
    # TPU layout: inline source_file/source_line; CPU layout:
    # stack_frame_id only. Both must parse (source degrades to "?").
    hlo = (
        '%fusion.7 = f32[8]{0} fusion(%p0), metadata={'
        'op_name="jit(f)/jvp()/conv" source_file="/x/nn.py" '
        'source_line=220 stack_frame_id=3}\n'
        '%tanh.2 = f32[8]{0} tanh(%p1), metadata={op_name="jit(f)/tanh" '
        'stack_frame_id=4}\n'
    )
    m = profiler.hlo_metadata_map(hlo)
    assert m["fusion.7"] == ("jit(f)/jvp()/conv", "/x/nn.py", 220)
    assert m["tanh.2"] == ("jit(f)/tanh", "?", 0)


def test_attribute_trace_end_to_end(tmp_path):
    def f(x, w):
        for _ in range(3):
            x = jnp.tanh(x @ w)
        return x.sum()

    x = jnp.ones((128, 128))
    w = jnp.ones((128, 128))
    jf = jax.jit(jax.grad(f))
    compiled = jf.lower(x, w).compile()
    outdir = str(tmp_path / "trace")
    with jax.profiler.trace(outdir):
        for _ in range(2):
            r = jf(x, w)
        r.block_until_ready()
    rows = profiler.attribute_trace(outdir, compiled.as_text())
    assert rows and all({"ms", "op", "source"} <= set(r) for r in rows)
    # the matmul chain is there and attributed to dot_general with time
    # of its own: which op of a 128 x 128 chain took longest is the
    # machine's and its other workers' to say, not the attribution's
    dots = [r for r in rows if "dot_general" in r["op"]]
    assert dots and all(r["ms"] > 0 and r["source"] for r in dots)
    # sorted descending
    assert rows == sorted(rows, key=lambda r: -r["ms"])


def test_attribute_trace_missing_dir():
    with pytest.raises(FileNotFoundError):
        profiler.attribute_trace("/nonexistent/dir-xyz", "")
