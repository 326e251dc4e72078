"""The traced run is the untraced program seen from inside (ISSUE 24):
spans cost nothing when telemetry is off, land on the profiler's clock
when it is on, carry the step id down the tree; the fused step's named
scopes are metadata only; and telemetry compiles nothing of its own.
"""
import json
import os
import re
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor
from mxnet_tpu import telemetry as tm
from mxnet_tpu.parallel import train_step
from mxnet_tpu.telemetry import anatomy, tracer
from mxnet_tpu.telemetry import setup as tm_setup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# table B of docs/observability.md: span -> parent inside one fit step
SPAN_PARENT = {
    "fit.step": None,
    "fit.input": "fit.step",
    "io.feed_fill": "fit.input",
    "module.update": "fit.step",
    "module.stage": "module.update",
    "train_step.dispatch": "module.update",
    "module.update_metric": "fit.step",
    "fit.callbacks": "fit.step",
    "fit.after_steps": "fit.step",
}


@pytest.fixture(autouse=True)
def _isolate():
    tm.reset()
    tm.disable()
    yield
    tm.reset()
    tm.disable()


class _CountingAnnotation:
    made = 0

    def __init__(self, *args, **kwargs):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    """Counts every TraceAnnotation the tracer constructs."""
    _CountingAnnotation.made = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    monkeypatch.setattr(tracer, "_sinks", None)
    return _CountingAnnotation


def _toy_symbol():
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0")
    net = mx.sym.Activation(net, act_type="relu", name="relu0")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max",
                         name="pool0")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=4,
                                name="fc0")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _toy_fit(batches=4, eval_metric="acc", mod=None, batch_size=8,
             **fit_kwargs):
    """A fused fit of ``batches`` steps on two virtual devices."""
    rng = np.random.RandomState(0)
    n = batch_size * batches
    it = mx.io.NDArrayIter(rng.rand(n, 3, 8, 8).astype("f"),
                           rng.randint(0, 4, n).astype("f"),
                           batch_size=batch_size)
    if mod is None:
        mod = mx.mod.Module(_toy_symbol(), context=[mx.cpu(0), mx.cpu(1)])
    mod.fit(it, eval_metric=eval_metric, optimizer="sgd", kvstore="device",
            num_epoch=1, **fit_kwargs)
    assert mod._fused_trainer is not None
    return mod


# ---------------------------------------------------------------------------
# D. what it costs when off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(SPAN_PARENT))
def test_disabled_span_is_the_shared_null(name, annotations):
    sp = tm.span(name, step=3)
    assert sp is tracer._NULL
    with sp as inner:
        assert inner is tracer._NULL
        inner.discard()
    assert annotations.made == 0


def test_disabled_fit_builds_no_span_and_no_annotation(annotations,
                                                       monkeypatch):
    built, listeners = [], []
    init = tracer.Span.__init__
    monkeypatch.setattr(tracer.Span, "__init__",
                        lambda self, *a: (built.append(a), init(self, *a))[1])
    # as in a process that never enabled telemetry
    monkeypatch.setattr(tm_setup, "_installed", False)
    for register in ("register_event_listener", "register_scalar_listener",
                     "register_event_duration_secs_listener"):
        monkeypatch.setattr(jax.monitoring, register, listeners.append)
    _toy_fit()
    assert built == [] and annotations.made == 0
    assert listeners == [] and not tm_setup._installed
    for metric in (tm_setup.JIT_SECONDS, tm_setup.JIT_CACHE,
                   tm_setup.H2D_BYTES, tm_setup.IMPORT_T0,
                   tm_setup.IMPORT_SECONDS, tm_setup.TRACE_SECONDS,
                   tm_setup.NODE_TRACE_SECONDS):
        assert metric.label_sets() == [], metric.name


# ---------------------------------------------------------------------------
# B. the span tree of one step
# ---------------------------------------------------------------------------

def test_fit_span_tree_parents_and_step_ids(tmp_path):
    jsonl = str(tmp_path / "t.jsonl")
    tm.enable(jsonl=jsonl)
    _toy_fit(batches=4)
    tm.flush()
    spans = [json.loads(ln) for ln in open(jsonl)]
    spans = [s for s in spans if s["type"] == "span"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # one fit.step per batch: the fifth, opened to find the iterator
    # empty, is discarded
    assert len(by_name["fit.step"]) == 4
    assert sorted(s["attrs"]["step"] for s in by_name["fit.step"]) == \
        [1, 2, 3, 4]
    # fit keeps one step in flight: the metric fetch and the callbacks
    # of step N run inside fit.step N+1, after its module.update, and
    # those of the last step in the drain at the epoch's end
    lagging = ("module.update_metric", "fit.callbacks")
    for name, parent in SPAN_PARENT.items():
        assert name in by_name, name
        inside = [s for s in by_name[name]
                  if s["attrs"].get("parent") == parent]
        assert inside, (name, parent)
        if parent is not None:
            # children inherit the step id of the fit.step they are in
            # (the wait that found the iterator empty belongs to a fifth,
            # discarded, step)
            ids = {s["attrs"]["step"] for s in inside}
            # (the first step's dispatch lies one level down, inside
            # train_step.first_dispatch)
            late = name in lagging or name == "train_step.dispatch"
            first = {2} if late else {1, 2}
            assert first | {3, 4} <= ids <= {1, 2, 3, 4, 5}, (name, ids)
    for name in lagging:
        assert len(by_name[name]) == 4
        drained = [s for s in by_name[name] if "parent" not in s["attrs"]]
        assert len(drained) == 1 and drained[0]["ts"] >= max(
            s["ts"] + s["dur"] for s in by_name["fit.step"]) - 1e-3
    # a step's children lie inside it on the clock, the previous step's
    # post-step work after this step's enqueue
    step = by_name["fit.step"][1]
    kids = {}
    for name in ("fit.input", "module.update", "module.update_metric",
                 "fit.callbacks", "fit.after_steps"):
        kid = kids[name] = [
            s for s in by_name[name]
            if s["attrs"].get("step") == step["attrs"]["step"]][0]
        assert kid["ts"] >= step["ts"] - 1e-3
        assert kid["ts"] + kid["dur"] <= step["ts"] + step["dur"] + 1e-3
    order = ["fit.input", "module.update", "module.update_metric",
             "fit.callbacks", "fit.after_steps"]
    assert sorted(order, key=lambda n: kids[n]["ts"]) == order


# ---------------------------------------------------------------------------
# E. set-up from inside (ISSUE 35)
# ---------------------------------------------------------------------------

SETUP_ROOTS = ("module.bind", "module.init_params", "module.init_optimizer",
               "train_step.first_dispatch")
SETUP_CHILDREN = ("module.fused_build", "train_step.place_params",
                  "train_step.make_state")


def _spans_of(jsonl):
    tm.flush()
    spans = [json.loads(ln) for ln in open(jsonl)]
    return [s for s in spans if s["type"] == "span"]


def _streams(metric):
    return tm.snapshot().get(metric, {"streams": []})["streams"]


@pytest.fixture
def traced_fit(tmp_path):
    jsonl = str(tmp_path / "t.jsonl")
    tm.enable(jsonl=jsonl)
    return _toy_fit(batches=3), jsonl


def test_setup_spans_come_once_and_in_order(traced_fit):
    spans = _spans_of(traced_fit[1])
    roots = sorted((s for s in spans if s["name"] in SETUP_ROOTS),
                   key=lambda s: s["ts"])
    assert [s["name"] for s in roots] == list(SETUP_ROOTS)
    # the dispatch that traces and compiles is the first step's, and the
    # steady-state span stays inside it
    first = roots[-1]
    assert first["attrs"]["parent"] == "module.update"
    inner = [s for s in spans if s["name"] == "train_step.dispatch"
             and s["attrs"].get("parent") == "train_step.first_dispatch"]
    assert len(inner) == 1
    assert len([s for s in spans
                if s["name"] == "train_step.dispatch"]) == 3


@pytest.mark.parametrize("child", SETUP_CHILDREN)
def test_setup_children_lie_under_init_optimizer(child, traced_fit):
    found = [s for s in _spans_of(traced_fit[1]) if s["name"] == child]
    assert len(found) == 1
    assert found[0]["attrs"]["parent"] == "module.init_optimizer"


def test_a_second_fit_is_no_second_setup(traced_fit):
    mod, jsonl = traced_fit
    _toy_fit(batches=3, mod=mod)
    names = [s["name"] for s in _spans_of(jsonl)]
    for name in SETUP_ROOTS + SETUP_CHILDREN:
        assert names.count(name) == 1, name
    assert names.count("train_step.dispatch") == 6


def test_a_new_batch_size_is_a_new_first_dispatch(traced_fit):
    mod, jsonl = traced_fit
    mod.reshape([("data", (4, 3, 8, 8))], [("softmax_label", (4,))])
    _toy_fit(batches=3, mod=mod, batch_size=4)
    names = [s["name"] for s in _spans_of(jsonl)]
    assert names.count("train_step.first_dispatch") == 2
    for name in SETUP_ROOTS[:-1] + SETUP_CHILDREN:
        assert names.count(name) == 1, name


@pytest.mark.parametrize("phase", ["trace", "lower", "compile"])
def test_jit_seconds_say_under_which_root(phase, traced_fit):
    streams = [s for s in _streams("jit.seconds")
               if s["labels"]["phase"] == phase]
    by_root = {s["labels"]["under"]: s["value"] for s in streams}
    # the fused step's own program, whatever else ran
    assert by_root.get("fit.step", 0) > 0, by_root
    # nothing of the program's runs outside every span
    assert "-" not in by_root, by_root
    assert all(v >= 0 for v in by_root.values())


def test_cost_capture_is_charged_to_itself(traced_fit):
    """What telemetry itself does in set-up is inside a span of its own
    (the symbol's cost table before the loop, the step's second lowering
    after its first dispatch), and what jax spends there is not the
    enclosing root's."""
    spans = [s for s in _spans_of(traced_fit[1])
             if s["name"] == "telemetry.cost_capture"]
    assert {s["attrs"].get("parent") for s in spans} == {None,
                                                         "module.update"}
    before = {s["labels"]["under"]: s["value"]
              for s in _streams("jit.seconds")
              if s["labels"]["phase"] == "trace"}
    with tm.span("fit.step"), tm.span("telemetry.cost_capture"):
        jax.jit(lambda x: x * 3 + 1).lower(np.ones((5,), "f"))
    after = {s["labels"]["under"]: s["value"]
             for s in _streams("jit.seconds")
             if s["labels"]["phase"] == "trace"}
    assert after["telemetry.cost_capture"] > before.get(
        "telemetry.cost_capture", 0)
    assert after["fit.step"] == before["fit.step"]


def test_jit_phases_do_not_count_nested_seconds_twice():
    """jax times a phase from enter to exit and phases nest; each stream
    holds its phase less what ran inside it."""
    tm.enable()
    begin, done = tm_setup._on_phase_begin, tm_setup._on_duration
    trace, compile_ = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/backend_compile_duration")
    with tm.span("module.bind"):
        begin(trace, 0.0, fun_name="step")
        begin(trace, 0.0, fun_name="inner")
        done(trace, 0.25, fun_name="inner")   # a jit traced inside the
        begin(compile_, 0.0, fun_name="jit(fold)")        # outer trace
        tm_setup._on_event("/jax/compilation_cache/cache_hits")
        done("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
        done(compile_, 0.75, fun_name="jit(fold)")   # a constant folded
        done(trace, 2.0, fun_name="step")            # while tracing
    got = {}
    for s in _streams("jit.seconds"):
        phase = s["labels"]["phase"]
        got[phase] = got.get(phase, 0.0) + s["value"]
    assert got == {"trace": 0.25 + 1.0, "compile": 0.25, "cache_load": 0.5}
    assert {s["labels"]["under"] for s in _streams("jit.seconds")} == {
        "module.bind"}
    # one function under one name in every phase; a cache lookup and
    # its load carry the name of the compile they happened in
    assert {(s["labels"]["phase"], s["labels"]["fun"]): s["value"]
            for s in _streams("jit.seconds")} == {
        ("trace", "step"): 1.0, ("trace", "inner"): 0.25,
        ("compile", "fold"): 0.25, ("cache_load", "fold"): 0.5}
    assert [s["labels"] for s in _streams("jit.cache")] == [
        {"result": "hit", "fun": "fold", "under": "module.bind"}]


# ---------------------------------------------------------------------------
# the first dispatch from inside (ISSUE 51): which function, which part
# of the step's body, which class of node
# ---------------------------------------------------------------------------

TOY_CLASSES = {"conv": 1, "bn": 1, "act": 1, "pool": 1, "other": 1,
               "fc": 1, "loss": 1}


def _under(metric, root="fit.step"):
    return [s for s in _streams(metric) if s["labels"]["under"] == root]


def _parts(root="fit.step"):
    return {s["labels"]["part"]: s["value"]
            for s in _under("jit.trace_seconds", root)}


def _nodes(root="fit.step"):
    return {s["labels"]["class"]: (s["count"], s["sum"])
            for s in _under("jit.node_trace_seconds", root)}


@pytest.mark.parametrize("metric", ["jit.seconds", "jit.cache"])
def test_jit_streams_say_which_function(metric, traced_fit):
    streams = _streams(metric)
    assert streams and all("fun" in s["labels"] for s in streams)
    funs = {s["labels"]["fun"] for s in streams}
    assert not [f for f in funs if f.startswith("jit(")], funs
    # the fused step's program by its name, in every phase it has
    own = {s["labels"].get("phase", s["labels"].get("result"))
           for s in _under(metric) if s["labels"]["fun"] == "step"}
    if metric == "jit.seconds":
        assert own >= {"trace", "lower", "compile"}, own
    else:
        assert own and own <= {"hit", "miss"}, own


def test_trace_parts_partition_the_steps_trace(traced_fit):
    parts = _parts()
    assert set(parts) == {"forward", "backward", "update"}
    assert all(v > 0 for v in parts.values())
    # the step's own trace and what nested in it: every function traced
    # under fit.step (the eager scalars round the call are its dust)
    traced = sum(s["value"] for s in _under("jit.seconds")
                 if s["labels"]["phase"] == "trace")
    assert sum(parts.values()) == pytest.approx(traced, rel=0.05, abs=0.01)
    assert sum(parts.values()) <= traced + 1e-3
    # every root that traces a step, and no other
    assert {s["labels"]["under"]
            for s in _streams("jit.trace_seconds")} == {"fit.step"}


def test_forward_holds_the_nodes(traced_fit):
    nodes = _nodes()
    assert _parts()["forward"] >= sum(v for _, v in nodes.values())
    assert all(v > 0 for _, v in nodes.values())


def test_a_node_is_observed_once_under_its_class(traced_fit):
    nodes = _nodes()
    program = traced_fit[0]._fused_trainer.program
    wanted = [executor.op_class(n.op.name) for n in program.nodes
              if not n.is_variable]
    assert {c: n for c, (n, _) in nodes.items()} == TOY_CLASSES
    assert {c: wanted.count(c) for c in set(wanted)} == TOY_CLASSES


def test_a_traced_signature_is_traced_once(traced_fit):
    """A second fit and its steady-state steps run no Python of the
    step: neither stream moves."""
    mod, _ = traced_fit
    before = _parts(), _nodes()
    _toy_fit(batches=3, mod=mod)
    assert (_parts(), _nodes()) == before


def test_a_new_batch_size_is_one_more_trace(traced_fit):
    mod, _ = traced_fit
    before = _parts()
    mod.reshape([("data", (4, 3, 8, 8))], [("softmax_label", (4,))])
    _toy_fit(batches=3, mod=mod, batch_size=4)
    assert {c: n for c, (n, _) in _nodes().items()} == {
        c: 2 * n for c, n in TOY_CLASSES.items()}
    after = _parts()
    assert all(after[p] > before[p] for p in ("forward", "backward",
                                              "update"))


def test_an_eager_forward_observes_no_node():
    """Outside a trace a node's seconds are asynchronous dispatch, which
    is not this: the executor path observes its nodes where jax traces
    them and nowhere else."""
    tm.enable()
    mod = mx.mod.Module(_toy_symbol(), context=mx.cpu(0))
    mod.bind([("data", (8, 3, 8, 8))], [("softmax_label", (8,))])
    mod.init_params()
    batch = mx.io.DataBatch(data=[mx.nd.ones((8, 3, 8, 8))],
                            label=[mx.nd.zeros((8,))])
    with jax.disable_jit():
        mod.forward(batch, is_train=False)
        mod.get_outputs()[0].asnumpy()
    assert _streams("jit.node_trace_seconds") == []
    assert _streams("jit.trace_seconds") == []
    mod.forward(batch, is_train=False)
    assert sum(s["count"]
               for s in _streams("jit.node_trace_seconds")) == len(
        TOY_CLASSES)
    # no fused step was traced: no part
    assert _streams("jit.trace_seconds") == []


def test_cost_capture_keeps_its_own_parts(traced_fit):
    """Should telemetry's second lowering ever trace the body again, the
    seconds are its own and no root's."""
    assert _parts("telemetry.cost_capture") == {}
    assert _nodes("telemetry.cost_capture") == {}
    before = _parts()

    def body(x):
        with tm_setup.trace_part("forward"):
            return x * 3 + 1

    with tm.span("fit.step"), tm.span("telemetry.cost_capture"):
        jax.jit(body).lower(np.ones((5,), "f"))
    assert set(_parts("telemetry.cost_capture")) == {"forward"}
    assert _parts() == before


def test_a_part_is_its_clock_less_the_other_phases(monkeypatch):
    """A part holds what jit.seconds{phase=trace} holds of its interval:
    a constant compiled or loaded while tracing and an eager op lowered
    are other streams' seconds, a nested trace is the part's own work;
    parts nest, and an inner part's seconds are not the outer's."""
    tm.enable()
    now = [100.0]
    monkeypatch.setattr(tm_setup, "time", type("clock", (), {
        "perf_counter": staticmethod(lambda: now[0])}))
    begin, done = tm_setup._on_phase_begin, tm_setup._on_duration
    trace, lower, compile_ = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration")
    assert not tm_setup.tracing()
    with tm.span("fit.step"):
        begin(trace, 0.0, fun_name="step")
        assert tm_setup.tracing()
        now[0] += 0.125                    # the body's rest
        with tm_setup.trace_part("backward"):
            now[0] += 1.0
            with tm_setup.trace_part("forward"):
                begin(trace, 0.0, fun_name="inner")
                now[0] += 0.25
                done(trace, 0.25, fun_name="inner")
                begin(lower, 0.0, fun_name="jit(const)")
                now[0] += 0.5
                done(lower, 0.5, fun_name="jit(const)")
                begin(compile_, 0.0, fun_name="jit(const)")
                now[0] += 0.75
                done("/jax/compilation_cache/cache_retrieval_time_sec",
                     0.5)
                done(compile_, 0.75, fun_name="jit(const)")
                now[0] += 2.0
        now[0] += 0.5                      # jax closes the jaxpr
        done(trace, 5.125, fun_name="step")
    assert not tm_setup.tracing()
    assert _parts() == {"forward": 2.25, "backward": 1.0}
    traced = sum(s["value"] for s in _streams("jit.seconds")
                 if s["labels"]["phase"] == "trace")
    assert traced == 5.125 - 0.5 - 0.75
    assert traced - sum(_parts().values()) == 0.125 + 0.5


def test_parts_and_nodes_cost_a_flag_test_when_off():
    assert tm_setup.trace_part("forward") is tm.NULL_SPAN
    assert not tm_setup.tracing()
    tm_setup._on_phase_begin("/jax/core/compile/jaxpr_trace_duration", 0.0,
                             fun_name="step")
    assert not tm_setup.tracing()


def test_h2d_bytes_of_a_bind_are_its_arrays():
    """A bind sends nothing and makes nothing (ISSUE 36): its arrays are
    declared, and the first reader of one makes it on its own device,
    counted beside the crossings and under the reader's span."""
    tm.enable()
    mod = mx.mod.Module(_toy_symbol(), context=mx.cpu(0))
    mod.bind([("data", (8, 3, 8, 8))], [("softmax_label", (8,))])
    exe = mod._exec_group.execs[0]
    arrays = (list(exe.arg_arrays) + list(exe.aux_arrays)
              + [g for g in exe.grad_arrays if g is not None])
    assert len(arrays) > 10
    assert _streams("device.h2d_bytes") == []
    assert _streams("device.const_bytes") == []
    with tm.span("reader"):
        first = arrays[0].asnumpy()
    assert not first.any()
    assert _streams("device.h2d_bytes") == []
    assert {s["labels"]["under"]: s["value"]
            for s in _streams("device.const_bytes")} == {
                "reader": first.nbytes}


def test_enable_publishes_the_import_stamps():
    tm.enable()
    t0 = _streams("process.import_t0")[0]["value"]
    seconds = _streams("process.import_seconds")[0]["value"]
    assert (t0, seconds) == (mx._IMPORT_T0, mx._IMPORT_SECONDS)
    assert 0 < seconds and t0 + seconds <= time.perf_counter()


def test_snapshots_are_kept_with_their_stamp():
    tm.enable()
    before = time.perf_counter()
    tm.counter("t.kept").inc(2)
    first = tm.snapshot()
    tm.counter("t.kept").inc(3)
    tm.snapshot()
    taken = tm.snapshots_taken()
    assert [d["t.kept"]["streams"][0]["value"] for _, d in taken] == [2, 5]
    assert taken[0][1] is first
    assert before <= taken[0][0] <= taken[1][0] <= time.perf_counter()


class _SleepyMetric(mx.metric.Accuracy):
    def update(self, labels, preds):
        time.sleep(0.02)
        super().update(labels, preds)


@pytest.mark.parametrize("kvstore,fused", [("device", True),
                                           ("local", False)])
def test_output_sync_holds_the_metric_update(kvstore, fused, tmp_path):
    """One timing site per path: on the fused path, where the metric's
    update runs one dispatch late, the histogram holds what
    eval_metric.update takes, the blocking fetch included, once a step;
    the executor path's update is the span alone."""
    jsonl = str(tmp_path / "t.jsonl")
    tm.enable(jsonl=jsonl)
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.rand(32, 3, 8, 8).astype("f"),
                           rng.randint(0, 4, 32).astype("f"), batch_size=8)
    mod = mx.mod.Module(_toy_symbol(), context=[mx.cpu(0), mx.cpu(1)])
    mod.fit(it, eval_metric=_SleepyMetric(), optimizer="sgd",
            kvstore=kvstore, num_epoch=1)
    assert (mod._fused_trainer is not None) == fused
    tm.flush()
    spans = [json.loads(ln) for ln in open(jsonl)]
    spans = [s for s in spans if s["type"] == "span"
             and s["name"] == "module.update_metric"]
    assert len(spans) == 4 and sum(s["dur"] for s in spans) >= 4 * 0.02
    sync = tm.snapshot().get("module.output_sync_seconds",
                             {"streams": []})["streams"]
    assert sum(s["count"] for s in sync) == (4 if fused else 0)
    if fused:
        assert sum(s["sum"] for s in sync) >= 4 * 0.02


def test_spans_land_in_the_profilers_trace(tmp_path):
    """Same clock as the device trace: a span is a TraceAnnotation in
    the host plane of the .xplane.pb the profiler writes."""
    import glob

    from jax.profiler import ProfileData

    tm.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            with tm.span("fit.step", step=i):
                with tm.span("module.update"):
                    pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    steps = [e for e in events if e.name == "fit.step"]
    updates = [e for e in events if e.name == "module.update"]
    assert len(steps) == 3 and len(updates) == 3
    for s, u in zip(sorted(steps, key=lambda e: e.start_ns),
                    sorted(updates, key=lambda e: e.start_ns)):
        assert s.start_ns <= u.start_ns
        assert u.start_ns + u.duration_ns <= s.start_ns + s.duration_ns


# ---------------------------------------------------------------------------
# C. scopes inside the fused step are metadata only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op,cls", [
    ("Convolution", "conv"), ("Deconvolution", "conv"),
    ("FullyConnected", "fc"), ("BatchNorm", "bn"), ("Pooling", "pool"),
    ("Activation", "act"), ("LeakyReLU", "act"), ("elemwise_add", "act"),
    ("_Plus", "act"), ("SoftmaxOutput", "loss"), ("MakeLoss", "loss"),
    ("LinearRegressionOutput", "loss"), ("Concat", "other"),
    ("Flatten", "other"),
])
def test_op_class(op, cls):
    assert executor.op_class(op) == cls


# what ``executor._OP_CLASS`` held before an op's registration said it (PR 71)
REGISTERED_CLASSES = {
    "Convolution": "conv", "Deconvolution": "conv",
    "FullyConnected": "fc", "BatchNorm": "bn", "Pooling": "pool",
    "Activation": "act", "LeakyReLU": "act", "relu": "act",
    "sigmoid": "act", "tanh": "act", "add_n": "act", "clip": "act",
    "MakeLoss": "loss", "softmax_cross_entropy": "loss",
    "_contrib_Attention": "attn", "_contrib_RoPE": "attn",
    "_contrib_LatentAttention": "attn", "_contrib_KeyIndexer": "attn",
    "_contrib_Mamba2": "ssm", "_contrib_ExitMix": "loss",
    "_contrib_Mamba1": "ssm", "_contrib_DiffAttention": "attn",
    "_contrib_LayerNorm": "norm",
    "_contrib_TopKMoE": "moe", "_contrib_RMSNorm": "norm",
    "Embedding": "embed", "_contrib_GatedDeltaNet": "gdn",
    "_contrib_ShortConv": "sconv", "_contrib_ScaledSum": "act",
    "_contrib_HyperCoeff": "hc", "_contrib_HyperMix": "hc",
    "_contrib_LinearAttention": "linattn", "_contrib_BlockSelect": "attn",
}


def test_op_class_is_what_each_registration_says():
    from mxnet_tpu.ops import registry

    said = {op.name: op.op_class for op in registry.primary_ops()
            if op.op_class is not None}
    assert said == REGISTERED_CLASSES
    for name, cls in said.items():
        assert executor.op_class(name) == cls, name


def test_op_class_of_an_unregistered_name_is_other():
    from mxnet_tpu.ops import registry

    assert not registry.exists("NoSuchOp")
    assert executor.op_class("NoSuchOp") == "other"
    assert executor.op_class("NoSuchOutput") == "loss"  # the rule by name


def _lowered_step(monkeypatch):
    """Lower the toy fit's fused step from what the trainer hands to the
    cost capture (the abstract arguments of its first dispatch)."""
    grabbed = {}
    monkeypatch.setattr(
        train_step.ShardedTrainStep, "_capture_cost",
        lambda self, key, fn, specs, shapes: grabbed.update(
            fn=fn, specs=specs))
    tm.enable()
    _toy_fit(batches=1)
    tm.disable()
    return grabbed["fn"].lower(*grabbed["specs"])


def test_lowered_step_carries_the_scopes(monkeypatch):
    text = _lowered_step(monkeypatch).as_text(debug_info=True)
    for scope in ("fwd_bwd/jvp(conv/conv0)", "fwd_bwd/jvp(bn/bn0)",
                  "jvp(pool/pool0)", "jvp(fc/fc0)", "jvp(act/relu0)",
                  "jvp(loss/softmax)", "transpose(jvp(conv/conv0))",
                  "jit(step)/update/"):
        assert scope in text, scope


def test_scopes_change_no_executable(monkeypatch):
    import contextlib

    def optimized(lowered):
        return re.sub(r", metadata=\{[^}]*\}", "",
                      lowered.compile().as_text())

    with_scopes = optimized(_lowered_step(monkeypatch))
    assert "jvp(conv/conv0)" not in with_scopes  # metadata is stripped
    tm.reset()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert optimized(_lowered_step(monkeypatch)) == with_scopes


# ---------------------------------------------------------------------------
# A. telemetry builds no program of its own
# ---------------------------------------------------------------------------

def test_cost_capture_falls_back_to_the_hand_count():
    class _NoAnalysis:  # a Lowered on the TPU's client
        def cost_analysis(self):
            return None

    tm.enable()
    assert anatomy.cost_pending(7, ("single", "sig"))
    cost = anatomy.capture_cost(
        7, ("single", "sig"), _NoAnalysis, devices=4,
        analytic=lambda: {"flops": 30.0, "bytes_accessed": 12.0})
    assert cost == {"flops": 30.0, "bytes_accessed": 12.0}
    assert not anatomy.cost_pending(7, ("single", "sig"))

    class _Global:  # the un-partitioned program: all devices together
        def cost_analysis(self):
            return {"flops": 80.0, "bytes accessed": 40.0}

    cost = anatomy.capture_cost(8, ("single",), _Global, devices=4)
    assert cost == {"flops": 20.0, "bytes_accessed": 10.0}


_COUNT_COMPILES = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    from __graft_entry__ import _force_cpu_mesh_platform
    _force_cpu_mesh_platform(8)
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.models import mlp

    seen = {"compiles": 0, "hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            seen["hits"] += 1
        elif event.endswith("/cache_misses"):
            seen["misses"] += 1

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    if sys.argv[1] == "on":
        tm.enable()
        jax.profiler.start_trace(sys.argv[2])
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.randn(64, 8).astype("f"),
                           (rng.rand(64) > 0.5).astype("f"), batch_size=16)
    mod = mx.mod.Module(mlp(num_classes=2, hidden=(8,)),
                        context=[mx.cpu(i) for i in range(4)])
    mod.fit(it, optimizer="sgd", kvstore="device", num_epoch=2)
    if sys.argv[1] == "on":
        jax.profiler.stop_trace()
        snap = tm.snapshot()
        assert snap["anatomy.model_flops"]["streams"][0]["value"] > 0
        # the second lowering is the tracing's own, timed as such, and
        # compiles nothing
        captured = [s for s in snap["mxtpu.span_seconds"]["streams"]
                    if s["labels"] == {"span": "telemetry.cost_capture"}]
        assert captured and captured[0]["count"] >= 2, captured
        own = {s["labels"]["phase"] for s in snap["jit.seconds"]["streams"]
               if s["labels"]["under"] == "telemetry.cost_capture"}
        assert own and own <= {"trace", "lower"}, snap["jit.seconds"]
    assert mod._fused_trainer.amp
    print("COMPILES %%(compiles)d %%(hits)d %%(misses)d" %% seen)
""") % REPO


def test_telemetry_compiles_what_the_untraced_run_compiles(tmp_path):
    """dp=4 with AMP (flat sharded update), a fresh cache directory for
    each run: with telemetry on and a profiler slice running, the
    backend compiles exactly the programs of the untraced run."""
    script = tmp_path / "count_compiles.py"
    script.write_text(_COUNT_COMPILES)
    counts = {}
    for mode in ("off", "on"):
        cache = tmp_path / ("cache_" + mode)
        cache.mkdir()
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
                   MXTPU_AMP="bf16", TF_CPP_MIN_LOG_LEVEL="3")
        env.pop("MXTPU_TELEMETRY", None)
        res = subprocess.run(
            [sys.executable, str(script), mode, str(tmp_path / "trace")],
            capture_output=True, text=True, timeout=300, env=env)
        assert res.returncode == 0, res.stderr[-2000:]
        line = [ln for ln in res.stdout.splitlines()
                if ln.startswith("COMPILES")][-1]
        counts[mode] = tuple(int(x) for x in line.split()[1:])
    assert counts["off"][0] > 0
    assert counts["on"] == counts["off"], counts
