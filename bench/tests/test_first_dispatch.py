"""The six readers over ``first_dispatch``: each value from a hand-built
registry dump, nothing where the registry lacks the step's partition
(the parent's), ``-`` and ``telemetry.cost_capture`` left out by label,
the remainder's three outcomes, and the dump taken as the window
opened."""
import time

import pytest

import first_dispatch
import lib
import setup_phases

NAMES = ("setup_trace_forward_s", "setup_trace_backward_s",
         "setup_trace_update_s", "setup_lower_s",
         "setup_trace_unattributed_s", "setup_trace_ms_node")
RUN = {"open_t": 160.0, "setup_s": 60.0, "first_step_t": 150.0}


def _counter(*streams):
    return {"kind": "counter", "streams": [
        {"labels": labels, "value": v} for labels, v in streams]}


def _nodes(*streams):
    return {"kind": "histogram", "streams": [
        {"labels": {"class": c, "under": u}, "sum": v, "count": n,
         "counts": [], "buckets": []} for c, u, v, n in streams]}


def _jit(phase, fun, under, value):
    return {"phase": phase, "fun": fun, "under": under}, value


def _part(part, under, value):
    return {"part": part, "under": under}, value


def _snap():
    """A step traced under fit.step (7.5 s: 3 + 2.5 + 1 in the three
    parts, the rest the body's and jax's own), the initializers' and the
    state's programs under their roots, the harness's under ``-`` and
    telemetry's second lowering under its own name."""
    return {
        "jit.seconds": _counter(
            _jit("trace", "step", "fit.step", 5.0),
            _jit("trace", "gmm_call", "fit.step", 1.5),
            _jit("trace", "multiply", "fit.step", 1.0),
            _jit("lower", "step", "fit.step", 2.0),
            _jit("compile", "step", "fit.step", 9.0),
            _jit("trace", "_normal", "module.init_params", 0.5),
            _jit("lower", "_normal", "module.init_params", 0.25),
            _jit("trace", "make", "module.init_optimizer", 0.25),
            _jit("trace", "batch", "-", 4.0),
            _jit("lower", "batch", "-", 2.0),
            _jit("trace", "step", "telemetry.cost_capture", 8.0),
            _jit("lower", "step", "telemetry.cost_capture", 2.0)),
        "jit.trace_seconds": _counter(
            _part("forward", "fit.step", 3.0),
            _part("backward", "fit.step", 2.5),
            _part("update", "fit.step", 1.0),
            _part("forward", "telemetry.cost_capture", 3.0),
            _part("forward", "-", 16.0)),
        "jit.node_trace_seconds": _nodes(
            ("attn", "fit.step", 1.5, 4), ("fc", "fit.step", 0.5, 36),
            ("attn", "telemetry.cost_capture", 1.5, 4),
            ("fc", "-", 100.0, 1)),
    }


WANT = {
    "setup_trace_forward_s": 3.0, "setup_trace_backward_s": 2.5,
    "setup_trace_update_s": 1.0, "setup_lower_s": 2.25,
    # 7.5 + 0.5 + 0.25 of trace less 6.5 in the three parts
    "setup_trace_unattributed_s": 1.75,
    "setup_trace_ms_node": 50.0,
}


def _read(name, monkeypatch, snap, run=RUN):
    monkeypatch.setattr(setup_phases, "registry_at_open",
                        lambda r: snap if r.get("open_t") else None)
    return lib.load_module("layer_metrics", name).compute(
        None, {"telemetry": {}}, dict(run))


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_its_streams(name, monkeypatch):
    value = _read(name, monkeypatch, _snap())
    if name == "setup_trace_unattributed_s":
        value, ok, why = value
        # under fit.step 7.5 s of trace less 6.5: inside max(1 s, 15%)
        assert ok, why
        assert "forward 3.000 + backward 2.500 + update 1.000" in why
        assert "under fit.step: trace 7.500 less the three 1.000" in why
        assert ("module.init_optimizer 0.250, module.init_params 0.500"
                in why)
    assert value == pytest.approx(WANT[name])


def test_the_five_terms_are_trace_and_lower(monkeypatch):
    """What ``setup_trace_lower_s`` reads, cut five ways."""
    snap = _snap()
    five = 0.0
    for name in NAMES[:5]:
        value = _read(name, monkeypatch, snap)
        five += value[0] if isinstance(value, tuple) else value
    assert five == pytest.approx(setup_phases.trace_lower_s(RUN))
    assert five == pytest.approx(7.5 + 0.5 + 0.25 + 2.0 + 0.25)


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_in_an_older_program(name, monkeypatch):
    """None, never zero, never a raise: the benchmark's files are laid
    over the parent's checkout too, whose registry counts jax's phases
    (without ``fun``) and no part of the step."""
    parent = {"jit.seconds": _counter(
        ({"phase": "trace", "under": "fit.step"}, 8.0),
        ({"phase": "lower", "under": "fit.step"}, 2.0))}
    assert _read(name, monkeypatch, parent) is None
    assert _read(name, monkeypatch, {}) is None
    assert _read(name, monkeypatch, None) is None
    assert _read(name, monkeypatch, _snap(), run={"steps": 0}) is None


@pytest.mark.parametrize("name", NAMES)
def test_outside_every_span_and_cost_capture_are_left_out(name,
                                                          monkeypatch):
    snap = _snap()
    for metric in snap.values():
        metric["streams"] = [
            s for s in metric["streams"]
            if s["labels"]["under"] not in ("-", "telemetry.cost_capture")]
    value = _read(name, monkeypatch, snap)
    assert (value[0] if isinstance(value, tuple) else value) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("step_own,ok", [
    (5.0, True),     # 1.0 under fit.step: the jaxpr's closing, constants
    (5.2, False),    # 1.2 where 7.7 s of trace allow 1.155: no part has it
    (3.6, True),     # -0.4: clock noise
    (3.4, False),    # -0.6: two parts counted one interval twice
])
def test_remainder_limits_under_the_steps_root(step_own, ok, monkeypatch):
    snap = _snap()
    snap["jit.seconds"]["streams"][0]["value"] = step_own
    value, got, why = _read("setup_trace_unattributed_s", monkeypatch, snap)
    assert value == pytest.approx(step_own + 2.5 + 0.75 - 6.5)
    assert got is ok, why


def test_folded_label_sets_fail_the_run(monkeypatch):
    """Past the registry's cardinality guard new label sets fold into
    one stream with no phase and no root: the terms are short of its
    seconds, and the check says so instead of reading them as whole."""
    snap = _snap()
    snap["jit.seconds"]["streams"].append(
        {"labels": {"overflow": "true"}, "value": 0.5})
    value, ok, why = _read("setup_trace_unattributed_s", monkeypatch, snap)
    assert value == pytest.approx(WANT["setup_trace_unattributed_s"])
    assert not ok and "overflow" in why


def test_roots_that_trace_no_step_are_taken_out_first(monkeypatch):
    """An initializer's minute of tracing is the remainder's value and
    not its failure."""
    snap = _snap()
    snap["jit.seconds"]["streams"][5]["value"] = 60.0
    value, ok, why = _read("setup_trace_unattributed_s", monkeypatch, snap)
    assert value == pytest.approx(61.25) and ok, why
    assert "module.init_params 60.000" in why


def test_values_are_the_window_opens_not_the_final_registry(monkeypatch):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import setup as tm_setup

    telemetry.reset()
    telemetry.enable()
    try:
        with telemetry.span("fit.step"):
            tm_setup.TRACE_SECONDS.inc(2.0, part="forward", under="fit.step")
            tm_setup.NODE_TRACE_SECONDS.observe(
                0.5, **{"class": "fc", "under": "fit.step"})
        open_t = time.perf_counter()
        telemetry.snapshot()                  # the harness's, at window_open
        # the reference check of an LM cell binds after the window
        tm_setup.TRACE_SECONDS.inc(7.0, part="forward", under="fit.step")
        tm_setup.NODE_TRACE_SECONDS.observe(
            9.5, **{"class": "fc", "under": "fit.step"})
        telemetry.snapshot()
        run = {"open_t": open_t}
        assert first_dispatch.part_s(run, "forward") == 2.0
        assert first_dispatch.part_s(run, "update") == 0.0
        assert first_dispatch.ms_node(run) == 500.0
        # jax timed no phase in this registry: no such stream
        assert first_dispatch.lower_s(run) is None
        assert first_dispatch.part_s(
            {"open_t": time.perf_counter()}, "forward") is None
    finally:
        telemetry.reset()
        telemetry.disable()


def test_entries_move_setup_s_in_every_cell():
    """Found by name: a later PR appends to the manifest. No list since
    PR 68 (ten cells until then): every cell's program traces a step."""
    manifest = lib.load_json(lib.MANIFEST)
    entries = {m["name"]: m for m in manifest["per_layer"]
               if m["name"] in NAMES}
    assert tuple(entries) == NAMES
    for m in entries.values():
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["source"] == "program_counter"
        assert "workloads" not in m
        assert m["unit"] == ("ms/node" if m["name"] == "setup_trace_ms_node"
                             else "s")
