"""The eight ``setup_*`` readers over ``setup_phases`` (ten until PR 68
took ``setup_bind_s`` and ``setup_telemetry_s`` out): each value from a
hand-built registry dump and ``run``, nothing where the registry holds
no such stream, the partition's sum, and the remainder's two limits."""
import pytest

import lib
import setup_phases
from helpers import check_rehearsal, run_bench

NAMES = ("setup_runtime_s", "setup_import_s", "setup_init_params_s",
         "setup_init_optimizer_s", "setup_first_dispatch_s",
         "setup_unattributed_s", "setup_trace_lower_s", "setup_h2d_gb")
# process start at 100 s on the clock, the window opens 60 s later
RUN = {"open_t": 160.0, "setup_s": 60.0, "first_step_t": 150.0}


def _hist(**by_span):
    return {"kind": "histogram", "streams": [
        {"labels": {"span": k.replace("__", ".")}, "sum": v, "count": n,
         "counts": [], "buckets": []} for k, (v, n) in by_span.items()]}


def _counter(*streams):
    return {"kind": "counter", "streams": [
        {"labels": labels, "value": v} for labels, v in streams]}


def _snap():
    return {
        "process.import_t0": {"kind": "gauge", "streams": [
            {"labels": {}, "value": 109.0}]},
        "process.import_seconds": {"kind": "gauge", "streams": [
            {"labels": {}, "value": 3.0}]},
        "mxtpu.span_seconds": _hist(
            module__bind=(5.0, 1), module__init_params=(7.0, 1),
            module__init_optimizer=(11.0, 1), module__fused_build=(1.0, 1),
            train_step__first_dispatch=(8.0, 2),
            train_step__dispatch=(9.5, 40),
            telemetry__cost_capture=(2.0, 2), fit__step=(30.0, 40)),
        "jit.seconds": _counter(
            ({"phase": "trace", "under": "fit.step"}, 3.0),
            ({"phase": "lower", "under": "fit.step"}, 1.5),
            ({"phase": "compile", "under": "fit.step"}, 2.0),
            ({"phase": "trace", "under": "module.init_params"}, 0.25),
            ({"phase": "lower", "under": "io.feed_fill"}, 0.125),
            ({"phase": "lower", "under": "telemetry.cost_capture"}, 1.0),
            ({"phase": "trace", "under": "-"}, 0.5)),
        "device.h2d_bytes": _counter(
            ({"under": "module.bind"}, 5.8e9),
            ({"under": "module.init_params"}, 2.9e9),
            ({"under": "module.init_optimizer"}, 8.8e9),
            ({"under": "-"}, 1e9), ({"under": "fit.step"}, 4e6)),
    }


WANT = {
    "setup_runtime_s": 9.0, "setup_import_s": 3.0,
    "setup_init_params_s": 7.0, "setup_init_optimizer_s": 11.0,
    "setup_first_dispatch_s": 8.0,
    # 60 - (9 + 3 + 7 + 11 + 8) - the harness's 10: ``module.bind``'s 5
    # and ``telemetry.cost_capture``'s 2 have no entry and lie in it
    "setup_unattributed_s": 12.0,
    "setup_trace_lower_s": 3.0 + 1.5 + 0.25 + 0.125,
    "setup_h2d_gb": 17.5,
}


def _read(name, monkeypatch, snap, run=RUN):
    monkeypatch.setattr(setup_phases, "registry_at_open",
                        lambda r: snap if r.get("open_t") else None)
    return lib.load_module("layer_metrics", name).compute(
        None, {"telemetry": {}}, dict(run))


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_its_streams(name, monkeypatch):
    value = _read(name, monkeypatch, _snap())
    if name == "setup_unattributed_s":
        value, ok, why = value
        # 12 s less the two spans' 7 and the 0.5 s of jax outside every
        # span is past the larger of 2 s and 5% of 60 s
        assert not ok and "unattributed 12.000" in why
        assert "module.bind + telemetry.cost_capture 7.000" in why
        assert "jax outside every span 0.500" in why
    assert value == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_in_an_older_program(name, monkeypatch):
    """None, never zero, never a raise: the benchmark's files are laid
    over the parent's checkout too."""
    older = {"mxtpu.span_seconds": _hist(fit__step=(30.0, 40),
                                         train_step__dispatch=(9.5, 40))}
    assert _read(name, monkeypatch, older) is None
    assert _read(name, monkeypatch, None) is None
    assert _read(name, monkeypatch, _snap(), run={"steps": 0}) is None


def test_terms_and_the_harness_interval_sum_to_setup_s():
    found = setup_phases.terms(RUN, _snap())
    assert set(found) == set(setup_phases.TERMS)
    assert sum(found.values()) + setup_phases.harness_s(RUN) == \
        pytest.approx(RUN["setup_s"])


@pytest.mark.parametrize("first_dispatch,ok", [
    # the remainder less the two spans that have no entry (7 s):
    (8.0, False),     # 5.0: something of size has no span
    (9.6, True),      # 3.4, of which 0.5 is jax outside every span
    (11.5, True),     # 1.5
    (13.4, True),     # -0.4: clock noise
    (13.6, False),    # -0.6: an interval counted twice
])
def test_remainder_limits(first_dispatch, ok, monkeypatch):
    snap = _snap()
    for s in snap["mxtpu.span_seconds"]["streams"]:
        if s["labels"]["span"] == "train_step.first_dispatch":
            s["sum"] = first_dispatch
    value, got, why = _read("setup_unattributed_s", monkeypatch, snap)
    assert value == pytest.approx(20.0 - first_dispatch)
    assert got is ok, why


def test_missing_span_terms_count_nothing_in_the_remainder(monkeypatch):
    """A path that never dispatches a fused step still closes."""
    snap = _snap()
    snap["mxtpu.span_seconds"]["streams"] = [
        s for s in snap["mxtpu.span_seconds"]["streams"]
        if s["labels"]["span"] != "train_step.first_dispatch"]
    assert _read("setup_first_dispatch_s", monkeypatch, snap) is None
    value, _, _ = _read("setup_unattributed_s", monkeypatch, snap)
    assert value == pytest.approx(20.0)


def test_registry_at_open_is_the_dump_taken_as_the_window_opened():
    from mxnet_tpu import telemetry

    telemetry.reset()
    telemetry.enable()
    try:
        c = telemetry.counter("t.setup_phases")
        c.inc(1)
        telemetry.snapshot()                  # someone's, before the window
        import time
        open_t = time.perf_counter()
        c.inc(1)
        telemetry.snapshot()                  # the harness's, at window_open
        c.inc(1)
        telemetry.snapshot()                  # at window_close
        c.inc(1)                              # after the window
        dump = setup_phases.registry_at_open({"open_t": open_t})
        assert dump["t.setup_phases"]["streams"][0]["value"] == 2
        assert setup_phases.registry_at_open(
            {"open_t": time.perf_counter()}) is None
        assert setup_phases.registry_at_open({}) is None
    finally:
        telemetry.reset()
        telemetry.disable()


def test_entries_move_setup_s_in_every_cell():
    """Found by name, wherever they stand; no ``workloads`` key since
    PR 68: every cell's program opens these spans, a later cell's too."""
    by_name = {m["name"]: m for m in lib.load_json(lib.MANIFEST)["per_layer"]}
    assert not {"setup_bind_s", "setup_telemetry_s"} & set(by_name)
    for name in NAMES:
        m = by_name[name]
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert "workloads" not in m
        assert m["unit"] == ("GB" if name == "setup_h2d_gb" else "s")


@pytest.mark.parametrize("workload", ["inception_v3_fit_resident",
                                      "kanana2_fit_share_8k"])
def test_rehearsal_prints_units_for_all_eight_and_values_for_none(workload):
    proc = run_bench(["--workload", workload, "--seed", "2147483659",
                      "--seconds", "1", "--trace", "1", "--rehearse-cpu"])
    check_rehearsal(proc, NAMES)
    assert "check name=setup_unattributed_s" in proc.stdout
