"""The scope reduction and its readers: a two-step trace built by hand
(spans on the host line, scoped ops on the device line), the sum checks
failing on a trace that does not add up, and a three-step toy ``fit``
recorded on the v5e (fixtures/record_toy_fit.py)."""
import os

import pytest

import lib
import reduce_scopes as rs
import reduce_trace as rt

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
TOY = os.path.join(FIXTURES, "v5e_toy_fit.xplane.pb")

FWD = "jit(step)/jit(main)/fwd_bwd/jvp(conv/conv0)/conv_general_dilated:"
BWD = "jit(step)/jit(main)/fwd_bwd/transpose(jvp(conv/conv0))/conv_general_dilated:"
BN_BWD = "jit(step)/jit(main)/fwd_bwd/transpose(fwd_bwd)/jvp(bn/bn0)/mul:"
POOL = "jit(step)/jit(main)/fwd_bwd/jvp(pool/pool0)/reduce_window_max:"
UPD = "jit(step)/jit(main)/update/add:"
WGRAD = ("jit(step)/fwd_bwd/transpose(fwd_bwd)/jvp(conv/c1)/wgrad/"
         "dot_general:")


def test_classify():
    assert rs.classify(FWD) == ("fwd_bwd", "conv", "fwd", None)
    assert rs.classify(BWD) == ("fwd_bwd", "conv", "bwd", None)
    assert rs.classify(BN_BWD) == ("fwd_bwd", "bn", "bwd", None)
    assert rs.classify(WGRAD) == ("fwd_bwd", "conv", "bwd", "wgrad")
    assert rs.classify(UPD) == ("update", None, None, None)
    assert rs.classify("jit(step)/guard/mul:") == ("guard",) + (None,) * 3
    assert rs.classify("jit(convert_element_type)/convert:") == (None,) * 4
    assert rs.classify(None) == (None,) * 4
    # a node's name never reads as a class or a phase
    assert rs.classify("jit(step)/fwd_bwd/jvp(other/update_gate)/mul:") == (
        "fwd_bwd", "other", "fwd", None)


def test_self_times_sum_to_the_union():
    events = [("while", 0, 100), ("a", 10, 30), ("b", 30, 50),
              ("c", 120, 130), ("late", 40, 45)]
    own = dict(rs.self_times(events))
    assert own == {"while": 60, "a": 20, "b": 15, "late": 5, "c": 10}
    assert sum(own.values()) == rt.total(rt.union(
        [(s, e) for _, s, e in events]))


def hand_trace():
    """Two steps of 100 ns between the slice marks at 1000 and 1200. A
    step: input 0-10, update 10-40, metric 40-95, callbacks 95-100;
    the device runs 20-90 of it (fwd 20, bwd 40, update 5, a copy 5)."""
    host = [("python", rt.SLICE_BEGIN, 990, 10),
            ("python", rt.SLICE_END, 1200, 5)]
    ops = []
    for t in (1000, 1100):
        host += [("python", "fit.step", t, 100),
                 ("python", "fit.input", t, 10),
                 ("python", "io.feed_fill", t + 2, 6),
                 ("python", "module.update", t + 10, 30),
                 ("python", "train_step.dispatch", t + 15, 25),
                 ("python", "module.update_metric", t + 40, 55),
                 ("python", "fit.callbacks", t + 95, 3)]
        ops += [("%conv.1 = f32[8] fusion(), kind=kOutput", t + 20, 20),
                ("%conv_bwd.1 = f32[8] fusion(), kind=kOutput", t + 40, 30),
                ("%bn_bwd.1 = f32[8] fusion(), kind=kLoop", t + 70, 8),
                ("%pool.1 = f32[8] reduce-window()", t + 78, 2),
                ("%sgd.1 = f32[8] fusion(), kind=kLoop", t + 80, 5),
                ("%copy.1 = f32[8] copy()", t + 85, 5)]
    raw = {"host": host,
           "devices": {0: {"ops": ops, "async": [], "modules": []}}}
    scopes = {0: {"%conv.1 = f32[8] fusion(), kind=kOutput": FWD,
                  "%conv_bwd.1 = f32[8] fusion(), kind=kOutput": BWD,
                  "%bn_bwd.1 = f32[8] fusion(), kind=kLoop": BN_BWD,
                  "%pool.1 = f32[8] reduce-window()": POOL,
                  "%sgd.1 = f32[8] fusion(), kind=kLoop": UPD}}
    return raw, scopes


def hand_run(raw, scopes):
    return {"trace_steps": 2, "cell": {"name": "hand"},
            "scopes": rs.reduce(raw, scopes)}


def read(name, trace, run):
    return lib.load_module("layer_metrics", name).compute(
        trace, {"telemetry": {}}, run)


def test_hand_built_trace_through_every_reader():
    raw, scopes = hand_trace()
    trace = rt.reduce(raw)
    run = hand_run(raw, scopes)
    red = run["scopes"]
    assert red["fit_steps"] == 2
    assert red["idle_s"] == pytest.approx(60e-9)
    assert trace["devices"][0]["busy_s"] == pytest.approx(140e-9)
    ms = 1e-6  # a nanosecond, in milliseconds
    assert read("idle_under_input_ms", trace, run) == pytest.approx(10 * ms)
    assert read("idle_under_dispatch_ms", trace, run) == pytest.approx(10 * ms)
    assert read("idle_under_metric_ms", trace, run) == pytest.approx(5 * ms)
    value, ok, why = read("idle_under_other_ms", trace, run)
    assert value == pytest.approx(5 * ms) and ok, why
    # fit.step's own time: 100 less input 10, update 30, metric 55,
    # callbacks 3
    assert read("fit_unattributed_ms_step", trace, run) == pytest.approx(
        2 * ms)
    assert read("step_fwd_device_ms", trace, run) == pytest.approx(22 * ms)
    assert read("step_bwd_device_ms", trace, run) == pytest.approx(38 * ms)
    assert read("step_update_device_ms", trace, run) == pytest.approx(5 * ms)
    value, ok, why = read("step_unscoped_device_ms", trace, run)
    assert value == pytest.approx(5 * ms) and ok, why
    assert read("conv_device_ms", trace, run) == pytest.approx(50 * ms)
    assert read("bn_device_ms", trace, run) == pytest.approx(8 * ms)
    assert read("pool_device_ms", trace, run) == pytest.approx(2 * ms)
    assert red["by_class_s"]["conv"] == {
        "fwd": pytest.approx(40e-9), "bwd": pytest.approx(60e-9)}


def test_sum_checks_fail_on_a_trace_that_does_not_add_up():
    raw, scopes = hand_trace()
    run = hand_run(raw, scopes)
    # the harness's own reduction saw another window: 10% more idle and
    # busy time than the scope reduction adds up to
    trace = rt.reduce(raw)
    d = trace["devices"][0]
    d["idle_share"] *= 1.1
    d["busy_s"] *= 1.1
    value, ok, why = read("idle_under_other_ms", trace, run)
    assert not ok and "against idle ms/step" in why
    value, ok, why = read("step_unscoped_device_ms", trace, run)
    assert not ok and "against step_device_ms" in why
    # within tolerance they pass: 1.5% on idle (2%), 0.5% on busy (1%)
    trace = rt.reduce(raw)
    trace["devices"][0]["idle_share"] *= 1.015
    trace["devices"][0]["busy_s"] *= 1.005
    assert read("idle_under_other_ms", trace, run)[1]
    assert read("step_unscoped_device_ms", trace, run)[1]


def test_a_program_without_spans_or_scopes_reads_as_nothing():
    raw, _ = hand_trace()
    raw["host"] = [h for h in raw["host"] if h[1].startswith("bench.")]
    trace = rt.reduce(raw)
    run = hand_run(raw, {})
    for m in lib.load_json(lib.MANIFEST)["per_layer"][-12:]:
        assert read(m["name"], trace, run) is None, m["name"]


def test_slice_path_follows_run_py(tmp_path, monkeypatch):
    prof = tmp_path / "trace" / "plugins" / "profile" / "t0"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr("sys.argv", ["run.py", "--out", str(tmp_path)])
    assert rs.slice_path({"cell": {"name": "x"}}) == str(
        prof / "host.xplane.pb")
    monkeypatch.setattr("sys.argv", ["run.py", "--out=" + str(tmp_path)])
    assert rs.slice_path({"cell": {"name": "x"}}) == str(
        prof / "host.xplane.pb")
    monkeypatch.setattr("sys.argv", ["run.py"])
    assert rs.slice_path({"cell": {"name": "no_such_cell"}}) is None
    assert rs.of({"cell": {"name": "no_such_cell"}}) is None


def test_recorded_v5e_toy_fit():
    """Three steps of a toy fit on the chip: the scope stat is read from
    the file's wire format, spans and ops lie on one clock."""
    assert os.path.getsize(TOY) < 400 * 1024
    names = rs.scope_names(TOY)
    assert sorted(names) == [0]
    paths = set(names[0].values())
    assert any("fwd_bwd/jvp(conv/conv0)" in p for p in paths)
    assert any("transpose(jvp(conv/conv0))" in p for p in paths)
    assert any("/update/" in p for p in paths)
    raw = rt.load(TOY)
    red = rs.reduce(raw, names)
    trace = rt.reduce(raw)
    d = trace["devices"][0]
    assert red["window_s"] == pytest.approx(0.01350196, rel=1e-6)
    assert red["busy_s"] == pytest.approx(d["busy_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(3.7912e-05, rel=1e-4)
    assert red["idle_s"] == pytest.approx(
        d["idle_share"] * trace["window_s"], rel=1e-9)
    # the slice opens and closes inside a callback, so the first and the
    # last fit.step are cut and two of the three lie inside it; their
    # children are whole
    assert red["fit_steps"] == 2
    under = red["idle_under_s"]
    assert sum(under.values()) == pytest.approx(red["idle_s"], rel=1e-9)
    assert under["dispatch"] == pytest.approx(0.007888213, rel=1e-4)
    assert under["metric"] == pytest.approx(0.002866534, rel=1e-4)
    assert under["input"] == pytest.approx(0.00182135, rel=1e-4)
    assert red["fit_self_s"] == pytest.approx(0.000202265, rel=1e-4)
    phases = red["phase_s"]
    assert sum(phases.values()) == pytest.approx(red["busy_s"], rel=1e-9)
    assert phases["fwd"] == pytest.approx(5.425e-06, rel=1e-3)
    assert phases["bwd"] == pytest.approx(1.7879e-05, rel=1e-3)
    assert phases["update"] == pytest.approx(1.52e-07, rel=1e-2)
    assert set(red["by_class_s"]) >= {"conv", "bn", "pool", "fc", "loss"}
    assert red["by_class_s"]["conv"]["bwd"] == pytest.approx(
        6.36e-06, rel=1e-3)
    # and through the readers, as run.py hands things over
    run = {"trace_steps": 3, "cell": {"name": "toy"}, "scopes": red}
    assert read("idle_under_other_ms", trace, run)[1]
    assert read("step_unscoped_device_ms", trace, run)[1]
    assert read("conv_device_ms", trace, run) == pytest.approx(
        1e3 * (3.692e-06 + 6.36e-06) / 3, rel=1e-3)
