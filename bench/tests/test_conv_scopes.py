"""``conv_scopes``: whole steps counted in a slice that cuts one, the
bounds of two of ResNet-50's convolutions against figures worked by hand,
the unnamed-gradient check, the six readers over a reduction handed in,
and their manifest entries (found by name)."""
import pytest

import conv_scopes as cs
import lib
import reduce_scopes as rs
import reduce_trace as rt

CELLS = ["resnet50_fit_resident", "resnet50_fit_dp4",
         "inception_v3_fit_resident"]
# the three passes' milliseconds and each pass's share of its bound (PR 66
# had room for the data gradient's alone; PR 68 listed the other two)
METRICS = dict({"conv_%s_device_ms" % p: "ms/step" for p in cs.PASSES},
               **{"conv_%s_roofline_share" % p: "%" for p in cs.PASSES})
PEAK = lib.load_json(lib.BENCH + "/peaks.json")["TPU v5 lite"]
TRACE = {"devices": {}}  # the harness's own reduction: only its truth is read

STEP = "jit(step)/fwd_bwd/"
FWD = STEP + "jvp(conv/c1)/conv_general_dilated:"
DGRAD = (STEP + "transpose(fwd_bwd)/jvp(conv/c1)/dgrad/transpose(jvp())/"
         "conv_general_dilated:")
WGRAD = (STEP + "transpose(fwd_bwd)/jvp(conv/c1)/wgrad/transpose(jvp())/"
         "conv_general_dilated:")
BARE = STEP + "transpose(jvp(conv/c1))/conv_general_dilated:"
BN = STEP + "transpose(fwd_bwd)/jvp(bn/bn1)/mul:"
# name: (scope, offset in the step, length); each half of a step holds 40 ns
# of convolutions, so that a step cut in half holds half a step's
OPS = {"%f.1 = f32[8] fusion(), kind=kOutput": (FWD, 0, 20),
       "%b.1 = f32[8] fusion(), kind=kLoop": (BN, 20, 5),
       "%d.1 = f32[8] fusion(), kind=kOutput": (DGRAD, 25, 20),
       "%w.1 = f32[8] fusion(), kind=kOutput": (WGRAD, 50, 40),
       "%s.1 = f32[8] fusion(), kind=kOutput": (BN, 90, 4)}


def hand_slice(names=None):
    """A slice of 450 ns over a device whose step program takes 100 and
    starts at -50, 50, 150, 250, 350 and 450: four whole steps, one cut
    at each edge, 4.5 periods. The cell said ``trace_steps`` 5."""
    host = [("python", rt.SLICE_BEGIN, -10, 10),
            ("python", rt.SLICE_END, 450, 5)]
    ops, modules = [], []
    for t in range(-50, 500, 100):
        modules.append(("jit_step(123)", t, 100))
        ops += [(name, t + at, length)
                for name, (_, at, length) in OPS.items()]
    raw = {"host": host,
           "devices": {0: {"ops": ops, "async": [], "modules": modules}}}
    scopes = {0: {name: (names or {}).get(scope, scope)
                  for name, (scope, _, _) in OPS.items()}}
    return raw, scopes


def test_a_whole_steps_milliseconds_where_trace_steps_reads_nine_tenths():
    raw, scopes = hand_slice()
    red = cs.reduce(raw, scopes)
    assert red["steps"] == 4
    assert red["periods"] == pytest.approx(4.5)
    assert red["pass_s"] == pytest.approx(
        {"fwd": 20e-9, "dgrad": 20e-9, "wgrad": 40e-9})
    assert red["table_s"][("c1", "wgrad")] == pytest.approx(40e-9)
    # a convolution-class fusion filed under another node's class
    assert red["stray_s"] == pytest.approx({"bn": 4e-9})
    # what the trace_steps readers make of the same slice
    run = {"trace_steps": 5, "scopes": rs.reduce(raw, scopes)}
    assert rs.class_ms(True, run, "conv") == pytest.approx(
        0.9 * 1e3 * 80e-9)


def test_a_step_the_recording_cut_is_not_whole():
    """The profiler stops inside a step and writes what it saw of it: an
    event that ends before the slice does (its mark comes after the last
    device event) and is no whole step (a v5e slice: 434.03 to 435.60 ms
    of 435.89, where a step takes 95.9)."""
    raw, scopes = hand_slice()
    dev = raw["devices"][0]
    dev["modules"] = [m for m in dev["modules"] if m[1] < 350]
    dev["modules"].append(("jit_step(123)", 350, 8))
    dev["ops"] = [op for op in dev["ops"] if op[1] < 350]
    dev["ops"].append(("%f.1 = f32[8] fusion(), kind=kOutput", 350, 8))
    red = cs.reduce(raw, scopes)
    assert red["steps"] == 3
    assert red["pass_s"]["fwd"] == pytest.approx(20e-9)


def test_no_whole_step_or_no_conv_scope_reads_none():
    raw, scopes = hand_slice()
    raw["devices"][0]["modules"] = []
    assert cs.reduce(raw, scopes) is None
    raw, scopes = hand_slice({FWD: BN, DGRAD: BN, WGRAD: BN})
    assert cs.reduce(raw, scopes) is None


def test_a_bare_gradient_fails_the_check_and_reads_none_for_its_passes():
    raw, scopes = hand_slice()
    ok, why = cs.unnamed_check(cs.reduce(raw, scopes))
    assert ok and "4 whole steps counted, 4.500 periods held" in why
    # an older commit: both gradients under the node's bare transpose
    raw, scopes = hand_slice({DGRAD: BARE, WGRAD: BARE})
    red = cs.reduce(raw, scopes)
    assert red["pass_s"] == pytest.approx({"fwd": 20e-9, "bwd": 60e-9})
    run = {"conv_scopes": red}
    assert cs.pass_ms(TRACE, run, "fwd") == pytest.approx(20e-6)
    assert cs.pass_ms(TRACE, run, "dgrad") is None
    assert cs.pass_ms(TRACE, run, "wgrad") is None
    # one lever left one gradient unnamed
    raw, scopes = hand_slice({DGRAD: BARE})
    ok, why = cs.unnamed_check(cs.reduce(raw, scopes))
    assert not ok and "unnamed" in why


def resnet50_nodes():
    cfg = lib.load_json(lib.find("configs", "resnet50", ".json"))
    return cfg, {n["name"]: n for n in cs.cell_nodes(cfg, 256)}


def test_bounds_of_two_resnet50_nodes_at_batch_256_in_bf16():
    cfg, nodes = resnet50_nodes()
    assert cs.array_width(cfg) == 2
    assert len(nodes) == 53 and not any(
        n["reads_batch"] for n in nodes.values())
    # stage1_unit1_conv3: 256 x 64 x 56 x 56 by 256 x 64 x 1 x 1
    flops_s, bytes_s = cs.bounds(nodes["stage1_unit1_conv3"], "fwd", 2, PEAK)
    assert 1e3 * flops_s == pytest.approx(0.134, abs=5e-4)
    assert 1e3 * bytes_s == pytest.approx(0.627, abs=5e-4)
    # stage2_unit1_sc, 1 x 1 of stride 2: a quarter of its input counted
    sc = nodes["stage2_unit1_sc"]
    assert sc["touched"] * 4 == 256 * 256 * 56 * 56
    flops_s, bytes_s = cs.bounds(sc, "fwd", 2, PEAK)
    assert 1e3 * flops_s == pytest.approx(0.267, abs=5e-4)
    assert 1e3 * bytes_s == pytest.approx(0.377, abs=5e-4)
    # the issue's sums: 10.62 ms of products and 13.01 ms of bytes a pass,
    # 30 nodes bound by their bytes, the larger bound 16.3 ms a pass
    both = [cs.bounds(n, "wgrad", 2, PEAK) for n in nodes.values()]
    assert 1e3 * sum(f for f, _ in both) == pytest.approx(10.62, abs=0.01)
    assert 1e3 * sum(b for _, b in both) == pytest.approx(13.01, abs=0.01)
    assert sum(b > f for f, b in both) == 30
    assert 1e3 * sum(max(fb) for fb in both) == pytest.approx(16.3, abs=0.05)


def test_the_batchs_data_gradient_has_no_bound():
    cfg = lib.load_json(lib.find("configs", "inception_v3", ".json"))
    assert cs.array_width(cfg) == 4
    nodes = cs.cell_nodes(cfg, 2)
    assert len(nodes) == 94
    first, = [n for n in nodes if n["reads_batch"]]
    assert first["name"] == "conv_conv2d"
    assert cs.bounds(first, "dgrad", 4, PEAK) is None
    assert cs.bounds(first, "wgrad", 4, PEAK) == cs.bounds(
        first, "fwd", 4, PEAK)
    amp = lib.load_json(lib.find("configs", "resnet50_amp", ".json"))
    assert cs.array_width(amp) == 2


@pytest.mark.parametrize("kernel,stride,dilate,pad,extent,out,touched", [
    (1, 2, 1, 0, 56, 28, 28),   # every other element
    (3, 2, 1, 1, 56, 28, 56),   # overlapping windows reach them all
    (1, 1, 1, 0, 14, 14, 14),
    (3, 1, 2, 2, 9, 9, 9),
    (2, 3, 1, 0, 9, 3, 6),      # windows of 2 every 3: a third untouched
    (7, 2, 1, 3, 224, 112, 224),
])
def test_touched_counts_what_some_output_reads(kernel, stride, dilate, pad,
                                               extent, out, touched):
    assert cs._touched(extent, out, kernel, stride, dilate, pad) == touched


def test_rows_are_sorted_by_milliseconds_over_the_bound():
    _, nodes = resnet50_nodes()
    name = "stage1_unit1_conv3"
    red = {"table_s": {(name, "fwd"): 1.0e-3, (name, "dgrad"): 0.7e-3,
                       ("gone", "bwd"): 5e-3}}
    table = cs.rows(red, list(nodes.values()), 2, PEAK)
    assert len(table) == 3 * 53 + 1
    assert [(r["node"], r["pass"]) for r in table[:2]] == [
        (name, "fwd"), (name, "dgrad")]
    assert table[0]["bound_ms"] == pytest.approx(0.627, abs=5e-4)
    assert table[0]["over_ms"] == pytest.approx(1.0 - 0.627, abs=5e-4)
    gone, = [r for r in table if r["node"] == "gone"]
    assert gone["bound_ms"] is None and gone["over_ms"] is None
    unseen, = [r for r in table if (r["node"], r["pass"]) == (name, "wgrad")]
    assert unseen["ms"] is None and unseen["bound_ms"] is not None


def reader(name):
    return lib.load_module("layer_metrics", name)


def hand_run():
    raw, scopes = hand_slice()
    cfg = lib.load_json(lib.find("configs", "resnet50", ".json"))
    return {"conv_scopes": cs.reduce(raw, scopes), "cfg": cfg, "batch": 256,
            "chips": 1, "peak": PEAK, "trace_steps": 5}


@pytest.mark.parametrize("which,ns", [("fwd", 20), ("dgrad", 20),
                                      ("wgrad", 40)])
def test_the_readers_read_the_reduction(which, ns):
    run = hand_run()
    value = reader("conv_%s_device_ms" % which).compute(TRACE, {}, run)
    if which == "wgrad":
        value, ok, why = value
        assert ok and "periods held" in why
    assert value == pytest.approx(ns * 1e-6)
    # 16.3 ms of bound over a hand-made step of nanoseconds
    assert cs.pass_roofline_share(TRACE, run, which) == pytest.approx(
        100 * 16.337 / (ns * 1e-6), rel=1e-3)
    assert reader("conv_%s_roofline_share" % which).compute(
        TRACE, {}, run) == cs.pass_roofline_share(TRACE, run, which)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_readers_read_none_without_a_slice_or_a_name(name):
    assert reader(name).compute(None, {}, hand_run()) is None
    raw, scopes = hand_slice({DGRAD: BARE, WGRAD: BARE})
    run = dict(hand_run(), conv_scopes=cs.reduce(raw, scopes))
    value = reader(name).compute(TRACE, {}, run)
    assert (value is None) is ("fwd" not in name)
    assert reader(name).compute(TRACE, {}, dict(run, conv_scopes=None)) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_manifest_lists_the_three_conv_cells(name):
    manifest = lib.load_json(lib.MANIFEST)
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": METRICS[name],
        "better": "lower" if name.endswith("_ms") else "higher",
        "source": "device_trace", "layer": "ops and kernels",
        "moves": "train_samples_s", "workloads": CELLS}
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert all(c in cells for c in CELLS)


def test_the_command_prints_the_table(tmp_path, capsys, monkeypatch):
    """``main`` over a reduction handed in through ``reduce``: the header,
    a line a pass, the rows and the straddling line."""
    raw, scopes = hand_slice({FWD: FWD.replace("c1", "conv0"),
                              DGRAD: DGRAD.replace("c1", "conv0"),
                              WGRAD: WGRAD.replace("c1", "conv0")})
    monkeypatch.setattr(rt, "load", lambda path: raw)
    monkeypatch.setattr(rs, "scope_names", lambda path: scopes)
    assert cs.main(["conv_scopes.py", "x.xplane.pb",
                    "resnet50_fit_resident"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith(
        "resnet50_fit_resident: 4 whole steps counted, 4.500 periods held "
        "(trace_steps 5)")
    assert any(line.startswith("pass wgrad") for line in out)
    head, = [i for i, line in enumerate(out) if line.startswith("node ")]
    assert len(out) == head + 1 + 3 * 53 + 1
    row = {tuple(line.split()[:2]): line.split() for line in out[head + 1:-1]}
    assert row["conv0", "wgrad"][6] == "0.000"      # ns of a hand-made op
    assert row["conv0", "wgrad"][8] == "0.596"      # its bytes' ms
    assert row["stage1_unit1_conv3", "fwd"][6] == "-"   # no op carries it
    assert out[-1].startswith("convolution-class time outside every conv")
    assert '"bn"' in out[-1]
