"""The trace reduction against a trace recorded on the v5e (three calls
of a two-matmul program, 1024x1024 bf16, with annotations) and against
a two-device trace built by hand."""
import os

import pytest

import reduce_trace as rt

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_tiny_matmul.xplane.pb")


def test_interval_arithmetic():
    assert rt.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]
    assert rt.total([(0, 3), (5, 8)]) == 6
    assert rt.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert rt.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert rt.subtract([(0, 4)], []) == [(0, 4)]
    assert rt.subtract([(2, 4)], [(0, 10)]) == []


def test_parse_and_class_of_recorded_hlo_text():
    conv = ("%convert_reduce_fusion.46 = f32[3]{0:T(128)S(1)} fusion("
            "bf16[256,64,112,112]{0,1,3,2:T(8,128)(2,1)} %fusion.15, "
            "bf16[64,3,7,7]{0,3,2,1:T(8,128)(2,1)S(1)} %copy-done.127), "
            "kind=kOutput, calls=%fused_computation.433")
    assert rt.parse_op(conv) == (
        "convert_reduce_fusion.46", "fusion", "kOutput")
    assert rt.op_class(*rt.parse_op(conv)) == "convolution"
    copy = ("%copy-start.1137 = (f32[3]{0:T(128)S(1)}, f32[3]{0:T(128)}, "
            "u32[]{:S(2)}) copy-start(f32[3]{0:T(128)} %p.1)")
    assert rt.parse_op(copy) == ("copy-start.1137", "copy-start", "")
    assert rt.op_class(*rt.parse_op(copy)) == "copy"
    loop = "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop, calls=%f"
    assert rt.op_class(*rt.parse_op(loop)) == "fusion"
    ar = ("%all-reduce-start.2 = f32[1024]{0} all-reduce-start(f32[1024]{0} "
          "%g), replica_groups={{0,1,2,3}}, to_apply=%add")
    assert rt.op_class(*rt.parse_op(ar)) == "collective"
    assert rt.op_class(*rt.parse_op("custom.1")) == "other"


def test_recorded_v5e_trace():
    raw = rt.load(FIXTURE)
    assert sorted(raw["devices"]) == [0]
    assert len(raw["devices"][0]["ops"]) == 12
    steps = [h for h in raw["host"] if h[1] == "bench.step"]
    sleeps = [h for h in raw["host"] if h[1] == "bench.sleep"]
    assert len(steps) == 3 and len(sleeps) == 3
    window = (steps[0][2], sleeps[-1][2] + sleeps[-1][3])
    out = rt.reduce(raw, window=window)
    d = out["devices"][0]
    # the ops of this program do not overlap: the union is their sum
    assert d["busy_s"] == pytest.approx(82016e-9, rel=1e-9)
    assert out["window_s"] == pytest.approx(10350911e-9, rel=1e-9)
    assert d["idle_share"] == pytest.approx(1 - 82016 / 10350911, rel=1e-9)
    assert d["modules"] == {"jit__lambda": 3}
    # both matmuls are kOutput fusions; the rest is three tiny copies
    assert d["by_class_s"]["convolution"] == pytest.approx(81968e-9)
    assert d["top_ops"][0][0] == "convolution_tanh_fusion kOutput"
    assert d["collective_s"] == 0 and d["collective_exposed_s"] == 0
    # the device sat idle while the host slept inside bench.sleep
    name, seconds = d["idle_gaps"][0]
    assert name.startswith("bench.sleep")
    assert seconds > 0.9 * (out["window_s"] - d["busy_s"]) * 0.9


def test_window_falls_back_to_annotated_slice_then_device_span():
    raw = rt.load(FIXTURE)
    ops = raw["devices"][0]["ops"]
    out = rt.reduce(raw)  # no slice annotations in this recording
    first = min(s for _, s, _ in ops)
    last = max(s + n for _, s, n in ops)
    assert out["window_s"] == pytest.approx((last - first) / 1e9)
    raw["host"] += [("main", "bench.slice_begin", first - 50, 10),
                    ("main", "bench.slice_end", last + 40, 10)]
    out = rt.reduce(raw)
    assert out["window_s"] == pytest.approx((last + 40 - (first - 40)) / 1e9)


def test_two_devices_by_hand_exposed_collective():
    """Device 0 computes 0-100 and runs an all-reduce 80-150 whose done
    op waits 100-150; device 1 computes until 120. Window 0-200 ns."""
    ar = "%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %g)"
    done = "%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} %s)"
    conv = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kOutput, calls=%c"
    raw = {"host": [("main", "bench.batch_end", 150, 50)], "devices": {
        0: {"ops": [(conv, 0, 100), (ar, 80, 1), (done, 100, 50)],
            "async": [(ar, 80, 70)], "modules": [("jit_step(1)", 0, 150)]},
        1: {"ops": [(conv, 0, 120), (ar, 80, 1), (done, 120, 30)],
            "async": [(ar, 80, 70)], "modules": [("jit_step(1)", 0, 150)]},
    }}
    out = rt.reduce(raw, window=(0, 200))
    d0, d1 = out["devices"][0], out["devices"][1]
    assert d0["busy_s"] == pytest.approx(150e-9)
    assert d0["idle_share"] == pytest.approx(0.25)
    assert d0["collective_s"] == pytest.approx(70e-9)
    assert d0["collective_exposed_s"] == pytest.approx(50e-9)
    assert d1["collective_exposed_s"] == pytest.approx(30e-9)
    assert d0["by_class_s"]["convolution"] == pytest.approx(100e-9)
    assert d0["idle_gaps"] == [["bench.batch_end", pytest.approx(50e-9)]]
    assert d0["modules"] == {"jit_step": 1}


def test_no_device_plane_gives_nothing():
    assert rt.reduce({"devices": {}, "host": []}) is None
