"""Step-time anatomy (ISSUE 6): cost-model capture + cache, per-interval
phase decomposition with an explicit unattributed remainder, the
recompile detector, and the perf_doctor diagnosis.

The acceptance contract under test: named phases + unattributed sum to
the measured wall time EXACTLY (the remainder is never clamped), a
steady warmed fit reports zero recompiles, and a shape-shifting fit is
flagged exactly once per new shape — with a structured diff saying what
changed.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tm
from mxnet_tpu.telemetry import anatomy, costmodel


@pytest.fixture(autouse=True)
def _isolate():
    tm.reset()
    tm.disable()
    yield
    tm.reset()
    tm.disable()


FOUR_DEV = [mx.cpu(i) for i in range(4)]


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _blob_iter(batch_size=8, n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype("f")
    y = rng.randint(0, 4, n).astype("f")
    return mx.io.NDArrayIter(x, y, batch_size=batch_size)


def _fit(mod, it, num_epoch=1):
    mod.fit(it, eval_metric=mx.metric.Accuracy(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, kvstore="device",
            num_epoch=num_epoch, initializer=mx.init.Uniform(0.05))
    assert mod._fused_trainer is not None, "fused path did not engage"


def _records(path, kind):
    out = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == kind:
                out.append(rec)
    return out


# ---------------------------------------------------------------------------
# costmodel
# ---------------------------------------------------------------------------

def test_classify_bounds():
    # 1s of compute at peak vs 0.1s of memory: compute-bound
    r = costmodel.classify(1e12, 1e11, 1.2, 0.0, 1e12, 1e12)
    assert r["bound"] == "compute" and r["t_compute"] == 1.0
    r = costmodel.classify(1e11, 1e12, 1.2, 0.0, 1e12, 1e12)
    assert r["bound"] == "memory"
    r = costmodel.classify(1e11, 1e11, 1.2, 0.9, 1e12, 1e12)
    assert r["bound"] == "comm" and r["t_comm"] == 0.9
    # largest leg explains <30% of wall: the device model can't see the
    # cost — host-bound
    r = costmodel.classify(1e11, 1e11, 10.0, 0.0, 1e12, 1e12)
    assert r["bound"] == "host"
    # no peaks, no comm: unknown
    r = costmodel.classify(1e11, 1e11, 1.0, 0.0, None, None)
    assert r["bound"] == "unknown"


def test_peak_lookup_and_env_override(monkeypatch):
    monkeypatch.delenv("MXTPU_ANATOMY_PEAK_TFLOPS", raising=False)
    monkeypatch.delenv("MXTPU_ANATOMY_PEAK_GBPS", raising=False)
    assert costmodel.peak_flops_for_kind("TPU v4") == 275.0e12
    # substring order: the lite kinds must not fall through to "v5"
    assert costmodel.peak_flops_for_kind("TPU v5e") == 197.0e12
    assert costmodel.peak_flops_for_kind("TPU v5p") == 459.0e12
    assert costmodel.peak_bytes_for_kind("TPU v6e") == 1640.0e9
    assert costmodel.peak_flops_for_kind("cpu") is None
    monkeypatch.setenv("MXTPU_ANATOMY_PEAK_TFLOPS", "2.5")
    monkeypatch.setenv("MXTPU_ANATOMY_PEAK_GBPS", "10")
    assert costmodel.peak_flops_for_kind("cpu") == 2.5e12
    assert costmodel.peak_bytes_for_kind("cpu") == 10e9
    monkeypatch.setenv("MXTPU_ANATOMY_PEAK_TFLOPS", "junk")
    assert costmodel.peak_flops_for_kind("TPU v4") == 275.0e12


def test_extract_cost_real_compiled():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((64, 64), jnp.float32)
    cost = costmodel.extract_cost(f.lower(x, x).compile())
    # dot(64,64) is exactly 2*64^3 flops in XLA's accounting
    assert cost["flops"] == 2.0 * 64 ** 3
    assert cost["bytes_accessed"] and cost["bytes_accessed"] > 0


def test_extract_cost_degrades():
    class _Bad:
        def cost_analysis(self):
            raise RuntimeError("unsupported")

    class _Odd:
        def cost_analysis(self):
            return [{"flops": 7.0}]

    assert costmodel.extract_cost(_Bad()) == {"flops": None,
                                              "bytes_accessed": None}
    assert costmodel.extract_cost(_Odd())["flops"] == 7.0


def test_analytic_forward_flops_hand_count():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3),
                             pad=(1, 1), name="c1")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    got = costmodel.analytic_forward_flops(sym, data=(2, 3, 8, 8),
                                           softmax_label=(2,))
    conv_out = 2 * 4 * 8 * 8              # N*K*OH*OW
    conv = 2.0 * conv_out * 3 * 9 + conv_out   # MACs*2 + bias
    fc_out = 2 * 10
    fc = 2.0 * fc_out * (4 * 8 * 8) + fc_out
    assert got == conv + fc, (got, conv + fc)


# ---------------------------------------------------------------------------
# cost capture cache
# ---------------------------------------------------------------------------

def test_capture_cost_cache_hit_miss():
    tm.enable()

    class _Compiled:
        def cost_analysis(self):
            return {"flops": 100.0, "bytes accessed": 40.0}

    calls = []

    def thunk():
        calls.append(1)
        return _Compiled()

    h0 = anatomy._C_COST_HITS.value()
    m0 = anatomy._C_COST_MISSES.value()
    c1 = anatomy.capture_cost(1, ("single", "sig"), thunk)
    assert c1 == {"flops": 100.0, "bytes_accessed": 40.0}
    c2 = anatomy.capture_cost(1, ("single", "sig"), thunk)
    assert c2 == c1 and len(calls) == 1, "thunk must run once per signature"
    assert anatomy._C_COST_MISSES.value() - m0 == 1
    assert anatomy._C_COST_HITS.value() - h0 == 1
    # a different signature is a fresh miss
    anatomy.capture_cost(1, ("single", "other"), thunk)
    assert len(calls) == 2

    # a program over several devices divides back to one device
    c4 = anatomy.capture_cost(2, ("single",), thunk, devices=4)
    assert c4 == {"flops": 25.0, "bytes_accessed": 10.0}

    # failures cache as None and never rerun the thunk
    bad_calls = []

    def bad():
        bad_calls.append(1)
        raise RuntimeError("no AOT on this backend")

    assert anatomy.capture_cost(3, ("single",), bad) is None
    assert anatomy.capture_cost(3, ("single",), bad) is None
    assert len(bad_calls) == 1


# ---------------------------------------------------------------------------
# recompile detector units
# ---------------------------------------------------------------------------

def test_fingerprint_diff_structure():
    prev = {"inputs": {"data": {"shape": [8, 8], "dtype": "float32",
                                "sharding": "S(x)"},
                       "w": {"shape": [8, 4], "dtype": "float32",
                             "sharding": "R"}},
            "mesh": "{'x': 4}"}
    now = {"inputs": {"data": {"shape": [4, 8], "dtype": "float32",
                               "sharding": "S(x)"},
                      "b": {"shape": [4], "dtype": "float32",
                            "sharding": "R"}},
           "mesh": "{'x': 8}"}
    d = anatomy.fingerprint_diff(prev, now)
    assert d["changed"] == {"data": {"shape": {"was": [8, 8],
                                               "now": [4, 8]}}}
    assert d["added"] == ["b"] and d["removed"] == ["w"]
    assert d["meta"]["mesh"] == {"was": "{'x': 4}", "now": "{'x': 8}"}


def test_note_plan_miss_warmup_then_counts():
    tm.enable()
    sig8 = (("data", (8, 8), "float32", "S"),)
    sig4 = (("data", (4, 8), "float32", "S"),)
    c0 = anatomy._C_RECOMPILES.value()
    anatomy.note_plan_miss(991, sig8)      # warmup compile: not counted
    assert anatomy._C_RECOMPILES.value() == c0
    anatomy.note_plan_miss(991, sig4)
    assert anatomy._C_RECOMPILES.value() == c0 + 1
    # a different program gets its own warmup
    anatomy.note_plan_miss(992, sig8)
    assert anatomy._C_RECOMPILES.value() == c0 + 1


# ---------------------------------------------------------------------------
# end-to-end: fused fit -> anatomy records
# ---------------------------------------------------------------------------

def test_fit_anatomy_phase_sum_invariant(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_ANATOMY_INTERVAL", "4")
    # deterministic peaks so MFU/roofline resolve on the CPU rig
    monkeypatch.setenv("MXTPU_ANATOMY_PEAK_TFLOPS", "1000")
    monkeypatch.setenv("MXTPU_ANATOMY_PEAK_GBPS", "1000")
    jl = str(tmp_path / "telemetry.jsonl")
    tm.enable(jsonl=jl)
    mod = mx.mod.Module(_mlp(), context=FOUR_DEV)
    _fit(mod, _blob_iter(), num_epoch=2)
    tm.flush()

    recs = _records(jl, "anatomy")
    # 8 steps/epoch at interval 4 -> 2 intervals/epoch, 2 epochs
    assert len(recs) >= 4, recs
    assert sum(r["steps"] for r in recs) == 16
    for r in recs:
        # the acceptance invariant: phases + unattributed == wall,
        # exactly (unattributed is the UNclamped remainder)
        assert set(r["phases"]) == {"input_wait", "stage_host",
                                    "dispatch_host", "device_sync",
                                    "collective"}
        gap = sum(r["phases"].values()) + r["unattributed_seconds"]
        assert abs(gap - r["wall_seconds"]) < 1e-9, r
        assert r["wall_seconds"] > 0 and r["step_ms"] > 0
        # warmed steady fit: zero recompiles in every interval
        assert r["recompiles"] == 0, r
    # the cost model resolved: flops gauge + per-record MFU/roofline
    priced = [r for r in recs if "flops_per_step" in r]
    assert priced, "cost capture never resolved"
    for r in priced:
        assert r["bytes_per_step"] > 0
        assert ("mfu" in r) or ("mfu_error" in r)
        assert r["roofline"]["bound"] in ("compute", "memory", "comm",
                                          "host", "unknown")
    snap = tm.snapshot()
    assert snap["anatomy.cost_cache_hits"]["streams"][0]["value"] > 0
    assert _records(jl, "recompile") == []


def test_fit_recompile_flagged_once_per_new_shape(tmp_path):
    jl = str(tmp_path / "telemetry.jsonl")
    tm.enable(jsonl=jl)
    mod = mx.mod.Module(_mlp(), context=FOUR_DEV)
    _fit(mod, _blob_iter(batch_size=8), num_epoch=1)
    tm.flush()
    assert _records(jl, "recompile") == []  # warmup is not a recompile

    # same module, new batch shape: exactly ONE structured recompile
    _fit(mod, _blob_iter(batch_size=4), num_epoch=1)
    tm.flush()
    recs = _records(jl, "recompile")
    assert len(recs) == 1, recs
    diff = recs[0]["diff"]
    assert diff["changed"]["data"]["shape"] == {"was": [8, 8],
                                                "now": [4, 8]}
    assert diff["changed"]["softmax_label"]["shape"] == {"was": [8],
                                                         "now": [4]}
    assert diff["added"] == [] and diff["removed"] == []
    assert recs[0]["fingerprint"]["inputs"]["data"]["shape"] == [4, 8]

    # the same shape again is a plan-cache hit: still exactly one
    _fit(mod, _blob_iter(batch_size=4), num_epoch=1)
    tm.flush()
    assert len(_records(jl, "recompile")) == 1
    assert anatomy._C_RECOMPILES.value() == 1


# ---------------------------------------------------------------------------
# perf_doctor on synthetic anatomy JSONL
# ---------------------------------------------------------------------------

def test_perf_doctor_names_largest_phase(tmp_path):
    from tools import perf_doctor

    path = str(tmp_path / "t.jsonl")
    phases = {"input_wait": 0.001, "stage_host": 0.001,
              "dispatch_host": 0.002, "device_sync": 0.003,
              "collective": 0.080}
    with open(path, "w") as f:
        for ivl, unattr in ((0, 1.5), (1, 0.01), (2, 0.01)):
            wall = sum(phases.values()) + unattr
            f.write(json.dumps({
                "type": "anatomy", "interval": ivl, "steps": 10,
                "wall_seconds": wall, "step_ms": 100.0 * wall,
                "phases": phases, "unattributed_seconds": unattr,
                "recompiles": 0}) + "\n")
    ranked, steps, _ = perf_doctor.diagnose(
        perf_doctor.steady_intervals(_records(path, "anatomy")))
    assert steps == 20 and ranked[0][0] == "collective", ranked
    text = perf_doctor.report(path)
    assert "diagnosis: largest cost is collective" in text
    assert "MXTPU_BUCKET_BYTES" in text  # the advice rides along
    # warmup interval kept -> its compile-heavy unattributed wins
    text_all = perf_doctor.report(path, keep_all=True)
    assert "diagnosis: largest cost is unattributed" in text_all
