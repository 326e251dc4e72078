"""Pallas conv-backward pair (ISSUE 17).

The acceptance contract under test:

- ``conv_bwd_filter`` / ``conv_bwd_input`` match ``jax.lax.conv``'s own
  gradients in interpret mode (rtol 1e-6 fp32), with f32 accumulation
  and a FIXED accumulation order under bf16 (bitwise-stable repeats);
- the ``MXTPU_CONV_KERNEL=pallas`` dispatch table only engages inside
  the tuned envelope — stride/dilation/groups/channel-alignment cases
  fall back to XLA (or the taps lever) and executor gradients stay
  identical with the flag on or off, including against the NHWC lever;
- a full lenet-style fit converges the same with the kernels on or off;
- a fused fit under telemetry emits one ``op_costs`` record.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tm
from mxnet_tpu.ops import nn as nnops
from mxnet_tpu.ops import kernels as pk

_ENV_VARS = (
    "MXTPU_CONV_KERNEL", "MXNET_CONV_WGRAD", "MXNET_CONV_BWD_LAYOUT",
    "MXNET_CONV_S2D",
)


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    for var in _ENV_VARS:
        monkeypatch.delenv(var, raising=False)
    pk.conv.conv_plan_cache.clear()
    tm.reset()
    tm.disable()
    yield
    pk.conv.conv_plan_cache.clear()
    tm.reset()
    tm.disable()


FOUR_DEV = [mx.cpu(i) for i in range(4)]


def _ref(dshape, wshape, pad, dtype, seed=0):
    """(x, w, cotangent, dgrad, wgrad) from jax's own conv vjp."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(*dshape), dtype)
    w = jnp.asarray(rng.randn(*wshape) * 0.1, dtype)
    dn = jax.lax.conv_dimension_numbers(
        dshape, wshape, ("NCHW", "OIHW", "NCHW"))

    def f(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), [(pad[0], pad[0]), (pad[1], pad[1])],
            dimension_numbers=dn)

    y, vjp = jax.vjp(f, x, w)
    g = jnp.asarray(rng.randn(*y.shape), dtype)
    gd, gw = vjp(g)
    return x, w, g, gd, gw


# kernel k x k at pad p across 1x1/3x3/5x5, 'same' and 'valid',
# non-square spatial, block_n both 1 and >1
CASES = [
    ((2, 8, 10, 10), (16, 8, 3, 3), (1, 1)),
    ((4, 16, 7, 9), (8, 16, 1, 1), (0, 0)),
    ((2, 8, 9, 11), (8, 8, 3, 3), (0, 0)),
    ((3, 8, 8, 8), (8, 8, 5, 5), (2, 2)),
]


# ---------------------------------------------------------------------------
# interpret-mode parity vs jax.lax.conv gradients
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dshape,wshape,pad", CASES)
def test_kernel_parity_fp32(dshape, wshape, pad):
    x, w, g, gd_ref, gw_ref = _ref(dshape, wshape, pad, jnp.float32)
    plan = pk.conv_bwd_plan(dshape, wshape, (1, 1), pad, (1, 1),
                            "float32")
    assert plan is not None and plan["block_n"] >= 1, plan
    gw = pk.conv_bwd_filter(x, g, wshape, pad, interpret=True)
    gd = pk.conv_bwd_input(g, w, dshape, pad, interpret=True)
    assert gw.dtype == jnp.float32 and gd.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(gd_ref),
                               rtol=1e-6, atol=1e-5)


def test_kernel_parity_bf16_f32_accumulation():
    # bf16 inputs accumulate in f32: each bf16*bf16 product is exact in
    # f32, so the kernel must agree with an all-f32 reference computed
    # from the SAME rounded values to f32-sum tolerance
    dshape, wshape, pad = (2, 8, 10, 10), (16, 8, 3, 3), (1, 1)
    x16, w16, g16, _, _ = _ref(dshape, wshape, pad, jnp.bfloat16)
    dn = jax.lax.conv_dimension_numbers(
        dshape, wshape, ("NCHW", "OIHW", "NCHW"))
    _, vjp = jax.vjp(
        lambda x, w: jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn),
        x16.astype(jnp.float32), w16.astype(jnp.float32))
    gd_ref, gw_ref = vjp(g16.astype(jnp.float32))
    gw = pk.conv_bwd_filter(x16, g16, wshape, pad, interpret=True)
    gd = pk.conv_bwd_input(g16, w16, dshape, pad, interpret=True)
    assert gw.dtype == jnp.float32 and gd.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(gd_ref),
                               rtol=1e-4, atol=1e-3)


def test_bf16_accumulation_order_bitwise_stable():
    # the grid order (N blocks, then taps) is fixed, so repeated runs
    # must agree BITWISE — not just to tolerance
    dshape, wshape, pad = (4, 8, 10, 10), (16, 8, 3, 3), (1, 1)
    x, w, g, _, _ = _ref(dshape, wshape, pad, jnp.bfloat16)
    gw_a = np.asarray(pk.conv_bwd_filter(x, g, wshape, pad, interpret=True))
    gw_b = np.asarray(pk.conv_bwd_filter(x, g, wshape, pad, interpret=True))
    assert gw_a.tobytes() == gw_b.tobytes()
    gd_a = np.asarray(pk.conv_bwd_input(g, w, dshape, pad, interpret=True))
    gd_b = np.asarray(pk.conv_bwd_input(g, w, dshape, pad, interpret=True))
    assert gd_a.tobytes() == gd_b.tobytes()


# ---------------------------------------------------------------------------
# dispatch table: tuned envelope + fallback
# ---------------------------------------------------------------------------

def test_envelope_rejections():
    ok = ((2, 8, 10, 10), (16, 8, 3, 3))
    assert pk.conv_bwd_plan(ok[0], ok[1], (1, 1), (1, 1), (1, 1),
                            "float32") is not None
    # stride, dilation, kernel-smaller-than-pad, unaligned channels,
    # grouped (C mismatch), f64 — all outside the tuned envelope
    assert pk.conv_bwd_plan(ok[0], ok[1], (2, 2), (1, 1), (1, 1),
                            "float32") is None
    assert pk.conv_bwd_plan(ok[0], ok[1], (1, 1), (1, 1), (2, 2),
                            "float32") is None
    assert pk.conv_bwd_plan(ok[0], (16, 8, 1, 1), (1, 1), (1, 1),
                            (1, 1), "float32") is None  # k=1 < p+1
    assert pk.conv_bwd_plan((2, 3, 10, 10), (16, 3, 3, 3), (1, 1),
                            (1, 1), (1, 1), "float32") is None
    assert pk.conv_bwd_plan(ok[0], (16, 4, 3, 3), (1, 1), (1, 1),
                            (1, 1), "float32") is None  # grouped
    assert pk.conv_bwd_plan(ok[0], ok[1], (1, 1), (1, 1), (1, 1),
                            "float64") is None
    # a shape whose block working set exceeds the VMEM budget
    assert pk.conv_bwd_plan((1, 256, 256, 256), (256, 256, 3, 3),
                            (1, 1), (1, 1), (1, 1), "float32") is None


def test_gate_requires_env(monkeypatch):
    z = jnp.zeros((2, 8, 10, 10), jnp.float32)
    zw = jnp.zeros((16, 8, 3, 3), jnp.float32)
    assert nnops._pallas_conv_plan(z, zw, (1, 1), (1, 1), (1, 1),
                                   1) is None  # flag unset: off
    monkeypatch.setenv("MXTPU_CONV_KERNEL", "pallas")
    assert nnops._pallas_conv_plan(z, zw, (1, 1), (1, 1), (1, 1),
                                   1) is not None
    assert nnops._pallas_conv_plan(z, zw, (2, 2), (1, 1), (1, 1),
                                   1) is None  # untuned: fallback
    monkeypatch.setenv("MXTPU_CONV_KERNEL", "xla")
    assert nnops._pallas_conv_plan(z, zw, (1, 1), (1, 1), (1, 1),
                                   1) is None


def _conv_net(stride=(1, 1), dilate=(1, 1), kernel=(3, 3), pad=(1, 1)):
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, name="conv", num_filter=16,
                             kernel=kernel, stride=stride, pad=pad,
                             dilate=dilate, no_bias=True)
    return mx.sym.sum(net)


def _levers(m, env):
    """``env`` set, and the pair (where ``env`` takes it) sent through the
    Pallas interpreter by the kernel layer's one test seam: off the TPU
    its own branch is XLA's gradient convs again."""
    for k, v in env.items():
        m.setenv(k, v)
    m.setattr(pk.common, "INTERPRET", True)


def _executor_grads(net, dshape, env, monkeypatch, seed=0):
    with monkeypatch.context() as m:
        _levers(m, env)
        ex = net.simple_bind(ctx=mx.cpu(), data=dshape)
        rng = np.random.RandomState(seed)
        ex.arg_dict["data"][:] = rng.randn(*dshape)
        ex.arg_dict["conv_weight"][:] = \
            rng.randn(*ex.arg_dict["conv_weight"].shape) * 0.1
        ex.forward(is_train=True)
        ex.backward()
        return {k: v.asnumpy().astype(np.float32)
                for k, v in ex.grad_dict.items()}


@pytest.mark.parametrize("case,kwargs", [
    ("tuned_3x3", dict()),
    ("stride2_fallback", dict(stride=(2, 2))),
    ("dilated_fallback", dict(dilate=(2, 2), pad=(2, 2))),
    ("valid_5x5", dict(kernel=(5, 5), pad=(2, 2))),
])
def test_executor_grads_on_vs_off(case, kwargs, monkeypatch):
    # NCHW executor path: gradients with the kernel flag on must match
    # the flag-off default — for tuned shapes (Pallas pair engaged) and
    # untuned stride/dilation shapes (automatic XLA fallback) alike
    net = _conv_net(**kwargs)
    dshape = (2, 8, 12, 12)
    off = _executor_grads(net, dshape, {}, monkeypatch)
    on = _executor_grads(net, dshape, {"MXTPU_CONV_KERNEL": "pallas"},
                         monkeypatch)
    for k in off:
        np.testing.assert_allclose(on[k], off[k], rtol=1e-5, atol=1e-5,
                                   err_msg="%s/%s" % (case, k))


def test_pallas_branch_beats_nhwc_and_taps_levers(monkeypatch):
    # with every backward lever set at once, the Pallas branch wins the
    # elif chain for in-envelope shapes — gradients still match default
    net = _conv_net()
    dshape = (2, 8, 12, 12)
    off = _executor_grads(net, dshape, {}, monkeypatch)
    on = _executor_grads(
        net, dshape,
        {"MXTPU_CONV_KERNEL": "pallas",
         "MXNET_CONV_BWD_LAYOUT": "NHWC",
         "MXNET_CONV_WGRAD": "taps"}, monkeypatch)
    for k in off:
        np.testing.assert_allclose(on[k], off[k], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# full fit: lenet-style convnet, kernels on vs off
# ---------------------------------------------------------------------------

def _lenet():
    # test_train_convergence.py's topology: the C=1 stem conv falls
    # back (channel alignment), conv2 (16 -> 32, 3x3, pad 1) sits
    # inside the tuned envelope — one fit exercises BOTH routes
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, name="conv1", num_filter=16,
                             kernel=(3, 3), pad=(1, 1))
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Convolution(net, name="conv2", num_filter=32,
                             kernel=(3, 3), pad=(1, 1))
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _digits():
    from sklearn.datasets import load_digits

    d = load_digits()
    X = (d.data / 16.0).astype(np.float32).reshape(-1, 1, 8, 8)
    y = d.target.astype(np.float32)
    rng = np.random.RandomState(0)
    perm = rng.permutation(len(X))
    return X[perm][:1000], y[perm][:1000]


def _fit_lenet(monkeypatch, env):
    with monkeypatch.context() as m:
        _levers(m, env)
        X, y = _digits()
        it = mx.io.NDArrayIter(X, y, batch_size=50, shuffle=True)
        np.random.seed(1)
        mx.random.seed(1)
        mod = mx.mod.Module(_lenet(), context=mx.cpu())
        mod.fit(it, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05,
                                  "momentum": 0.9, "wd": 1e-4},
                initializer=mx.initializer.Xavier(), num_epoch=10)
        it.reset()
        return dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]


def test_lenet_fit_convergence_kernel_on_vs_off(monkeypatch):
    acc_off = _fit_lenet(monkeypatch, {})
    pk.conv.conv_plan_cache.clear()
    acc_on = _fit_lenet(monkeypatch, {"MXTPU_CONV_KERNEL": "pallas"})
    # the kernel actually engaged for the body conv (the C=1 stem
    # fell back on channel alignment)
    plans = list(pk.conv.conv_plan_cache.values())
    assert any(p not in (None, "miss") for p in plans), plans
    assert acc_off > 0.9, acc_off
    assert acc_on > 0.9, acc_on
    # same data, same init, grads equal to f32 rounding: convergence
    # must match closely, not just directionally
    assert abs(acc_on - acc_off) < 0.05, (acc_on, acc_off)


# ---------------------------------------------------------------------------
# the op_costs record of a fused fit
# ---------------------------------------------------------------------------

def test_op_costs_record_emitted(tmp_path, monkeypatch):
    # the fit loop emits one op_costs record (the feed into
    # perf_doctor's kernel-candidates table)
    monkeypatch.setenv("MXTPU_ANATOMY_INTERVAL", "8")
    jl = str(tmp_path / "telemetry.jsonl")
    tm.enable(jsonl=jl)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.randn(256, 8).astype("f"),
                           rng.randint(0, 4, 256).astype("f"),
                           batch_size=8)
    mod = mx.mod.Module(net, context=FOUR_DEV)
    mod.fit(it, eval_metric=mx.metric.Accuracy(),
            optimizer="sgd", optimizer_params={"learning_rate": 0.05},
            kvstore="device", num_epoch=1,
            initializer=mx.init.Uniform(0.05))
    assert mod._fused_trainer is not None, "fused path did not engage"
    tm.flush()
    with open(jl) as f:
        recs = [r for r in map(json.loads, f) if r.get("type") == "op_costs"]
    assert recs, "no op_costs record"
    ops = recs[-1]["ops"]
    assert any(o["op"] == "FullyConnected" for o in ops), ops
    assert any(o["op"] == "SoftmaxOutput" for o in ops), ops
    for o in ops:
        assert o["flops"] > 0 and o["bytes"] > 0, o
