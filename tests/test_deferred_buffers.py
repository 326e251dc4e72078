"""A buffer nobody has read is not made, and bytes that carry no
information do not cross the host link (ISSUE 36): bind's arrays and an
optimizer's fresh state are born deferred (``nd.deferred_full``), the
executor group gets its weights when an executor first runs, and
``ShardedTrainStep.make_state`` makes the state tree on the mesh by one
program. ``device.h2d_bytes`` and ``device.const_bytes`` are the
measure: what a span allocated is the sum of the two.
"""
import pickle

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import telemetry as tm
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.train_step import ShardedTrainStep, _from_slab


@pytest.fixture(autouse=True)
def _isolate():
    tm.reset()
    tm.disable()
    yield
    tm.reset()
    tm.disable()


def _by_span(name):
    return {s["labels"]["under"]: s["value"]
            for s in tm.snapshot().get(name, {"streams": []})["streams"]}


def _net(dtype="float32"):
    """conv + BatchNorm + fc: weights an initializer draws, biases and
    gammas it sets to a constant, and two auxiliary states."""
    net = mx.sym.Variable("data", dtype=dtype)
    net = mx.sym.Convolution(net, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0")
    net = mx.sym.Activation(net, act_type="relu", name="relu0")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=4, name="fc0")
    return mx.sym.SoftmaxOutput(net, name="softmax")


DATA = [("data", (8, 3, 4, 4))]
LABEL = [("softmax_label", (8,))]
# name -> shape of _net()'s parameters and auxiliary states at DATA
PARAMS = {"conv0_weight": (4, 3, 3, 3), "conv0_bias": (4,),
          "bn0_gamma": (4,), "bn0_beta": (4,),
          "fc0_weight": (4, 64), "fc0_bias": (4,)}
AUX = {"bn0_moving_mean": (4,), "bn0_moving_var": (4,)}
DRAWN = ("conv0_weight", "fc0_weight")   # the rest are set to a constant


def _size(shapes, names=None):
    return sum(int(np.prod(s)) for n, s in shapes.items()
               if names is None or n in names)


def _is_deferred(arr):
    return isinstance(arr._buf, nd._Deferred)


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    return mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(*DATA[0][1]).astype("f"))],
        label=[mx.nd.array(rng.randint(0, 4, LABEL[0][1]).astype("f"))])


# -- the fused fit's set-up ---------------------------------------------------

@pytest.mark.parametrize("path", ["per_key", "flat"])
def test_fused_setup_sends_each_parameter_once_and_nothing_else(path):
    """Bind sends nothing and makes nothing; init_params draws on the
    host (Xavier: numpy's stream) and sends nothing; init_optimizer
    places each drawn weight once (the one crossing that carries
    information) and makes the momentum on the mesh. The constants an
    initializer declared (biases, gammas, moving statistics) nobody has
    read: place_params makes them on the mesh too (ISSUE 63), and the
    host arrays stay the records they were."""
    tm.enable()
    if path == "per_key":
        ctx = mx.cpu(1)
        mod = mx.mod.Module(_net(), context=ctx, mesh=make_mesh(
            dp=1, devices=[ctx.jax_device]))
    else:
        mod = mx.mod.Module(_net(), context=[mx.cpu(1), mx.cpu(2)])
    mod.bind(DATA, LABEL)
    assert _by_span("device.h2d_bytes") == {}
    assert _by_span("device.const_bytes") == {}
    exe = mod._exec_group.execs[0]
    assert all(_is_deferred(a) for a in exe.arg_arrays + exe.aux_arrays
               + [g for g in exe.grad_arrays if g is not None])

    mod.init_params(mx.init.Xavier())
    assert _by_span("device.h2d_bytes") == {}
    assert _by_span("device.const_bytes") == {}

    mod.init_optimizer(kvstore="device", optimizer="sgd", optimizer_params={
        "learning_rate": 0.1, "momentum": 0.9})
    trainer = mod._fused_trainer
    assert trainer is not None
    assert (trainer.flat_mode is not None) == (path == "flat")
    placed = 4 * _size(PARAMS, DRAWN)
    sent = _by_span("device.h2d_bytes")
    # the flat path's two loss-scaler scalars do not exist without AMP
    assert sent == {"module.init_optimizer": placed}
    declared = 4 * (_size(PARAMS) - _size(PARAMS, DRAWN) + _size(AUX))
    momentum = 4 * sum(int(np.prod(leaf.shape))
                       for leaf in jax.tree_util.tree_leaves(mod._fused_opt))
    assert momentum >= 4 * _size(PARAMS)   # the flat slabs are padded
    assert _by_span("device.const_bytes") == {
        "module.init_optimizer": declared + momentum}
    assert _by_span("device.drawn_bytes") == {}
    for n, arr in list(mod._arg_params.items()) + list(
            mod._aux_params.items()):
        assert _is_deferred(arr) == (n not in DRAWN)
        assert mod._fused_trainer._sharding_for(n) == (
            dict(mod._fused_params, **mod._fused_aux)[n].sharding)
    # and still no buffer in the executor group: the fused step owns them
    assert all(_is_deferred(exe.arg_dict[n]) and _is_deferred(exe.grad_dict[n])
               for n in PARAMS)

    mod.forward(_batch(), is_train=True)
    mod.update()
    assert np.isfinite(mod.get_outputs()[0].asnumpy()).all()
    sent = _by_span("device.h2d_bytes")
    sent.pop("-")                         # this test's own batch
    assert set(sent) == {"module.init_optimizer", "module.update"}
    assert sent["module.init_optimizer"] == placed


# -- make_state ---------------------------------------------------------------

def _trainer(optimizer, dtype="float32", devices=1, **kwargs):
    mesh = make_mesh(dp=devices, devices=jax.devices()[1:1 + devices])
    step = ShardedTrainStep(_net(dtype), mesh, optimizer=optimizer,
                            data_names=["data"],
                            label_names=["softmax_label"], **kwargs)
    rng = np.random.RandomState(0)
    params, aux = step.place_params(
        {n: rng.randn(*s).astype(dtype) for n, s in PARAMS.items()},
        {n: np.ones(s, dtype) for n, s in AUX.items()})
    return step, params


def _zeros(shape):
    return np.zeros(shape, np.float32)


# what the parent's make_state placed on the mesh, written out: name ->
# the state's tree over float32 zeros of the weight's shape, whatever
# the weight's own type
PER_KEY = {
    "sgd": (lambda: mx.optimizer.SGD(learning_rate=0.1),
            lambda shape: None),
    "sgd_momentum": (lambda: mx.optimizer.SGD(learning_rate=0.1,
                                              momentum=0.9), _zeros),
    "adam": (lambda: mx.optimizer.Adam(),
             lambda shape: (_zeros(shape), _zeros(shape))),
    # the kept copy is a copy of the stand-in's zeros, not of the weight
    "dcasgd": (lambda: mx.optimizer.DCASGD(learning_rate=0.1),
               lambda shape: (None, _zeros(shape))),
    "dcasgd_momentum": (lambda: mx.optimizer.DCASGD(learning_rate=0.1,
                                                    momentum=0.9),
                        lambda shape: (_zeros(shape), _zeros(shape))),
}


def _assert_same(got, want, sharding):
    if want is None:
        assert got is None
    elif isinstance(want, tuple):
        assert isinstance(got, tuple) and len(got) == len(want)
        for g, w in zip(got, want):
            _assert_same(g, w, sharding)
    else:
        assert isinstance(got, jax.Array)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.sharding == sharding
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(PER_KEY))
def test_make_state_is_the_parents_bit_for_bit(name, dtype):
    make, want_of = PER_KEY[name]
    tm.enable()
    step, params = _trainer(make(), dtype, flat_update=False)
    before = _by_span("device.h2d_bytes")
    state = step.make_state(params)
    crossed = _by_span("device.h2d_bytes")
    # no constant crossed; DCASGD's kept copy is a copy of a stand-in
    # that holds no buffer: the same record, made with the rest
    assert crossed == before
    assert list(state) == list(step.param_names)
    sharding = NamedSharding(step.mesh, P())
    for n in step.param_names:
        _assert_same(state[n], want_of(PARAMS[n]), sharding)


@pytest.mark.parametrize("name", ["sgd", "sgd_momentum", "adam"])
def test_flat_make_state_is_the_parents_bit_for_bit(name):
    make, want_of = PER_KEY[name]
    tm.enable()
    step, params = _trainer(make(), devices=2)
    assert step.flat_mode == "shard"
    before = _by_span("device.h2d_bytes")
    state = step.make_state(params)
    assert _by_span("device.h2d_bytes") == before
    plan = step._flat_plan
    sharding = NamedSharding(step.mesh, P("dp"))
    want_keys = [] if name == "sgd" else [
        step._flat_key(bi) for bi in range(len(plan.buckets))]
    assert list(state) == want_keys
    for bi, b in enumerate(plan.buckets):
        assert b.padded % 2 == 0 and b.padded >= b.size
        if want_keys:
            _assert_same(state[step._flat_key(bi)], want_of((b.padded,)),
                         sharding)
    assert sum(b.size for b in plan.buckets) == _size(PARAMS)


def test_amp_master_slabs_are_packed_on_the_mesh(monkeypatch):
    """The float32 masters are the placed parameters, packed where they
    are: the same values the host packing gives, and nothing fetched and
    sent back."""
    monkeypatch.setenv("MXTPU_AMP", "bf16")
    tm.enable()
    step, params = _trainer(mx.optimizer.SGD(learning_rate=0.1,
                                             momentum=0.9), devices=2)
    assert step.amp
    before = _by_span("device.h2d_bytes")
    state = step.make_state(params)
    after = _by_span("device.h2d_bytes")
    # the scaler's two float32 scalars are values, not constants
    assert sum(after.values()) - sum(before.values()) == 8
    host = step.build_amp_master_state(
        {n: np.asarray(p) for n, p in params.items()})
    for bi, b in enumerate(step._flat_plan.buckets):
        key = step._master_key(bi)
        assert state[key].sharding == host[key].sharding
        assert state[key].dtype == np.float32
        np.testing.assert_array_equal(np.asarray(state[key]),
                                      np.asarray(host[key]))
        for (_i, n, off, size, shape) in b.views:
            np.testing.assert_array_equal(
                _from_slab(np.asarray(state[key])[off:off + size], shape),
                np.asarray(params[n]))
        np.testing.assert_array_equal(np.asarray(state[key])[b.size:], 0)


def test_make_state_is_one_program(monkeypatch):
    """A program for the tree, not one per key or per shape."""
    calls = []
    real = nd._constants_program

    def counting(specs, shardings):
        calls.append(len(specs))
        return real(specs, shardings)

    counting.cache_clear = real.cache_clear    # place() drops its programs
    step, params = _trainer(mx.optimizer.Adam(), flat_update=False)
    monkeypatch.setattr(nd, "_constants_program", counting)
    step.make_state(params)
    assert calls == [2 * len(PARAMS)]


# -- the executor path --------------------------------------------------------

def test_executor_forward_after_init_params_sees_the_weights():
    """No optimizer, no fused step: the weights reach the executor group
    when its executor first runs, once."""
    tm.enable()
    mod = mx.mod.Module(_net(), context=mx.cpu(1))
    mod.bind(DATA, LABEL, for_training=False)
    mod.init_params(mx.init.Xavier())
    exe = mod._exec_group.execs[0]
    assert all(_is_deferred(exe.arg_dict[n]) for n in PARAMS)
    assert _by_span("device.h2d_bytes") == {}
    want, want_aux = mod.get_params()
    batch = _batch()
    mod.forward(batch, is_train=False)
    out = mod.get_outputs()[0].asnumpy()
    for n in PARAMS:
        np.testing.assert_array_equal(exe.arg_dict[n].asnumpy(),
                                      want[n].asnumpy())
    for n in AUX:
        np.testing.assert_array_equal(exe.aux_dict[n].asnumpy(),
                                      want_aux[n].asnumpy())
    assert np.abs(want["fc0_weight"].asnumpy()).max() > 0
    # a second module fed the same parameters up front agrees
    ref = mx.mod.Module(_net(), context=mx.cpu(1))
    ref.bind(DATA, LABEL, for_training=False)
    ref.set_params(want, want_aux)
    ref.forward(batch, is_train=False)
    np.testing.assert_array_equal(out, ref.get_outputs()[0].asnumpy())
    # and a second forward sends only its batch
    sent = sum(_by_span("device.h2d_bytes").values())
    mod.forward(batch, is_train=False)
    again = sum(_by_span("device.h2d_bytes").values()) - sent
    assert again <= 4 * (int(np.prod(DATA[0][1])) + LABEL[0][1][0])


def test_set_params_between_forwards_reaches_the_executors():
    mod = mx.mod.Module(_net(), context=mx.cpu(1))
    mod.bind(DATA, LABEL, for_training=False)
    mod.init_params(mx.init.Xavier())
    batch = _batch()
    mod.forward(batch, is_train=False)
    first = mod.get_outputs()[0].asnumpy()
    arg, aux = mod.get_params()
    arg = {n: v * 2 for n, v in arg.items()}
    mod.set_params(arg, aux)
    mod.forward(batch, is_train=False)
    exe = mod._exec_group.execs[0]
    np.testing.assert_array_equal(exe.arg_dict["fc0_weight"].asnumpy(),
                                  arg["fc0_weight"].asnumpy())
    assert not np.array_equal(first, mod.get_outputs()[0].asnumpy())


def test_reshape_before_any_forward_keeps_the_initialised_weights():
    """The executor group holds nothing yet: the host's are the truth
    and must not be overwritten with the unmade zeros."""
    mod = mx.mod.Module(_net(), context=mx.cpu(1))
    mod.bind(DATA, LABEL, for_training=False)
    mod.init_params(mx.init.Xavier())
    want = {n: v.asnumpy() for n, v in mod.get_params()[0].items()}
    mod.reshape([("data", (4, 3, 4, 4))], [("softmax_label", (4,))])
    got = mod.get_params()[0]
    for n in PARAMS:
        np.testing.assert_array_equal(got[n].asnumpy(), want[n])
        np.testing.assert_array_equal(
            mod._exec_group.execs[0].arg_dict[n].asnumpy(), want[n])


@pytest.mark.parametrize("req", ["add", "write"])
def test_gradient_accumulates_from_zero(req):
    x = mx.sym.Variable("x")
    exe = (x * x).simple_bind(mx.cpu(1), grad_req=req, x=(3,))
    assert _is_deferred(exe.grad_dict["x"])
    exe.arg_dict["x"][:] = np.array([1.0, 2.0, 3.0], "f")
    for _ in range(2):
        exe.forward(is_train=True)
        exe.backward(out_grads=mx.nd.ones((3,), ctx=mx.cpu(1)))
    once = np.array([2.0, 4.0, 6.0], "f")
    np.testing.assert_array_equal(exe.grad_dict["x"].asnumpy(),
                                  once * (2 if req == "add" else 1))


# -- a never-read deferred array ---------------------------------------------

def _fresh(value=0, dtype="float32"):
    arr = nd.deferred_full((2, 3), value, ctx=mx.cpu(1), dtype=dtype)
    assert _is_deferred(arr)
    return arr


def _op_describe(arr):
    assert arr.shape == (2, 3) and arr.size == 6 and arr.ndim == 2
    assert arr.dtype == np.float32 and arr.context == mx.cpu(1)
    assert len(arr) == 2 and "2x3" in repr(arr)
    arr.wait_to_read()
    return None   # still no buffer


def _op_asnumpy(arr):
    return arr.asnumpy()


def _op_arithmetic(arr):
    return ((arr + 1) * 2 - arr).asnumpy() - 2


def _op_inplace(arr):
    arr += 0
    return arr.asnumpy()


def _op_copyto_from(arr):
    out = mx.nd.ones((2, 3), ctx=mx.cpu(2))
    arr.copyto(out)
    assert out.context == mx.cpu(2)
    return out.asnumpy()


def _op_copyto_into(arr):
    src = mx.nd.array(np.arange(6, dtype="f").reshape(2, 3))
    src.copyto(arr)
    assert arr.context == mx.cpu(1) and not _is_deferred(arr)
    got = arr.asnumpy()
    np.testing.assert_array_equal(got, src.asnumpy())
    return got - src.asnumpy()


def _op_slice_assign(arr):
    arr[0:1] = 5.0
    got = arr.asnumpy().copy()
    np.testing.assert_array_equal(got[0], 5.0)
    got[0] = 0
    return got


def _op_whole_assign(arr):
    arr[:] = np.full((2, 3), 7.0, "f")
    assert arr.context == mx.cpu(1)
    return arr.asnumpy() - 7


def _op_whole_scalar(arr):
    arr[:] = 3.0
    assert _is_deferred(arr)     # a constant still: nothing to make
    return arr.asnumpy() - 3


def _op_index(arr):
    return np.stack([arr[0].asnumpy(), arr[1].asnumpy()])


def _op_pickle(arr):
    back = pickle.loads(pickle.dumps(arr))
    assert back.shape == (2, 3) and back._engine_dep is None
    return back.asnumpy()


def _op_as_in_context(arr):
    assert arr.as_in_context(mx.cpu(1)) is arr
    return arr.as_in_context(mx.cpu(2)).asnumpy()


OPS = [_op_describe, _op_asnumpy, _op_arithmetic, _op_inplace,
       _op_copyto_from, _op_copyto_into, _op_slice_assign,
       _op_whole_assign, _op_whole_scalar, _op_index, _op_pickle,
       _op_as_in_context]


@pytest.mark.parametrize("op", OPS, ids=[f.__name__[4:] for f in OPS])
def test_a_never_read_array_is_the_constant_it_was_declared_as(op):
    """Every reader finds zeros (each op returns what should be zeros),
    on the array's own device."""
    arr = _fresh()
    got = op(arr)
    if got is None:
        assert _is_deferred(arr)
    else:
        assert got.shape == (2, 3) and got.dtype == np.float32
        np.testing.assert_array_equal(got, 0)
        assert arr.context == mx.cpu(1)
        assert arr._placement == mx.cpu(1).jax_device


@pytest.mark.parametrize("value, dtype", [(1, "float32"), (2.5, "float16"),
                                          (7, "int32"), (0, "bfloat16")])
def test_a_deferred_constant_has_its_value_and_type(value, dtype):
    tm.enable()
    arr = _fresh(value, dtype)
    assert _by_span("device.const_bytes") == {}
    got = arr.asnumpy()
    assert got.dtype == mx.base.np_dtype(dtype)
    np.testing.assert_array_equal(got.astype("f"), np.float32(value))
    assert _by_span("device.const_bytes") == {"-": 6 * got.dtype.itemsize}
    assert _by_span("device.h2d_bytes") == {}
    arr.asnumpy()                                       # made once
    assert _by_span("device.const_bytes") == {"-": 6 * got.dtype.itemsize}


def test_creation_functions_keep_the_cpu_context_as_it_was():
    for make in (lambda: mx.nd.zeros((2, 2)), lambda: mx.nd.ones((2, 2)),
                 lambda: mx.nd.full((2, 2), 3.0), lambda: mx.nd.empty((2, 2)),
                 lambda: mx.nd.zeros((2, 2), ctx=mx.cpu(1))):
        assert not _is_deferred(make())


class _Accelerator:
    """A context that says it is not the host's (no such device here:
    ``mx.tpu()`` raises without one); its buffers land on a cpu device."""
    device_type = "tpu"
    jax_device = mx.cpu(2).jax_device


@pytest.mark.parametrize("make, value", [
    (lambda c: mx.nd.zeros((2, 3), ctx=c), 0),
    (lambda c: mx.nd.ones((2, 3), ctx=c, dtype="float16"), 1),
    (lambda c: mx.nd.full((2, 3), 2.5, ctx=c), 2.5),
    (lambda c: mx.nd.empty((2, 3), ctx=c), 0)])
def test_creation_functions_cross_nothing_to_an_accelerator(make, value):
    tm.enable()
    arr = make(_Accelerator())
    assert _is_deferred(arr) and arr.context == mx.cpu(2)
    np.testing.assert_array_equal(arr.asnumpy().astype("f"), value)
    assert _by_span("device.h2d_bytes") == {}
    assert _by_span("device.const_bytes") == {
        "-": 6 * np.dtype(arr.dtype).itemsize}


def test_a_dropped_buffer_has_nothing_to_read():
    arr = mx.nd.ones((2, 3), ctx=mx.cpu(1))
    arr._drop_buffer()
    assert arr.shape == (2, 3) and arr.context == mx.cpu(1)
    with pytest.raises(mx.MXNetError, match="fused step"):
        arr.asnumpy()
    mx.nd.ones((2, 3)).copyto(arr)
    np.testing.assert_array_equal(arr.asnumpy(), 1)


# -- sharing, and resuming ----------------------------------------------------

def _bucket_sym(key):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    # the bucket only changes the batch's rows
    return mx.sym.SoftmaxOutput(fc, name="softmax"), ("data",), (
        "softmax_label",)


def test_bucketing_shares_the_arrays_and_what_is_known_of_them():
    mod = mx.mod.BucketingModule(_bucket_sym, default_bucket_key=8,
                                 context=mx.cpu(1))
    mod.bind([("data", (8, 5))], [("softmax_label", (8,))])
    mod.init_params(mx.init.Uniform(0.5))
    want = mod.get_params()[0]["fc_weight"].asnumpy()
    assert np.abs(want).max() > 0
    first = mod._curr_module
    mod.switch_bucket(4, [("data", (4, 5))], [("softmax_label", (4,))])
    second = mod._curr_module
    assert second is not first
    for n in ("fc_weight", "fc_bias"):
        assert (second._exec_group.execs[0].arg_dict[n]
                is first._exec_group.execs[0].arg_dict[n])
    assert second._exec_group.weights is first._exec_group.weights
    assert second._exec_params_stale      # nobody has run an executor
    rng = np.random.RandomState(0)
    x = rng.randn(4, 5).astype("f")
    mod.forward(mx.io.DataBatch(
        data=[mx.nd.array(x)], label=[mx.nd.array(np.zeros(4, "f"))],
        bucket_key=4, provide_data=[("data", (4, 5))],
        provide_label=[("softmax_label", (4,))]), is_train=False)
    assert not first._exec_params_stale   # filled for both at once
    np.testing.assert_array_equal(
        first._exec_group.execs[0].arg_dict["fc_weight"].asnumpy(), want)
    logits = x @ want.T + mod.get_params()[0]["fc_bias"].asnumpy()
    e = np.exp(logits - logits.max(1, keepdims=True))
    np.testing.assert_allclose(mod.get_outputs()[0].asnumpy(),
                               e / e.sum(1, keepdims=True), rtol=1e-5)


def test_resume_places_the_saved_state_over_the_made_one(tmp_path):
    def fused():
        ctx = mx.cpu(1)
        mod = mx.mod.Module(_net(), context=ctx, mesh=make_mesh(
            dp=1, devices=[ctx.jax_device]))
        mod.bind(DATA, LABEL)
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(kvstore="device", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        return mod

    mod = fused()
    for seed in range(2):
        mod.forward(_batch(seed), is_train=True)
        mod.update()
    saved = {n: np.asarray(s) for n, s in mod._fused_opt.items()}
    assert np.abs(saved["fc0_weight"]).max() > 0
    fname = str(tmp_path / "opt.states")
    mod.save_optimizer_states(fname)

    again = fused()
    assert all(not np.asarray(s).any() for s in again._fused_opt.values())
    again.load_optimizer_states(fname)
    assert again._fused_t == 2
    for n, s in again._fused_opt.items():
        assert s.dtype == np.float32
        assert s.sharding == NamedSharding(again._fused_trainer.mesh, P())
        np.testing.assert_array_equal(np.asarray(s), saved[n])


# -- a draw nobody has read (ISSUE 63) ----------------------------------------
# ``Normal`` takes its key from ``mx.random``'s stream when it is called
# and leaves a record; the value is made where its first reader is: the
# fused step's mesh (``ndarray.place``), else the array's own device.

SIGMA = 0.05


def _mixed_net(dtype="float32"):
    """``Normal`` weights (conv0, fc0), constants (biases, gamma, beta,
    the moving statistics) and one weight that states a numpy-stream
    initializer of its own."""
    net = mx.sym.Variable("data", dtype=dtype)
    net = mx.sym.Convolution(net, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0")
    net = mx.sym.Activation(net, act_type="relu", name="relu0")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=8, name="fc0")
    net = mx.sym.FullyConnected(
        net, num_hidden=4, name="fc1", weight=mx.sym.Variable(
            "fc1_weight", init=mx.init.Uniform(0.1)))
    return mx.sym.SoftmaxOutput(net, name="softmax")


MIXED = dict(PARAMS, fc0_weight=(8, 64), fc0_bias=(8,), fc1_weight=(4, 8),
             fc1_bias=(4,))
NORMAL = ("conv0_weight", "fc0_weight")      # in the order they are drawn
NUMPY = ("fc1_weight",)


def _eager_normal(key, shape, dtype, device):
    """What the parent's ``Normal._init_weight`` wrote: three eager
    programs on the array's device."""
    import jax.numpy as jnp

    with jax.default_device(device):
        return np.asarray(jnp.asarray(
            SIGMA * jax.random.normal(key, shape, jnp.float32),
            dtype=mx.base.np_dtype(dtype)))


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint8)


def _mixed_module(mesh_devices=(1,), dtype="float32", seed=11, specs=None):
    ctx = mx.cpu(mesh_devices[0])
    mesh = make_mesh(dp=len(mesh_devices),
                     devices=[jax.devices()[i] for i in mesh_devices])
    mod = mx.mod.Module(_mixed_net(dtype), context=ctx, mesh=mesh,
                        param_specs=specs)
    mod.bind([("data", (8, 3, 4, 4))], LABEL)
    mx.random.seed(seed)
    np.random.seed(seed)
    mod.init_params(mx.init.Normal(SIGMA))
    return mod


def _fuse(mod):
    mod.init_optimizer(kvstore="device", optimizer="sgd", optimizer_params={
        "learning_rate": 0.1, "momentum": 0.9})
    assert mod._fused_trainer is not None
    return mod


def _want_normals(seed, dtype="float32", device=None):
    """name -> the parent's eager value at ``seed``, and the stream's
    state after the last of them."""
    mx.random.seed(seed)
    want = {n: _eager_normal(mx.random.next_key(), MIXED[n], dtype,
                             device or mx.cpu(0).jax_device)
            for n in NORMAL}
    return want, mx.random.get_state()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
@pytest.mark.parametrize("born", ["deferred", "buffer"])
def test_a_normal_read_on_the_host_is_the_eager_draw_bit_for_bit(born, dtype):
    shape = (33, 17)
    if born == "deferred":
        arr = nd.deferred_full(shape, 0, ctx=mx.cpu(1), dtype=dtype)
    else:
        arr = mx.nd.ones(shape, ctx=mx.cpu(1), dtype=dtype)
    tm.enable()
    mx.random.seed(7)
    mx.init.Normal(SIGMA)("x_weight", arr)
    after = mx.random.get_state()
    # an array that held a buffer is written at once, as it was
    assert _is_deferred(arr) == (born == "deferred")
    nbytes = 33 * 17 * np.dtype(arr.dtype).itemsize
    assert _by_span("device.drawn_bytes") == (
        {} if born == "deferred" else {"-": nbytes})
    got = arr.asnumpy()
    assert not _is_deferred(arr) and arr.context == mx.cpu(1)
    mx.random.seed(7)
    want = _eager_normal(mx.random.next_key(), shape, dtype,
                         mx.cpu(1).jax_device)
    np.testing.assert_array_equal(mx.random.get_state(), after)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))
    arr.asnumpy()                                        # made once
    assert _by_span("device.drawn_bytes") == {"-": nbytes}
    streams = tm.snapshot()["device.drawn_bytes"]["streams"]
    assert [s["labels"]["host"] for s in streams] == ["1"]  # a cpu device
    assert _by_span("device.h2d_bytes") == {}
    assert _by_span("device.const_bytes") == {}


def test_init_params_leaves_the_stream_where_the_parent_left_it():
    """One key a ``Normal`` weight, in the symbol's order; constants and
    numpy-stream initializers take none, and nothing is made."""
    tm.enable()
    mod = _mixed_module()
    want, state = _want_normals(11)
    np.testing.assert_array_equal(mx.random.get_state(), state)
    # numpy's stream too: the one Uniform weight, drawn eagerly
    np.random.seed(11)
    np.testing.assert_array_equal(
        mod._arg_params["fc1_weight"].asnumpy(),
        np.random.uniform(-0.1, 0.1, (4, 8)).astype("f"))
    assert _by_span("device.drawn_bytes") == {}
    assert _by_span("device.const_bytes") == {}
    for n, arr in mod._arg_params.items():
        assert _is_deferred(arr) == (n not in NUMPY)
    # read on the host (no fused step): the parent's values
    got, _ = mod.get_params()
    for n in NORMAL:
        np.testing.assert_array_equal(_bits(got[n].asnumpy()),
                                      _bits(want[n]))
    assert _by_span("device.drawn_bytes") == {
        "-": 4 * _size(MIXED, NORMAL)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_fused_fit_makes_its_parameters_on_the_mesh(dtype):
    """Nothing reads a parameter between init_params and the placement:
    every draw is made once, under its sharding on the mesh; the only
    bytes that cross are the numpy-stream weight's; the host arrays give
    their records up and get_params reads the step's copy."""
    tm.enable()
    item = np.dtype(mx.base.np_dtype(dtype)).itemsize
    mod = _fuse(_mixed_module(dtype=dtype))
    drawn = item * _size(MIXED, NORMAL)
    assert _by_span("device.drawn_bytes") == {"module.init_optimizer": drawn}
    # a fit places once: no executable of the placement stays loaded
    assert nd._draw_program.cache_info().currsize == 0
    assert _by_span("device.h2d_bytes") == {
        "module.init_optimizer": item * _size(MIXED, NUMPY)}
    for n in NORMAL:
        arr = mod._arg_params[n]
        assert _is_deferred(arr) and arr._buf.value is None
        with pytest.raises(mx.MXNetError, match="fused step"):
            arr.asnumpy()
        assert mod._fused_params[n].sharding == (
            mod._fused_trainer._sharding_for(n))
    # the mesh is a cpu device here, so the step holds the parent's bits
    want, _ = _want_normals(11, dtype, mx.cpu(1).jax_device)
    held = {n: np.asarray(v) for n, v in mod._fused_params.items()}
    for n in NORMAL:
        np.testing.assert_array_equal(_bits(held[n]), _bits(want[n]))
    # the kvstore's copy is the record, not 2 x the parameters
    assert all(_is_deferred(mod._kvstore._store[i])
               for i, n in enumerate(mod._param_names) if n in NORMAL)
    assert mod._params_dirty
    got, got_aux = mod.get_params()
    for n in MIXED:
        np.testing.assert_array_equal(_bits(got[n].asnumpy()),
                                      _bits(held[n]))
    np.testing.assert_array_equal(got_aux["bn0_moving_var"].asnumpy(), 1)
    # drawn once: reading the step's copy draws nothing
    assert sum(_by_span("device.drawn_bytes").values()) == drawn

    batch = _batch()
    batch.data[0] = batch.data[0].astype(dtype)
    mod.forward(batch, is_train=True)
    mod.update()
    assert np.isfinite(mod.get_outputs()[0].asnumpy().astype("f")).all()
    after = {n: np.asarray(v) for n, v in mod._fused_params.items()}
    assert any(not np.array_equal(after[n], held[n]) for n in NORMAL)
    got, _ = mod.get_params()
    for n in MIXED:
        np.testing.assert_array_equal(_bits(got[n].asnumpy()),
                                      _bits(after[n]))
    assert sum(_by_span("device.drawn_bytes").values()) == drawn


def test_fit_draws_every_normal_once_and_none_before_the_placement():
    """``Module.fit`` end to end: a single draw made for a reader other
    than the step would show as bytes beyond the parameters'."""
    tm.enable()
    ctx = mx.cpu(1)
    mod = mx.mod.Module(_mixed_net(), context=ctx, mesh=make_mesh(
        dp=1, devices=[ctx.jax_device]))
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.randn(16, 3, 4, 4).astype("f"),
                           rng.randint(0, 4, (16,)).astype("f"), batch_size=8)
    mx.random.seed(3)
    np.random.seed(3)
    mod.fit(it, num_epoch=1, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Normal(SIGMA))
    assert mod._fused_trainer is not None
    assert _by_span("device.drawn_bytes") == {
        "module.init_optimizer": 4 * _size(MIXED, NORMAL)}
    got, _ = mod.get_params()
    want, _ = _want_normals(3)
    for n in NORMAL:
        assert np.isfinite(got[n].asnumpy()).all()
        assert not np.array_equal(got[n].asnumpy(), want[n])   # trained


def _read_get_params(mod, tmp_path):
    return {n: v.asnumpy() for n, v in mod.get_params()[0].items()}


def _read_save_checkpoint(mod, tmp_path):
    prefix = str(tmp_path / "early")
    mod.save_checkpoint(prefix, 0)
    _, arg, _ = mx.model.load_checkpoint(prefix, 0)
    return {n: v.asnumpy() for n, v in arg.items()}


@pytest.mark.parametrize("read", [_read_get_params, _read_save_checkpoint],
                         ids=["get_params", "save_checkpoint"])
def test_a_read_before_init_optimizer_takes_the_host_path(read, tmp_path):
    """The reader made the values on the host: those bytes are what the
    step receives, over the link, and nothing is drawn a second time."""
    tm.enable()
    mod = _mixed_module()
    seen = read(mod, tmp_path)
    drawn = 4 * _size(MIXED, NORMAL)
    assert _by_span("device.drawn_bytes") == {"-": drawn}
    want, _ = _want_normals(11)
    for n in NORMAL:
        np.testing.assert_array_equal(_bits(seen[n]), _bits(want[n]))
    _fuse(mod)
    assert _by_span("device.drawn_bytes") == {"-": drawn}
    assert _by_span("device.h2d_bytes")["module.init_optimizer"] >= drawn
    for n in MIXED:
        np.testing.assert_array_equal(
            _bits(np.asarray(mod._fused_params[n])), _bits(seen[n]))
        assert not _is_deferred(mod._arg_params[n])
    got, _ = mod.get_params()
    for n in MIXED:
        np.testing.assert_array_equal(_bits(got[n].asnumpy()),
                                      _bits(seen[n]))


@pytest.mark.parametrize("devices, spec", [
    ((1, 2), P("dp")), ((1, 2, 3, 4), P("dp")), ((1, 2, 3, 4), P(None, "dp")),
    ((2, 3), P())], ids=["rows2", "rows4", "cols4", "replicated2"])
def test_a_sharded_spec_gives_the_single_device_values(devices, spec):
    tm.enable()
    one = _fuse(_mixed_module())
    want = {n: np.asarray(one._fused_params[n]) for n in MIXED}
    tm.reset()
    mod = _fuse(_mixed_module(devices, specs={"fc0_weight": spec}))
    held = mod._fused_params["fc0_weight"]
    assert held.sharding == NamedSharding(mod._fused_trainer.mesh, spec)
    assert len(held.sharding.device_set) == len(devices)
    if spec != P():
        assert held.addressable_shards[0].data.size < held.size
    for n in MIXED:
        np.testing.assert_array_equal(
            _bits(np.asarray(mod._fused_params[n])), _bits(want[n]))
    # counted once however many devices hold a part of it
    assert _by_span("device.drawn_bytes") == {
        "module.init_optimizer": 4 * _size(MIXED, NORMAL)}
    got, _ = mod.get_params()
    np.testing.assert_array_equal(_bits(got["fc0_weight"].asnumpy()),
                                  _bits(want["fc0_weight"]))


def _drawn(dtype="float32", ctx=None):
    arr = nd.deferred_full((5, 6), 0, ctx=ctx or mx.cpu(1), dtype=dtype)
    mx.random.seed(21)
    mx.init.Normal(SIGMA)("x_weight", arr)
    mx.random.seed(21)
    want = _eager_normal(mx.random.next_key(), (5, 6), dtype,
                         mx.cpu(1).jax_device)
    assert _is_deferred(arr)
    return arr, want


def _draw_copy(arr, want):
    twin = arr.copy()
    assert _is_deferred(twin) and _is_deferred(arr)    # the same record
    assert twin.context == mx.cpu(1)
    return twin


def _draw_copyto_unread(arr, want):
    out = nd.deferred_full((5, 6), 0, ctx=mx.cpu(2))
    assert arr.copyto(out) is out
    assert _is_deferred(out) and _is_deferred(arr)
    assert out.context == mx.cpu(2)
    assert _by_span("device.h2d_bytes") == {}
    assert _by_span("device.drawn_bytes") == {}
    np.testing.assert_array_equal(_bits(arr.asnumpy()), _bits(want))
    return out


def _draw_copyto_dropped(arr, want):
    out = mx.nd.ones((5, 6), ctx=mx.cpu(2))
    out._drop_buffer()                      # an executor's released weight
    arr.copyto(out)
    assert _is_deferred(out) and out.context == mx.cpu(2)
    return out


def _draw_copyto_buffer(arr, want):
    out = mx.nd.ones((5, 6), ctx=mx.cpu(2))
    arr.copyto(out)
    assert not _is_deferred(out) and not _is_deferred(arr)   # a read
    assert out.context == mx.cpu(2)
    return out


def _draw_copyto_context(arr, want):
    return arr.copyto(mx.cpu(3))


def _draw_pickle(arr, want):
    back = pickle.loads(pickle.dumps(arr))
    assert back.shape == (5, 6) and not _is_deferred(back)
    return back


def _draw_overwritten(arr, want):
    arr[:] = 2.0                            # never made: a constant again
    assert _is_deferred(arr) and arr._buf.value == 2.0
    np.testing.assert_array_equal(arr.asnumpy(), 2.0)
    assert _by_span("device.drawn_bytes") == {}
    return None


def _draw_dropped(arr, want):
    arr._drop_buffer()
    assert arr.shape == (5, 6) and arr.context == mx.cpu(1)
    with pytest.raises(mx.MXNetError, match="fused step"):
        arr.asnumpy()
    with pytest.raises(mx.MXNetError, match="fused step"):
        arr.copy().asnumpy()
    assert _by_span("device.drawn_bytes") == {}
    return None


def _draw_arithmetic(arr, want):
    return (arr * 1)


DRAW_OPS = [_draw_copy, _draw_copyto_unread, _draw_copyto_dropped,
            _draw_copyto_buffer, _draw_copyto_context, _draw_pickle,
            _draw_overwritten, _draw_dropped, _draw_arithmetic]


@pytest.mark.parametrize("op", DRAW_OPS,
                         ids=[f.__name__[6:] for f in DRAW_OPS])
def test_an_array_holding_a_draw_reads_as_the_draw_everywhere(op):
    tm.enable()
    arr, want = _drawn()
    out = op(arr, want)
    if out is not None:
        np.testing.assert_array_equal(_bits(out.asnumpy()), _bits(want))
        np.testing.assert_array_equal(_bits(arr.asnumpy()), _bits(want))


def test_place_puts_from_a_mesh_device_without_the_host(monkeypatch):
    """What already lies on a device of the sharding's is put from
    there, a fresh buffer (the step donates its own); what lies
    elsewhere goes by way of the host as it did."""
    devs = jax.devices()
    sharding = NamedSharding(make_mesh(dp=2, devices=devs[1:3]), P())
    on_mesh = mx.nd.array(np.arange(6, dtype="f").reshape(2, 3),
                          ctx=mx.cpu(1))
    off_mesh = mx.nd.array(np.arange(6, dtype="f").reshape(2, 3) + 1,
                           ctx=mx.cpu(4))
    put, real = [], jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda x, *r, **k: (
        put.append(type(x).__module__.split(".")[0]), real(x, *r, **k))[1])
    a, b, c = nd.place([on_mesh, off_mesh, np.ones((2, 3), "f")],
                       [sharding] * 3)
    monkeypatch.undo()
    assert put == ["jaxlib", "numpy", "numpy"]
    for got, want in ((a, on_mesh.asnumpy()), (b, off_mesh.asnumpy()),
                      (c, np.ones((2, 3), "f"))):
        assert got.sharding == sharding
        np.testing.assert_array_equal(np.asarray(got), want)
    a.delete()                              # as a donation would
    np.testing.assert_array_equal(on_mesh.asnumpy(),
                                  np.arange(6, dtype="f").reshape(2, 3))


def test_bucketing_and_borrowers_read_the_owners_fused_state():
    """The host arrays of a fused owner hold nothing: whoever shares
    them reads the step's copy."""
    def sym_gen(key):
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        return mx.sym.SoftmaxOutput(fc, name="softmax"), ("data",), (
            "softmax_label",)

    tm.enable()
    ctx = mx.cpu(1)
    mod = mx.mod.BucketingModule(
        sym_gen, default_bucket_key=8, context=ctx)
    mod.bind([("data", (8, 5))], [("softmax_label", (8,))])
    mx.random.seed(2)
    mod.init_params(mx.init.Normal(SIGMA))
    mod.switch_bucket(4, [("data", (4, 5))], [("softmax_label", (4,))])
    mod.switch_bucket(8, [("data", (8, 5))], [("softmax_label", (8,))])
    for m in mod._buckets.values():
        m._mesh = make_mesh(dp=1, devices=[ctx.jax_device])
    mod.init_optimizer(kvstore="device", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    owner = mod._curr_module
    assert owner._fused_trainer is not None
    assert _is_deferred(owner._arg_params["fc_weight"])
    # every parameter a draw or a constant: nothing crossed, and the
    # counter says 0 rather than nothing
    assert _by_span("device.h2d_bytes") == {"module.init_optimizer": 0}
    assert _by_span("device.drawn_bytes") == {
        "module.init_optimizer": 4 * 4 * 5}
    mx.random.seed(2)
    want = _eager_normal(mx.random.next_key(), (4, 5), "float32",
                         ctx.jax_device)
    held = np.asarray(owner._fused_owner._fused_params["fc_weight"])
    np.testing.assert_array_equal(_bits(held), _bits(want))
    borrower = mod._buckets[4]
    got = borrower.get_params()[0]["fc_weight"].asnumpy()
    np.testing.assert_array_equal(_bits(got), _bits(want))
    got = mod.get_params()[0]["fc_weight"].asnumpy()
    np.testing.assert_array_equal(_bits(got), _bits(want))
