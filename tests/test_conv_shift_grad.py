"""An input BatchNorm's beta takes its gradient through the convolution
that reads it (``executor._shift_grad_plan`` finds the pair in the graph,
``ops/nn.py::_carry_shift_grad`` takes channel c's as ``<sum_n g,
conv(e_c, w)>``, one forward convolution at batch ``C``): of the
convolution's data gradient of the whole batch only ``dbeta = sum(dy)`` had
a reader, and a convolution is linear in its data and the same map for every
sample.

Held here: the gradients against ``jax.grad`` of the plain composition
``conv(cast(batch_norm(x)))`` written in ``jax`` alone; the forward and the
moving statistics against the program that does not take the form, to the
bit; every bypass (counter at 0, the parent's program but for names); the
five levers; and the lowered step of three models through
``ShardedTrainStep``. Host only.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor, telemetry
from mxnet_tpu.ops import kernels, nn
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.train_step import ShardedTrainStep

from test_conv_pass_scopes import PARENT_FORM, stripped

EPS = 2e-5
# spatial dims, channels, filters, kernel, stride, pad, dilate, groups
CASES = {
    "2d_k7_s2_p3_stem": (2, 3, 8, (7, 7), (2, 2), (3, 3), (1, 1), 1),
    "2d_k3_s1_p0": (2, 3, 8, (3, 3), (1, 1), (0, 0), (1, 1), 1),
    "2d_k3_s2_p3": (2, 4, 8, (3, 3), (2, 2), (3, 3), (1, 1), 1),
    "2d_k1_s1_p0": (2, 4, 8, (1, 1), (1, 1), (0, 0), (1, 1), 1),
    "2d_k1_s2_p0": (2, 4, 8, (1, 1), (2, 2), (0, 0), (1, 1), 1),
    "2d_k3_groups2": (2, 4, 8, (3, 3), (1, 1), (0, 0), (1, 1), 2),
    "2d_k7_s2_groups2": (2, 4, 8, (7, 7), (2, 2), (3, 3), (1, 1), 2),
    "2d_k3_dilate2": (2, 3, 8, (3, 3), (1, 1), (3, 3), (2, 2), 1),
    "2d_k3_s2_dilate2": (2, 3, 8, (3, 3), (2, 2), (0, 0), (2, 2), 1),
    "1d_k7_s2_p3": (1, 3, 8, (7,), (2,), (3,), (1,), 1),
    "1d_k3_s1_p0_groups2": (1, 4, 8, (3,), (1,), (0,), (1,), 2),
    "1d_k3_dilate2": (1, 3, 8, (3,), (1,), (0,), (2,), 1),
    # wide enough for the Pallas pair (a multiple of 8 channels)
    "2d_k3_p1_c8": (2, 8, 16, (3, 3), (1, 1), (1, 1), (1, 1), 1),
}
EXTENT = {1: 33, 2: 18}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
# float32: summation order; bfloat16: the plain composition rounds its data
# gradient to bf16 before it sums it, the form sums float32 products
TOL = {"f32": 2e-5, "bf16": 2e-2}
BATCH = 10


@pytest.fixture
def lowerings():
    """() -> {node: count} of ``conv.shift_grad_lowerings`` since the test
    began."""
    telemetry.reset()
    telemetry.enable()

    def by_node():
        dump = telemetry.REGISTRY.snapshot().get(
            "conv.shift_grad_lowerings", {})
        return {s["labels"]["node"]: s["value"]
                for s in dump.get("streams", [])}
    try:
        yield by_node
    finally:
        telemetry.disable()
        telemetry.reset()


def stem(case, dtype, cast):
    """data -> BatchNorm ``bn_data`` -> [Cast] -> Convolution ``c``."""
    nd, cin, nf, kernel, stride, pad, dilate, groups = CASES[case]
    body = mx.sym.BatchNorm(mx.sym.Variable("data"), fix_gamma=True, eps=EPS,
                            name="bn_data")
    if cast:
        body = mx.sym.Cast(body, dtype=np.dtype(dtype).name, name="cast_in")
    return mx.sym.Convolution(
        body, kernel=kernel, stride=stride, pad=pad, dilate=dilate,
        num_filter=nf, num_group=groups, no_bias=True, name="c")


def values(case, dtype, cast, seed=0):
    """(arguments, moving statistics) of ``stem``: with a Cast the batch and
    BatchNorm are float32 and the filter ``dtype`` (ResNet-50's bfloat16
    symbol), without one everything is ``dtype`` (a step under AMP)."""
    nd, cin, nf, kernel, _, _, _, groups = CASES[case]
    rng = np.random.RandomState(seed)
    front = jnp.float32 if cast else dtype
    args = {
        "data": jnp.asarray(
            2 * rng.randn(BATCH, cin, *(EXTENT[nd],) * nd) + 1, front),
        "bn_data_gamma": jnp.ones(cin, front),
        "bn_data_beta": jnp.asarray(rng.randn(cin), front),
        "c_weight": jnp.asarray(
            rng.randn(nf, cin // groups, *kernel) / np.sqrt(np.prod(kernel)),
            dtype)}
    aux = {"bn_data_moving_mean": jnp.zeros(cin, jnp.float32),
           "bn_data_moving_var": jnp.ones(cin, jnp.float32)}
    return args, aux


def head(out):
    """A loss whose cotangent differs from element to element."""
    out = out.astype(jnp.float32)
    return jnp.sum(jnp.sin(out) + 0.5 * out * out)


def loss_of(program, aux, is_train=True):
    def loss(args):
        outs, new_aux = program(args, aux, None, is_train)
        return head(outs[0]), (outs, new_aux)

    return loss


def plain_out(case, dtype=jnp.float32, cast=False):
    """The composition in ``jax`` alone: no node, no ``custom_vjp``,
    BatchNorm's two-pass formula."""
    nd, _, _, _, stride, pad, dilate, groups = CASES[case]

    def out(args):
        x = args["data"].astype(jnp.float32)
        axes = (0,) + tuple(range(2, 2 + nd))
        bshape = (1, -1) + (1,) * nd
        mean = jnp.mean(x, axis=axes)
        var = jnp.mean(jnp.square(x - mean.reshape(bshape)), axis=axes)
        y = ((x - mean.reshape(bshape))
             * jax.lax.rsqrt(var + EPS).reshape(bshape)
             + args["bn_data_beta"].astype(jnp.float32).reshape(bshape))
        y = y.astype(args["data"].dtype)
        if cast:
            y = y.astype(dtype)
        return jax.lax.conv_general_dilated(
            y, args["c_weight"], window_strides=stride,
            padding=[(p, p) for p in pad], rhs_dilation=dilate,
            dimension_numbers=nn._conv_dn(nd), feature_group_count=groups)

    return out


def plain_loss(case, dtype, cast):
    """``head`` over ``plain_out``: the loss ``loss_of`` takes."""
    out = plain_out(case, dtype, cast)
    return lambda args: head(out(args))


def sum_of_sines(case):
    """What the Module and the Executor tests bind (``MakeLoss`` over
    ``sum(sin(conv))``), over ``plain_out``."""
    out = plain_out(case)
    return lambda args: jnp.sum(jnp.sin(out(args)))


def close(mine, ref, tol, what):
    mine, ref = (np.asarray(a.astype(jnp.float32)) for a in (mine, ref))
    np.testing.assert_allclose(mine, ref, rtol=tol,
                               atol=tol * np.abs(ref).max(), err_msg=what)


def convolution_results(text):
    """The result shape of every convolution of a lowering (StableHLO) or
    of a compiled module (HLO)."""
    found = [tuple(int(d) for d in dims.split("x")) for dims in re.findall(
        r"stablehlo\.convolution.*-> tensor<([\dx]+)x\w+>", text)]
    found += [tuple(int(d) for d in dims.split(",")) for dims in re.findall(
        r"= \w+\[([\d,]+)\]\S* convolution\(", text)]
    return found


every_case = pytest.mark.parametrize("case", sorted(CASES))
every_dtype = pytest.mark.parametrize("dtype", sorted(DTYPES))
with_and_without_cast = pytest.mark.parametrize(
    "cast", [True, False], ids=["cast", "nocast"])


@with_and_without_cast
@every_dtype
@every_case
def test_gradients_are_the_plain_compositions(case, dtype, cast, lowerings):
    dt = DTYPES[dtype]
    program = executor._GraphProgram(stem(case, dt, cast))
    args, aux = values(case, dt, cast)
    params = {k: v for k, v in args.items() if k != "data"}

    def of(loss):
        return jax.jit(jax.grad(
            lambda p: loss(dict(p, data=args["data"]))))

    step = of(lambda a: loss_of(program, aux)(a)[0])
    mine, ref = step(params), of(plain_loss(case, dt, cast))(params)
    assert lowerings() == {"c": 1}
    assert mine["bn_data_beta"].dtype == args["bn_data_beta"].dtype
    close(mine["bn_data_beta"], ref["bn_data_beta"], TOL[dtype], "beta")
    close(mine["c_weight"], ref["c_weight"], TOL[dtype], "filter")
    assert not np.asarray(mine["bn_data_gamma"].astype(jnp.float32)).any()
    # the batch's data gradient is gone: forward, filter gradient, and the
    # forward response to each channel's indicator image
    results = convolution_results(step.lower(params).as_text())
    image, filt = tuple(args["data"].shape), tuple(args["c_weight"].shape)
    out, = [r for r in results if r[0] == BATCH]
    assert sorted(results) == sorted([out, filt, image[1:2] + out[1:]])


@with_and_without_cast
@every_dtype
@pytest.mark.parametrize("case", ["2d_k7_s2_p3_stem", "2d_k3_groups2",
                                  "1d_k7_s2_p3"])
def test_forward_filter_and_statistics_are_the_bypassed_programs_bits(
        case, dtype, cast):
    """Against the program that hands no shift over (the parent's): the
    output, the moving statistics and the filter's gradient to the bit, the
    data's gradient too where it is asked for; beta's to summation order."""
    dt = DTYPES[dtype]
    sym = stem(case, dt, cast)
    args, aux = values(case, dt, cast, seed=1)
    program, bypassed = executor._GraphProgram(sym), executor._GraphProgram(sym)
    bypassed._shift_grads = {}
    (g_new, (out_new, aux_new)), (g_old, (out_old, aux_old)) = (
        jax.jit(jax.grad(loss_of(p, aux), has_aux=True))(args)
        for p in (program, bypassed))
    np.testing.assert_array_equal(*(np.asarray(o[0].astype(jnp.float32))
                                    for o in (out_new, out_old)))
    for name in aux:
        assert not np.array_equal(aux_new[name], aux[name])
        np.testing.assert_array_equal(aux_new[name], aux_old[name])
    for name in ("c_weight", "data"):
        np.testing.assert_array_equal(
            *(np.asarray(g[name].astype(jnp.float32))
              for g in (g_new, g_old)), err_msg=name)
    close(g_new["bn_data_beta"], g_old["bn_data_beta"], TOL[dtype], "beta")


def test_the_response_is_float32_at_full_precision():
    """No less exact than the parent's: a float32 filter does not round to
    bfloat16 on its way into the MXU, and the products with the batch's
    summed cotangent are float32's."""
    dt = jnp.bfloat16
    program = executor._GraphProgram(stem("2d_k7_s2_p3_stem", dt, True))
    args, aux = values("2d_k7_s2_p3_stem", dt, True)
    params = {k: v for k, v in args.items() if k != "data"}
    text = jax.jit(jax.grad(lambda p: loss_of(program, aux)(
        dict(p, data=args["data"]))[0])).lower(params).as_text()
    small, = [line for line in text.splitlines()
              if re.search(r"stablehlo\.convolution.*-> tensor<3x", line)]
    assert "tensor<3x8x9x9xf32>" in small
    assert small.count("precision HIGHEST") == 2
    assert "bf16" not in small


def test_no_fewer_channels_than_samples_keeps_the_parents_program(
        lowerings, monkeypatch):
    """The response at batch ``C`` would cost what the batch's data gradient
    does: the pair is in the plan and the step does not take it."""
    case = "2d_k3_p1_c8"
    program = executor._GraphProgram(stem(case, jnp.float32, False))
    assert len(program._shift_grads) == 2
    args, aux = values(case, jnp.float32, False)
    few = {k: (v[:8] if k == "data" else v) for k, v in args.items()}
    params = {k: v for k, v in few.items() if k != "data"}

    def loss(p):
        return loss_of(program, aux)(dict(p, data=few["data"]))[0]

    new = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    assert lowerings() == {}
    monkeypatch.setattr(nn, "_conv_named_grads", PARENT_FORM)
    old = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    assert stripped(new) == stripped(old)


def test_module_inputs_need_grad_still_returns_the_datas_gradient():
    case, dt = "2d_k7_s2_p3_stem", jnp.float32
    net = mx.sym.MakeLoss(mx.sym.sum(mx.sym.sin(stem(case, dt, False))),
                          name="loss")
    args, aux = values(case, dt, False)
    mod = mx.mod.Module(net, data_names=["data"], label_names=None,
                        context=mx.cpu())
    mod.bind(data_shapes=[("data", args["data"].shape)], for_training=True,
             inputs_need_grad=True)
    mod.init_params(
        arg_params={k: mx.nd.array(np.asarray(v)) for k, v in args.items()
                    if k != "data"},
        aux_params={k: mx.nd.array(np.asarray(v)) for k, v in aux.items()})
    mod.forward(mx.io.DataBatch([mx.nd.array(np.asarray(args["data"]))], []),
                is_train=True)
    mod.backward()
    got, = mod.get_input_grads()
    ref = jax.grad(sum_of_sines(case))(args)
    close(jnp.asarray(got.asnumpy()), ref["data"], 1e-3, "data")
    grads = dict(zip(mod._exec_group.param_names,
                     (g[0].asnumpy() for g in mod._exec_group.grad_arrays)))
    close(jnp.asarray(grads["bn_data_beta"]), ref["bn_data_beta"], 2e-5,
          "beta")


# -- the bypasses ------------------------------------------------------------

def _input_bn(data=None, **attrs):
    return mx.sym.BatchNorm(
        mx.sym.Variable("data") if data is None else data, eps=EPS,
        name="bn_data", **dict({"fix_gamma": True}, **attrs))


def _conv(body, op=mx.sym.Convolution, **attrs):
    return op(body, name="c", **dict(
        {"kernel": (3, 3), "num_filter": 8, "no_bias": True}, **attrs))


BYPASSES = {
    # gamma needs sum(dy * xhat): the whole data gradient
    "learns_gamma": lambda: _conv(_input_bn(fix_gamma=False)),
    "use_global_stats": lambda: _conv(_input_bn(use_global_stats=True)),
    # BatchNorm's input is another op's output: its dx needs the whole dy
    "mid_network": lambda: _conv(_input_bn(
        mx.sym.Activation(mx.sym.Variable("data"), act_type="tanh"))),
    "op_in_between": lambda: _conv(
        mx.sym.Activation(_input_bn(), act_type="tanh")),
    "two_readers": lambda: (lambda bn: _conv(
        bn, pad=(1, 1), num_filter=3) + bn)(_input_bn()),
    "an_output_too": lambda: (lambda bn: mx.sym.Group(
        [_conv(bn), bn]))(_input_bn()),
    "deconvolution": lambda: _conv(_input_bn(), op=mx.sym.Deconvolution),
}


@pytest.mark.parametrize("which", sorted(BYPASSES))
def test_a_bypass_keeps_the_parents_program(which, lowerings, monkeypatch):
    program = executor._GraphProgram(BYPASSES[which]())
    assert program._shift_grads == {}
    args, aux = values("2d_k3_s1_p0", jnp.float32, False)
    if which == "two_readers":
        args["c_weight"] = args["c_weight"][:3]
    elif which == "deconvolution":
        args["c_weight"] = jnp.swapaxes(args["c_weight"], 0, 1)
    params = {k: v for k, v in args.items() if k != "data"}

    def loss(p):
        outs, _ = program(dict(p, data=args["data"]), aux, None, True)
        return sum(head(o) for o in outs)

    new = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    assert lowerings() == {}
    assert convolution_results(new)
    monkeypatch.setattr(nn, "_conv_named_grads", PARENT_FORM)
    old = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    assert stripped(new) == stripped(old)


def test_inference_keeps_the_parents_program(lowerings, monkeypatch):
    program = executor._GraphProgram(stem("2d_k3_s1_p0", jnp.float32, True))
    assert len(program._shift_grads) == 2  # the pair is there: training takes it
    args, aux = values("2d_k3_s1_p0", jnp.float32, True)

    def loss(a):
        return loss_of(program, aux, is_train=False)(a)[0]

    new = jax.jit(jax.grad(loss)).lower(args).compile().as_text()
    assert lowerings() == {}
    monkeypatch.setattr(nn, "_conv_named_grads", PARENT_FORM)
    old = jax.jit(jax.grad(loss)).lower(args).compile().as_text()
    assert stripped(new) == stripped(old)


def test_the_placed_program_keeps_the_plain_form(lowerings):
    """Model parallel segments (``_PlacedProgram``) trace node by node
    without the plan: beta's gradient is BatchNorm's own there."""
    case = "2d_k3_s1_p0"
    net = mx.sym.MakeLoss(mx.sym.sum(mx.sym.sin(_conv(
        _input_bn(attr={"ctx_group": "a"}), attr={"ctx_group": "b"}))))
    args, aux = values(case, jnp.float32, False)
    exe = net.simple_bind(mx.cpu(0), grad_req="write",
                          group2ctx={"a": mx.cpu(0), "b": mx.cpu(1)},
                          data=args["data"].shape)
    for k, v in args.items():
        exe.arg_dict[k][:] = np.asarray(v)
    exe.forward(is_train=True)
    exe.backward()
    assert lowerings() == {}
    ref = jax.grad(sum_of_sines(case))(args)
    close(jnp.asarray(exe.grad_dict["bn_data_beta"].asnumpy()),
          ref["bn_data_beta"], 2e-5, "beta")


# -- the levers --------------------------------------------------------------

LEVERS = {
    "nhwc": ({"MXNET_CONV_BWD_LAYOUT": "NHWC"}, "2d_k7_s2_p3_stem"),
    "s2d_stem": ({"MXNET_CONV_S2D": "1"}, "2d_k7_s2_p3_stem"),
    "s2d_k1": ({"MXNET_CONV_S2D": "1"}, "2d_k1_s2_p0"),
    "wgrad_patches": ({"MXNET_CONV_WGRAD": "patches"}, "2d_k7_s2_p3_stem"),
    "wgrad_taps": ({"MXNET_CONV_WGRAD": "taps"}, "2d_k7_s2_p3_stem"),
    "pallas": ({"MXTPU_CONV_KERNEL": "pallas"}, "2d_k3_p1_c8"),
}


@pytest.mark.parametrize("lever", sorted(LEVERS))
def test_the_form_holds_under_every_lever(lever, lowerings, monkeypatch):
    """The shift's gradient hangs on the node's output and reads the plain
    convolution alone, whichever path computed that output and its two
    gradients."""
    env, case = LEVERS[lever]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(kernels.common, "INTERPRET", True)
    dt = jnp.float32
    program = executor._GraphProgram(stem(case, dt, False))
    args, aux = values(case, dt, False)
    params = {k: v for k, v in args.items() if k != "data"}
    step = jax.jit(jax.grad(
        lambda p: loss_of(program, aux)(dict(p, data=args["data"]))[0]))
    mine = step(params)
    ref = jax.grad(lambda p: plain_loss(case, dt, False)(
        dict(p, data=args["data"])))(params)
    assert lowerings() == {"c": 1}
    close(mine["bn_data_beta"], ref["bn_data_beta"], 2e-5, "beta")
    close(mine["c_weight"], ref["c_weight"], 1e-4, "filter")
    results = convolution_results(step.lower(params).as_text())
    assert tuple(args["data"].shape) not in results
    assert len([r for r in results if r[0] == CASES[case][1]]) >= 1


# -- whole models through the fused step -------------------------------------

def lowered_step(sym, data_shape):
    """StableHLO of ``ShardedTrainStep``'s step over ``sym`` at
    ``data_shape``, traced from shapes."""
    batch = data_shape[0]
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=data_shape, softmax_label=(batch,))
    shapes = dict(zip(sym.list_arguments(), arg_shapes))
    types = dict(zip(sym.list_arguments(),
                     sym.infer_type(data=np.float32)[0]))
    names = [n for n in sym.list_arguments()
             if n not in ("data", "softmax_label")]
    opt = mx.optimizer.create(
        "sgd", sym=sym, param_idx2name=dict(enumerate(names)),
        learning_rate=0.05, momentum=0.9, rescale_grad=1.0 / batch)
    step = ShardedTrainStep(sym, make_mesh(dp=1, devices=jax.devices()[:1]),
                            optimizer=opt)
    sds = jax.ShapeDtypeStruct
    params = {n: sds(shapes[n], types[n]) for n in names}
    aux = {n: sds(s, jnp.float32)
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    batch_in = {"data": sds(data_shape, jnp.float32),
                "softmax_label": sds((batch,), jnp.float32)}
    scalar = sds((), jnp.float32)
    return jax.jit(step._make_step_fn()).lower(
        params, aux, params, batch_in, sds((2,), jnp.uint32), scalar, scalar,
        scalar).as_text()


@pytest.mark.parametrize("model,first,kwargs", [
    ("resnet", "conv0", dict(num_layers=18)),
    ("resnet", "conv0", dict(num_layers=18, dtype="bfloat16")),
    ("resnext", "stem_conv", dict(num_layers=50, num_group=8)),
], ids=["resnet18", "resnet18_bf16", "resnext50"])
def test_a_residual_nets_step_computes_no_gradient_of_the_image(
        model, first, kwargs, lowerings):
    from mxnet_tpu import models

    sym = getattr(models, model)(num_classes=10, image_shape="3,64,64",
                                 **kwargs)
    text = lowered_step(sym, (8, 3, 64, 64))
    assert lowerings() == {first: 1}
    results = convolution_results(text)
    assert len(results) > 30
    assert not [r for r in results if r[:2] == (8, 3)], results
    # the stem's forward, and its response to the three channels' images
    assert [r for r in results if r[0] == 3] == [(3,) + results[0][1:]]


def test_inception_v3s_step_takes_no_such_form(lowerings):
    """Its first convolution reads the batch itself; no BatchNorm stands in
    front of a convolution's only input."""
    from mxnet_tpu.models import inception_v3

    sym = inception_v3(num_classes=10)
    assert executor._GraphProgram(sym)._shift_grads == {}
    text = lowered_step(sym, (2, 3, 299, 299))
    assert lowerings() == {}
    assert 3 not in [r[0] for r in convolution_results(text)]
