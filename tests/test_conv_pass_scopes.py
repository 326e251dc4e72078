"""The default path of ``Convolution`` names its two gradient convolutions
(``ops/nn.py::_conv_named_grads``): in a device trace a backward op of a
node reads ``transpose(jvp(conv/<node>))/dgrad/...`` or ``.../wgrad/...``,
which is what ``bench/conv_scopes.py`` files it by. A scope is metadata,
so the tests hold the named form to the bare differentiated call three
ways: the names are there, the gradients are the same bits, and the
optimized HLO is the same text once the metadata is stripped.

Host only. The same comparison for a described v5e at two of ResNet-50's
shapes is in ``tests/test_flash_compile_tpu.py`` (the one file that loads
the TPU's library) and imports ``stripped`` and ``PARENT_FORM`` from here.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor
from mxnet_tpu.ops import nn

# (spatial dims, channels in, filters, kernel, stride, dilate, pad, groups,
#  no_bias)
CASES = {
    "1d_k3": (1, 8, 16, (3,), (1,), (1,), (1,), 1, True),
    "1d_s2_bias": (1, 8, 16, (3,), (2,), (1,), (0,), 1, False),
    "2d_k3_pad": (2, 8, 16, (3, 3), (1, 1), (1, 1), (1, 1), 1, True),
    "2d_k3_s2_bias": (2, 8, 16, (3, 3), (2, 2), (1, 1), (1, 1), 1, False),
    "2d_k1_s2": (2, 8, 16, (1, 1), (2, 2), (1, 1), (0, 0), 1, True),
    "2d_dilate2": (2, 8, 8, (3, 3), (1, 1), (2, 2), (2, 2), 1, True),
    "2d_groups2_bias": (2, 8, 8, (3, 3), (1, 1), (1, 1), (1, 1), 2, False),
    "2d_k7_s2_stem": (2, 3, 8, (7, 7), (2, 2), (1, 1), (3, 3), 1, True),
    "2d_k1x7": (2, 8, 8, (1, 7), (1, 1), (1, 1), (0, 3), 1, True),
    "3d_k3": (3, 4, 8, (3, 3, 3), (1, 1, 1), (1, 1, 1), (1, 1, 1), 1, True),
    "3d_s2_groups2": (3, 4, 8, (3, 3, 3), (2, 2, 2), (1, 1, 1), (0, 0, 0), 2,
                      False),
}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
EXTENT = {1: 17, 2: 12, 3: 7}


def PARENT_FORM(plain, data, weight, dgrad=None, wgrad=None):
    """What the default path was before it named its gradients: the bare
    call, which jax differentiates."""
    return plain(data, weight)


def stripped(hlo_text):
    """Optimized HLO without what only names things: each instruction's
    ``metadata={...}`` and the module's tables of files, functions and
    stack frames."""
    text = re.sub(r",? ?metadata=\{[^{}]*\}", "", hlo_text)
    head, sep, rest = text.partition("\n\nFileNames")
    if sep:
        text = head + "\n\n" + rest[re.search(r"\n\n\n", rest).end():]
    return text


def conv_names(lowered):
    """The scope path of every ``stablehlo.convolution`` of a lowering."""
    text = lowered.as_text(debug_info=True)
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    return [named[ref] for ref in re.findall(
        r"stablehlo\.convolution.*loc\((#loc\d+)\)", text)]


def node(case, dtype, seed=0):
    """A one-node program and its arguments: the node ``c`` through
    ``executor._compute_node``, as a step traces it."""
    nd, cin, nf, kernel, stride, dilate, pad, groups, no_bias = CASES[case]
    sym = mx.sym.Convolution(
        mx.sym.Variable("data"), kernel=kernel, stride=stride, dilate=dilate,
        pad=pad, num_filter=nf, num_group=groups, no_bias=no_bias, name="c")
    rng = np.random.RandomState(seed)
    args = {"data": rng.randn(2, cin, *(EXTENT[nd],) * nd),
            "c_weight": rng.randn(nf, cin // groups, *kernel)}
    if not no_bias:
        args["c_bias"] = rng.randn(nf)
    return (executor._GraphProgram(sym),
            {k: jnp.asarray(v, dtype) for k, v in args.items()})


def loss_of(program):
    def loss(args):
        out, = program(args, {}, None, True)[0]
        return jnp.sum((out * out).astype(jnp.float32))

    return loss


def bare_loss(case):
    """The same loss over the bare ``lax`` call, with no node round it."""
    nd, _, _, _, stride, dilate, pad, groups, _ = CASES[case]

    def loss(args):
        out = jax.lax.conv_general_dilated(
            args["data"], args["c_weight"], window_strides=stride,
            padding=[(p, p) for p in pad], rhs_dilation=dilate,
            dimension_numbers=nn._conv_dn(nd), feature_group_count=groups)
        if "c_bias" in args:
            out = out + args["c_bias"].reshape((1, -1) + (1,) * nd)
        return jnp.sum((out * out).astype(jnp.float32))

    return loss


every_case = pytest.mark.parametrize("case", sorted(CASES))
every_dtype = pytest.mark.parametrize("dtype", sorted(DTYPES))


@every_dtype
@every_case
def test_both_gradient_convolutions_carry_their_name(case, dtype):
    program, args = node(case, DTYPES[dtype])
    names = conv_names(jax.jit(jax.grad(loss_of(program))).lower(args))
    assert len(names) == 3, names
    fwd = [n for n in names if "transpose(" not in n]
    assert len(fwd) == 1 and "jvp(conv/c)/" in fwd[0]
    assert "dgrad" not in fwd[0] and "wgrad" not in fwd[0]
    for grad in ("dgrad", "wgrad"):
        mine = [n for n in names if "/%s/" % grad in n]
        assert len(mine) == 1, names
        assert "transpose(jvp(conv/c))/%s/" % grad in mine[0]
        assert mine[0].count("dgrad") + mine[0].count("wgrad") == 1


@every_dtype
@every_case
def test_gradients_are_the_bare_calls_bit_for_bit(case, dtype):
    program, args = node(case, DTYPES[dtype])
    mine = jax.jit(jax.grad(loss_of(program)))(args)
    bare = jax.jit(jax.grad(bare_loss(case)))(args)
    assert sorted(mine) == sorted(args)
    for name in args:
        assert mine[name].dtype == args[name].dtype
        np.testing.assert_array_equal(
            np.asarray(mine[name].astype(jnp.float32)),
            np.asarray(bare[name].astype(jnp.float32)), err_msg=name)


@every_dtype
@every_case
def test_the_compiled_backward_is_the_parents_but_for_names(
        case, dtype, monkeypatch):
    program, args = node(case, DTYPES[dtype])
    new = jax.jit(jax.grad(loss_of(program))).lower(args).compile().as_text()
    monkeypatch.setattr(nn, "_conv_named_grads", PARENT_FORM)
    old = jax.jit(jax.grad(loss_of(program))).lower(args).compile().as_text()
    assert "dgrad" in new and "dgrad" not in old
    assert stripped(new) == stripped(old)


def test_inside_the_steps_scope_the_names_stay_under_the_node():
    """The fused step differentiates under ``jax.named_scope("fwd_bwd")``;
    a ``custom_vjp`` rule's ops then read ``transpose(fwd_bwd)/jvp(conv/c)/
    dgrad/...`` (as BatchNorm's rule always has), which is the form
    ``bench/reduce_scopes.classify`` files: a ``transpose(`` somewhere,
    ``conv/<node>`` and the gradient's name after it."""
    program, args = node("2d_k3_pad", jnp.float32)

    def step(a):
        with jax.named_scope("fwd_bwd"):
            return jax.grad(loss_of(program))(a)

    names = conv_names(jax.jit(step).lower(args))
    assert len(names) == 3
    for grad in ("dgrad", "wgrad"):
        mine, = [n for n in names if "/%s/" % grad in n]
        assert "transpose(" in mine and "fwd_bwd" in mine
        assert re.search(r"[/(]conv/c\)*/%s/" % grad, mine), mine


def block(first_reads):
    """conv + BatchNorm + ReLU + conv, the pattern of both conv cells; the
    first convolution reads the ``batch`` itself, a BatchNorm of it whose
    beta is trained (``input_bn``: ResNet-50's ``conv0`` reads ``bn_data``;
    the node then carries beta's gradient, tests/test_conv_shift_grad.py)
    or one that learns its gamma too and so needs the whole data gradient
    (``input_bn_gamma``)."""
    data = mx.sym.Variable("data")
    if first_reads != "batch":
        data = mx.sym.BatchNorm(data, fix_gamma=first_reads == "input_bn",
                                name="bn_data")
    body = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=8,
                              no_bias=True, name="c1")
    body = mx.sym.BatchNorm(body, fix_gamma=False, name="bn1")
    body = mx.sym.Activation(body, act_type="relu", name="relu1")
    sym = mx.sym.Convolution(body, kernel=(1, 1), stride=(2, 2),
                             num_filter=16, no_bias=True, name="c2")
    program = executor._GraphProgram(sym)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=(4, 3, 10, 10))
    rng = np.random.RandomState(3)
    args = {n: jnp.asarray(rng.rand(*s) + 0.5, jnp.float32)
            for n, s in zip(program.arg_names, arg_shapes)}
    aux = {n: jnp.ones(s, jnp.float32)
           for n, s in zip(program.aux_names, aux_shapes)}
    batch = args.pop("data")

    def loss(params):
        out, = program(dict(params, data=batch), aux, None, True)[0]
        return jnp.sum(out * out)

    return loss, args


@pytest.mark.parametrize("first_reads", ["batch", "input_bn_gamma"])
def test_a_block_compiles_to_the_parents_program(first_reads, monkeypatch):
    loss, params = block(first_reads)
    new = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    monkeypatch.setattr(nn, "_conv_named_grads", PARENT_FORM)
    old = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    assert stripped(new) == stripped(old)


@pytest.mark.parametrize("first_reads,dgrads", [
    ("batch", 1), ("input_bn", 2), ("input_bn_gamma", 2)])
def test_a_data_gradient_nobody_reads_is_not_compiled(first_reads, dgrads):
    """The rule hands back both gradients; the batch's is dead code (jax
    drops it from the jaxpr before it lowers, and XLA would), as jax's own
    rule would never have made it. Behind an input BatchNorm that trains
    its beta alone the second one under ``dgrad`` is the shift's: a forward
    convolution at batch ``C``."""
    loss, params = block(first_reads)
    lowered = jax.jit(jax.grad(loss)).lower(params)
    assert sum("/dgrad/" in n for n in conv_names(lowered)) == dgrads
    assert sum("/wgrad/" in n for n in conv_names(lowered)) == 2
    # two forward, two filter gradients and the data gradients that are read
    compiled = re.findall(r" convolution\(", lowered.compile().as_text())
    assert len(compiled) == 4 + dgrads


def test_a_levered_node_carries_its_own_two_names_once(monkeypatch):
    monkeypatch.setenv("MXNET_CONV_BWD_LAYOUT", "NHWC")
    program, args = node("2d_k3_pad", jnp.float32)
    names = conv_names(jax.jit(jax.grad(loss_of(program))).lower(args))
    assert len(names) == 3
    for grad in ("dgrad", "wgrad"):
        mine, = [n for n in names if "/%s/" % grad in n]
        assert mine.count("dgrad") + mine.count("wgrad") == 1
        assert "transpose(jvp(conv/c))/%s/" % grad in mine


def test_forward_alone_names_no_gradient():
    program, args = node("2d_k3_pad", jnp.float32)
    infer = jax.jit(lambda a: program(a, {}, None, False)[0][0])
    names = conv_names(infer.lower(args))
    assert names and not any("grad" in n for n in names)
    text = infer.lower(args).as_text(debug_info=True)
    assert "dgrad" not in text and "wgrad" not in text


def test_a_second_derivative_goes_through_the_rule():
    """The rule's backward is jax's own transposes, so it differentiates
    again: a gradient penalty trains."""
    program, args = node("2d_k3_pad", jnp.float32)
    bare = bare_loss("2d_k3_pad")

    def penalty(loss):
        def f(a):
            g = jax.grad(loss)(a)
            return sum(jnp.sum(v * v) for v in g.values())

        return f

    mine = jax.grad(penalty(loss_of(program)))(args)
    ref = jax.grad(penalty(bare))(args)
    for name in args:
        np.testing.assert_allclose(mine[name], ref[name], rtol=1e-5)
