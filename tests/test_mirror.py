"""Gradient checkpointing / memory mirror (MXNET_BACKWARD_DO_MIRROR).

Reference: graph_executor.cc:213-226 — with the env flag set, backward
recomputes every op except Convolution/FullyConnected/Concat/
SoftmaxOutput instead of keeping its output alive. TPU translation:
``jax.checkpoint`` over the traced graph with a policy that saves
dot/conv residuals only (executor._mirror_policy).

What is pinned here (CPU): the flag actually wires a remat into the
traced computation (falsifiable: remove the wiring and the jaxpr has no
remat equation), gradients are bit-compatible with the non-mirrored
path, and the fused ShardedTrainStep honors the same flag. The MEMORY
effect on the chip is not measured (PERF.md); XLA's CPU pipeline largely
undoes rematerialization, so a CPU memory assertion would pin XLA
internals, not our behavior.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx


def _conv_bn_net(n_layers=3):
    net = mx.sym.Variable("data")
    for i in range(n_layers):
        net = mx.sym.Convolution(net, kernel=(3, 3), num_filter=8,
                                 pad=(1, 1), name="conv%d" % i)
        net = mx.sym.BatchNorm(net, name="bn%d" % i)
        net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=5, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


@pytest.fixture
def _mirror_env():
    os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    yield
    os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)


def _run_fwdbwd(seed=0):
    exe = _conv_bn_net().simple_bind(ctx=mx.cpu(0), data=(4, 3, 16, 16),
                                     softmax_label=(4,))
    rng = np.random.RandomState(seed)
    for n, a in exe.arg_dict.items():
        if n not in ("data", "softmax_label"):
            a[:] = rng.randn(*a.shape) * 0.05
    exe.arg_dict["data"][:] = rng.rand(4, 3, 16, 16)
    exe.arg_dict["softmax_label"][:] = rng.randint(0, 5, (4,))
    exe.forward(is_train=True)
    exe.backward()
    return exe


def test_mirror_gradients_match_plain():
    exe_plain = _run_fwdbwd()
    os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    try:
        exe_mirror = _run_fwdbwd()
    finally:
        os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)
    for name in exe_plain.grad_dict:
        if exe_plain.grad_dict[name] is None:
            continue
        # atol covers reassociation noise on degenerate ~0 grads (conv
        # bias feeding BatchNorm has an exactly-zero true gradient)
        np.testing.assert_allclose(
            exe_mirror.grad_dict[name].asnumpy(),
            exe_plain.grad_dict[name].asnumpy(), rtol=1e-5, atol=5e-5,
            err_msg=name)


def test_mirror_inserts_remat(_mirror_env):
    import jax

    exe = _conv_bn_net().simple_bind(ctx=mx.cpu(0), data=(4, 3, 16, 16),
                                     softmax_label=(4,))
    arg_vals = tuple(a._data for a in exe.arg_arrays)
    aux_vals = tuple(a._data for a in exe.aux_arrays)
    jaxpr = str(jax.make_jaxpr(
        lambda a, x: exe._fwdbwd_jit.__wrapped__(a, x, None, None)
    )(arg_vals, aux_vals))
    assert "remat" in jaxpr or "checkpoint" in jaxpr


def test_no_mirror_no_remat():
    import jax

    exe = _conv_bn_net().simple_bind(ctx=mx.cpu(0), data=(4, 3, 16, 16),
                                     softmax_label=(4,))
    arg_vals = tuple(a._data for a in exe.arg_arrays)
    aux_vals = tuple(a._data for a in exe.aux_arrays)
    jaxpr = str(jax.make_jaxpr(
        lambda a, x: exe._fwdbwd_jit.__wrapped__(a, x, None, None)
    )(arg_vals, aux_vals))
    assert "remat" not in jaxpr and "checkpoint" not in jaxpr


def test_force_mirroring_attr_enables_remat():
    """__force_mirroring__ on a symbol enables the mirror without the
    env flag (reference need_mirror checks the attr first)."""
    import jax

    data = mx.sym.Variable("data")
    with mx.AttrScope(__force_mirroring__="True"):
        h = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
        h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(h, name="softmax")
    exe = net.simple_bind(ctx=mx.cpu(0), data=(4, 6), softmax_label=(4,))
    arg_vals = tuple(a._data for a in exe.arg_arrays)
    aux_vals = tuple(a._data for a in exe.aux_arrays)
    jaxpr = str(jax.make_jaxpr(
        lambda a, x: exe._fwdbwd_jit.__wrapped__(a, x, None, None)
    )(arg_vals, aux_vals))
    assert "remat" in jaxpr or "checkpoint" in jaxpr


def test_fused_step_honors_mirror(_mirror_env):
    """ShardedTrainStep under the flag still trains correctly (numerics
    vs the plain fused step)."""
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh

    def train(flag):
        if flag:
            os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
        else:
            os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)
        net = _conv_bn_net(n_layers=1)
        mesh = make_mesh(dp=2, tp=1)
        opt = mx.optimizer.SGD(learning_rate=0.1, rescale_grad=1.0 / 8)
        step = ShardedTrainStep(net, mesh, optimizer=opt).compile()
        shapes = {"data": (8, 3, 16, 16), "softmax_label": (8,)}
        arg_shapes, _, _ = net.infer_shape(**shapes)
        np.random.seed(0)
        params, aux, st = step.init(
            dict(zip(net.list_arguments(), arg_shapes)),
            mx.initializer.Uniform(0.05))
        rng = np.random.RandomState(1)
        import jax

        batch = {
            "data": jax.device_put(
                rng.rand(8, 3, 16, 16).astype(np.float32),
                step.batch_sharding()),
            "softmax_label": jax.device_put(
                rng.randint(0, 5, (8,)).astype(np.float32),
                step.batch_sharding()),
        }
        for t in range(3):
            params, aux, st, _ = step(params, aux, st, batch, t=t + 1)
        return {k: np.asarray(v) for k, v in params.items()}

    p_mirror = train(True)
    p_plain = train(False)
    for k in p_plain:
        np.testing.assert_allclose(p_mirror[k], p_plain[k],
                                   rtol=1e-5, atol=1e-7, err_msg=k)
