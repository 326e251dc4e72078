"""``DiffAttention`` (``ops/transformer/attention.py``; differential
attention, Ye et al., arXiv:2410.05258) against materialised ``[T, T]``
maps.

The oracle (``by_hand``) builds both softmax maps of every head pair under
an explicit mask in float32 at the highest matmul precision. The op runs
two calls of ``kernels.attention`` (at T >= 128 ``flash_attention``, whose
branch off the TPU is the materialised arithmetic in tiles) and combines
them in float32, so float32 results differ by the order of summation
alone: rtol 1e-5 with an atol of 64 float32 ulps of the tensor's largest
entry (256 for gradients, which pass the norm's ``1 / rms``).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.contrib import symbol as csym
from mxnet_tpu.ops.transformer import attention, diff_attention

H, G, D = 8, 4, 16          # query heads, key/value heads, head width


def by_hand(q, k, v, lq1, lk1, lq2, lk2, gamma, depth, window, eps=1e-5):
    b, t, _ = q.shape
    pairs, groups = H // 2, G // 2
    q = q.reshape(b, t, pairs, 2, D)
    k = jnp.repeat(k.reshape(b, t, groups, 2, D), pairs // groups, axis=2)
    v = jnp.repeat(v.reshape(b, t, groups, 2 * D), pairs // groups, axis=2)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_init
    delta = np.arange(t)[:, None] - np.arange(t)[None, :]
    mask = (delta >= 0) & ((delta < window) if window else True)
    maps = []
    with jax.default_matmul_precision("highest"):
        for i in (0, 1):
            scores = jnp.einsum("bqpd,bkpd->bpqk", q[:, :, :, i],
                                k[:, :, :, i]) * D ** -0.5
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
            maps.append(jnp.einsum("bpqk,bkpe->bqpe",
                                   jax.nn.softmax(scores, axis=-1), v))
    o = maps[0] - lam * maps[1]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return (gamma * o * (1.0 - lam_init)).reshape(b, t, H * D)


def _inputs(seed, b, t):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(scale * rng.normal(size=shape), jnp.float32)

    return (draw(b, t, H * D), draw(b, t, G * D), draw(b, t, G * D),
            draw(D, scale=0.3), draw(D, scale=0.3), draw(D, scale=0.3),
            draw(D, scale=0.3), 1 + draw(2 * D, scale=0.1))


def _close(got, want, what, rtol=1e-5, ulps=64):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _op(depth, window, cross=False):
    def f(*ins):
        return diff_attention(
            *ins, num_heads=H, num_kv_heads=G,
            lambda_init=attention.diff_lambda_init(depth), window=window,
            cross=cross)
    return f


# a window of 512 against the materialised mask at T 1024 (two tiles of
# keys) and T 600 (a ragged length under the same window); full; a short
# sequence under the dispatch's T >= 128 (the materialised branch)
@pytest.mark.parametrize("t,window,depth", [
    (1024, 512, 1), (600, 512, 3), (600, 0, 17), (200, 64, 5), (40, 12, 1)])
def test_the_two_maps_their_difference_and_the_norm(t, window, depth):
    ins = _inputs(t + depth, 1, t)
    with jax.default_matmul_precision("highest"):
        got = _op(depth, window)(*ins)
    _close(got, by_hand(*ins, depth, window), "out")


@pytest.mark.parametrize("t,window", [(160, 48), (136, 0)])
def test_every_inputs_gradient_lambda_and_the_four_vectors_among_them(
        t, window):
    ins = _inputs(7, 2, t)
    weight = jnp.asarray(np.random.default_rng(8).normal(
        size=(2, t, H * D)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *p: jnp.sum(_op(9, window)(*p) * weight),
                       argnums=tuple(range(8)))(*ins)
    want = jax.grad(lambda *p: jnp.sum(by_hand(*p, 9, window) * weight),
                    argnums=tuple(range(8)))(*ins)
    names = ("query", "key", "value", "lambda_q1", "lambda_k1", "lambda_q2",
             "lambda_k2", "subln_gamma")
    for name, g, w in zip(names, got, want):
        _close(g, w, "d" + name, ulps=256)
        assert float(jnp.abs(w).max()) > 1e-6, name


def test_lambda_init_follows_the_layers_number():
    assert attention.diff_lambda_init(0) == pytest.approx(0.2)
    assert attention.diff_lambda_init(17) == pytest.approx(
        0.8 - 0.6 * math.exp(-5.1))
    ins = _inputs(3, 1, 64)
    shallow, deep = (_op(depth, 0)(*ins) for depth in (1, 21))
    for depth, got in ((1, shallow), (21, deep)):
        _close(got, by_hand(*ins, depth, 0), "depth %d" % depth)
    assert float(jnp.abs(shallow - deep).max()) > 0.05


def test_keys_and_values_from_another_node_are_the_cross_form():
    """The cross form is the same mathematics on keys and values another
    node made: through the symbol, two readers of ONE key / value pair,
    the pair's gradient the sum of both readers' parts."""
    t = 48
    data = mx.sym.Variable("data")
    keys, values = mx.sym.Variable("keys"), mx.sym.Variable("values")

    def node(name, depth, cross):
        return csym.DiffAttention(
            mx.sym.Variable(name + "_q"), keys, values, num_heads=H,
            num_kv_heads=G, depth=depth, cross=cross, kv_from="made_here",
            name=name)

    both = node("a", 17, False) + node("b", 19, True) + 0 * mx.sym.sum(data)
    ins = _inputs(11, 1, t)
    extra = _inputs(12, 1, t)
    args = {"data": np.zeros((1, 1), np.float32), "keys": ins[1],
            "values": ins[2], "a_q": ins[0], "b_q": extra[0]}
    for name, src in (("a", ins), ("b", extra)):
        for key, value in zip(("lambda_q1", "lambda_k1", "lambda_q2",
                               "lambda_k2", "subln_gamma"), src[3:]):
            args["%s_%s" % (name, key)] = value
    telemetry.reset()
    telemetry.enable()
    try:
        exe = both.bind(mx.cpu(0), {k: mx.nd.array(np.asarray(v))
                                    for k, v in args.items()},
                        args_grad={k: mx.nd.zeros(np.asarray(v).shape)
                                   for k, v in args.items()})
        out = exe.forward(is_train=True)[0].asnumpy()
        weight = np.random.default_rng(13).normal(size=out.shape).astype(
            np.float32)
        exe.backward([mx.nd.array(weight)])
        readers = telemetry.REGISTRY.get("attention.shared_kv_readers")
        assert readers.value(source="made_here") >= 1
        lowerings = telemetry.REGISTRY.get("attention.diff_lowerings")
        assert lowerings.value(heads=H, window=0, cross=1) >= 1
        assert lowerings.value(heads=H, window=0, cross=0) >= 1
    finally:
        telemetry.disable()
        telemetry.reset()

    def hand(keys, values):
        return (by_hand(ins[0], keys, values, *ins[3:], 17, 0)
                + by_hand(extra[0], keys, values, *extra[3:], 19, 0))

    _close(out, hand(ins[1], ins[2]), "out")
    d_keys, d_values = jax.grad(
        lambda k, v: jnp.sum(hand(k, v) * weight), argnums=(0, 1))(
            ins[1], ins[2])
    _close(exe.grad_dict["keys"].asnumpy(), d_keys, "dkeys", ulps=256)
    _close(exe.grad_dict["values"].asnumpy(), d_values, "dvalues", ulps=256)
    # and each reader's part alone is not the sum
    alone = jax.grad(lambda k: jnp.sum(by_hand(
        ins[0], k, ins[2], *ins[3:], 17, 0) * weight))(ins[1])
    assert float(jnp.abs(alone - d_keys).max()) > 1e-3


def test_in_bf16_the_combination_stays_float32():
    ins = tuple(v.astype(jnp.bfloat16) for v in _inputs(5, 1, 200))
    got = _op(3, 64)(*ins)
    assert got.dtype == jnp.bfloat16
    want = by_hand(*(v.astype(jnp.float32) for v in ins), 3, 64)
    err = jnp.abs(got.astype(jnp.float32) - want)
    # bf16 maps through a difference and a 1 / rms: 3% of the rms
    assert float(jnp.sqrt(jnp.mean(err ** 2))) < 0.03 * float(
        jnp.sqrt(jnp.mean(want ** 2)))


def test_shapes_are_inferred_and_what_is_wrong_is_named():
    q = mx.sym.Variable("q")
    node = csym.DiffAttention(q, mx.sym.Variable("k"), mx.sym.Variable("v"),
                              num_heads=H, num_kv_heads=G, depth=3,
                              window=12, name="attn")
    assert node.list_arguments() == [
        "q", "k", "v", "attn_lambda_q1", "attn_lambda_k1", "attn_lambda_q2",
        "attn_lambda_k2", "attn_subln_gamma"]
    args, outs, _ = node.infer_shape(q=(2, 30, H * D), k=(2, 30, G * D),
                                     v=(2, 30, G * D))
    assert args[3:] == [(D,)] * 4 + [(2 * D,)]
    assert outs == [(2, 30, H * D)]

    def infer(**attrs):
        merged = dict(num_heads=H, num_kv_heads=G, depth=3)
        merged.update(attrs)
        return csym.DiffAttention(
            q, mx.sym.Variable("k"), mx.sym.Variable("v"), name="x",
            **merged).infer_shape(q=(2, 30, H * D), k=(2, 30, G * D),
                                  v=(2, 30, G * D))

    with pytest.raises(ValueError, match="must be even"):
        infer(num_heads=6, num_kv_heads=3)
    with pytest.raises(ValueError, match="depth=.* must be the layer's"):
        infer(depth=-1)
    with pytest.raises(ValueError, match="window=-4 must be 0"):
        infer(window=-4)
    with pytest.raises(ValueError, match="does not share query's batch"):
        node.infer_shape(q=(2, 30, H * D), k=(2, 20, G * D),
                         v=(2, 30, G * D))
    with pytest.raises(ValueError, match="has head_dim 8 over 4 heads"):
        node.infer_shape(q=(2, 30, H * D), k=(2, 30, G * 8),
                         v=(2, 30, G * D))


def test_layer_norm_is_an_op_with_gamma_and_beta():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 5, 32)).astype(np.float32) * 3 + 1
    gamma, beta = (rng.normal(size=(32,)).astype(np.float32)
                   for _ in range(2))
    node = csym.LayerNorm(mx.sym.Variable("x"), eps=1e-5, name="ln")
    assert node.list_arguments() == ["x", "ln_gamma", "ln_beta"]
    exe = node.bind(mx.cpu(0), {"x": mx.nd.array(x),
                                "ln_gamma": mx.nd.array(gamma),
                                "ln_beta": mx.nd.array(beta)})
    got = exe.forward()[0].asnumpy()
    mean = x.mean(-1, keepdims=True)
    want = gamma * (x - mean) / np.sqrt(x.var(-1, keepdims=True) + 1e-5) \
        + beta
    _close(got, want, "layer norm", ulps=16)
