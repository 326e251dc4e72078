"""``Mamba1`` (``ops/transformer/ssm.py``) against the selective recurrence
itself, and the kernel pair of ``ops/kernels/sscan.py`` through the Pallas
interpreter against the ``jax.numpy`` form.

The oracle here is the token-by-token recurrence in float32 (``naive``):
``S_t[c, n] = exp(dt_t[c] a[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]``,
``y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]``. Both forms of the op
compute it in float32 in another order of summation (an associative scan
a chunk of 64 tokens; the kernel's walk over blocks of 8 tokens with the
state index on sublanes), so float32 results are held to rtol 1e-5 with
an atol of 64 float32 ulps of the tensor's largest entry: measured 2-10
ulps at these sizes, a wrong term is off by a tenth of the scale. In bf16
the operands and ``m`` are rounded to 8 bits of mantissa: the band is an
ulp of bf16 an entry, or a few percent of the tensor's rms for what has
passed through several roundings.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.kernels import sscan
from mxnet_tpu.ops.transformer import mamba1, selective_scan, ssm

N = 16


def naive(x, dt, bmat, cmat, a, skip):
    f32 = jnp.float32
    x, bmat, cmat = x.astype(f32), bmat.astype(f32), cmat.astype(f32)

    def token(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t[..., None] * a) * state
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], -1)

    _, y = jax.lax.scan(
        token, jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), f32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bmat, cmat)))
    return jnp.moveaxis(y, 0, 1) + skip * x


def _operands(seed, b, t, d, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(b, t, d)), dtype),
            jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                           (b, t, d))), jnp.float32),
            jnp.asarray(rng.normal(size=(b, t, N)), dtype),
            jnp.asarray(rng.normal(size=(b, t, N)), dtype),
            -jnp.asarray(np.tile(np.arange(1, N + 1, dtype=np.float32),
                                 (d, 1)) * rng.uniform(0.5, 2, (d, 1)),
                         jnp.float32),
            jnp.asarray(rng.normal(size=(d,)), jnp.float32))


def _close(got, want, what, rtol=1e-5, ulps=64):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _value_and_grads(f, args, weight):
    return jax.value_and_grad(
        lambda *p: jnp.sum(f(*p).astype(jnp.float32) * weight),
        argnums=tuple(range(len(args))))(*args)


NAMES = ("x", "dt", "B", "C", "a", "skip")


@pytest.mark.parametrize("t,remat", [(200, False), (200, True), (64, False),
                                     (7, True)])
def test_the_jnp_form_is_the_recurrence_with_its_gradients(t, remat):
    """T a multiple of the chunk of 64, not one, and shorter than one; with
    and without the chunks computed again in the backward pass."""
    args = _operands(t, 2, t, 24)
    weight = jnp.asarray(np.random.default_rng(1).normal(size=(2, t, 24)),
                         jnp.float32)
    _close(selective_scan(*args, remat=remat), naive(*args), "y")
    _, got = _value_and_grads(
        lambda *p: selective_scan(*p, remat=remat), args, weight)
    _, want = _value_and_grads(naive, args, weight)
    for name, g, w in zip(NAMES, got, want):
        _close(g, w, "d" + name)


@pytest.mark.parametrize("t,d", [(128, 128), (200, 256), (384, 512)])
def test_the_kernel_pair_is_the_jnp_form_in_float32(t, d):
    """Through the interpreter at tile-sized shapes: one chunk of one
    tile; a ragged length over two tiles of 128; three chunks of a tile of
    512 (the cell's width)."""
    args = _operands(t + d, 1 + (d == 128), t, d)
    assert pk.sscan_takes(d, N, jnp.float32)
    assert sscan.sscan_tiles(d, N, jnp.float32) == (128, min(d, 512))
    weight = jnp.asarray(np.random.default_rng(2).normal(
        size=args[0].shape), jnp.float32)

    def kernel(*p):
        return pk.selective_scan(*p, interpret=True)

    _close(kernel(*args), selective_scan(*args), "y")
    _, got = _value_and_grads(kernel, args, weight)
    _, want = _value_and_grads(selective_scan, args, weight)
    for name, g, w in zip(NAMES, got, want):
        _close(g, w, "d" + name)


def test_the_kernel_pair_in_bf16_rounds_x_and_y_only():
    """bf16 operands: the kernels read the same rounded x, B and C as the
    ``jax.numpy`` form and round y once; the state, the decays and every
    cotangent stay float32."""
    args = _operands(5, 1, 256, 128, jnp.bfloat16)
    weight = jnp.asarray(np.random.default_rng(3).normal(
        size=args[0].shape), jnp.float32)

    def kernel(*p):
        return pk.selective_scan(*p, interpret=True)

    got = kernel(*args)
    want = selective_scan(*args)
    assert got.dtype == jnp.bfloat16
    # one rounding of y to bf16: an ulp of 2^-8 of the entry's own size
    rms = float(jnp.sqrt(jnp.mean(jnp.square(want))))
    assert float((jnp.abs(got.astype(jnp.float32) - want)
                  / jnp.maximum(jnp.abs(want), rms)).max()) < 2.0 ** -7
    _, g = _value_and_grads(kernel, args, weight)
    # y rounded as the kernel rounds it, so that both cotangents are the
    # same bf16 values
    _, w = _value_and_grads(
        lambda *p: selective_scan(*p).astype(jnp.bfloat16), args, weight)
    for name, gi, wi in zip(NAMES, g, w):
        assert gi.dtype == wi.dtype, name
        gi, wi = np.asarray(gi, np.float32), np.asarray(wi, np.float32)
        rms = max(np.sqrt(np.mean(np.square(wi))), 1e-6)
        # both sides round dx, dB and dC to bf16 once; the float32
        # cotangents differ by the order of summation alone
        assert (np.abs(gi - wi) / np.maximum(np.abs(wi), rms)).max() < (
            2.0 ** -6 if name in "xBC" else 1e-3), name


def test_the_kernel_has_no_tiles_for_other_shapes():
    assert not pk.sscan_takes(100, N, jnp.float32)     # no whole lane row
    assert not pk.sscan_takes(128, 12, jnp.float32)    # no whole sublanes
    assert not pk.sscan_takes(128, N, jnp.float16)
    with pytest.raises(ValueError, match="no tiles for 100 channels"):
        pk.selective_scan(*_operands(0, 1, 8, 100))


# -- the op ----------------------------------------------------------------
def _block_inputs(seed, b, t, d_in, rank, taps=4, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(scale * rng.normal(size=shape), dtype)

    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (d_in,)))
    return (draw(b, t, 2 * d_in), draw(taps, d_in, scale=0.4),
            draw(d_in, scale=0.1), draw(rank + 2 * N, d_in, scale=0.2),
            draw(d_in, rank, scale=0.3),
            jnp.asarray(dt + np.log(-np.expm1(-dt)), dtype),
            jnp.asarray(np.log(np.tile(np.arange(1, N + 1.0), (d_in, 1))),
                        dtype), draw(d_in))


def mixer_by_hand(proj, conv_w, conv_b, w_x, w_dt, dt_bias, a_log, d_skip):
    """The op's mathematics from the recurrence, float32."""
    f32 = jnp.float32
    proj, conv_w, conv_b, w_x, w_dt, dt_bias, a_log, d_skip = (
        v.astype(f32) for v in (proj, conv_w, conv_b, w_x, w_dt, dt_bias,
                                a_log, d_skip))
    d_in = a_log.shape[0]
    rank = w_dt.shape[1]
    t = proj.shape[1]
    x, z = proj[..., :d_in], proj[..., d_in:]
    padded = jnp.pad(x, ((0, 0), (conv_w.shape[0] - 1, 0), (0, 0)))
    x = jax.nn.silu(conv_b + sum(padded[:, j:j + t] * conv_w[j]
                                 for j in range(conv_w.shape[0])))
    with jax.default_matmul_precision("highest"):
        low = x @ w_x.T
        dt = jax.nn.softplus(low[..., :rank] @ w_dt.T + dt_bias)
    m = naive(x, dt, low[..., rank:rank + N], low[..., rank + N:],
              -jnp.exp(a_log), d_skip)
    return m * jax.nn.silu(z), m


@pytest.fixture(params=["scan", "kernel"])
def scan_path(request, monkeypatch):
    """Both branches of the op: the ``jax.numpy`` form (the kernel's entry
    lowers it off the TPU), and the kernel pair through the interpreter by
    the one seam."""
    ssm._mamba1_block.clear_cache()
    if request.param == "kernel":
        monkeypatch.setattr(pk.common, "INTERPRET", True)
    yield request.param
    ssm._mamba1_block.clear_cache()


@pytest.mark.parametrize("t", [136, 50])
def test_mamba1_matches_the_recurrence_in_float32(scan_path, t):
    ins = _block_inputs(t, 2, t, 128, 5)
    with jax.default_matmul_precision("highest"):
        gated, memory = mamba1(*ins)
    want_gated, want_m = mixer_by_hand(*ins)
    _close(memory, want_m, "memory", ulps=256)
    _close(gated, want_gated, "gated", ulps=256)


def test_mamba1_in_training_has_the_recurrences_gradients(scan_path):
    """Every input's gradient, with the memory read by a SECOND reader
    beside the gate: its cotangent arrives from both."""
    t = 72
    ins = _block_inputs(11, 1, t, 128, 3)
    rng = np.random.default_rng(4)
    w1, w2 = (jnp.asarray(rng.normal(size=(1, t, 128)), jnp.float32)
              for _ in range(2))

    def loss(f):
        def total(*p):
            gated, memory = f(*p)
            return jnp.sum(gated * w1) + jnp.sum(jnp.tanh(memory) * w2)
        return total

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda *p: mamba1(*p, remat=True)),
                       argnums=tuple(range(8)))(*ins)
        want = jax.grad(loss(mixer_by_hand), argnums=tuple(range(8)))(*ins)
    names = ("proj", "conv_weight", "conv_bias", "x_proj_weight",
             "dt_proj_weight", "dt_bias", "a_log", "d")
    for name, g, w in zip(names, got, want):
        _close(g, w, "d" + name, ulps=512)
        assert float(jnp.abs(w).max()) > 1e-6, name


def test_mamba1_in_bf16_is_inside_the_rms_band(scan_path):
    ins = _block_inputs(21, 1, 200, 128, 4, dtype=jnp.bfloat16)
    gated, memory = mamba1(*ins)
    assert gated.dtype == memory.dtype == jnp.bfloat16
    want_gated, want_m = mixer_by_hand(*ins)
    for got, want in ((gated, want_gated), (memory, want_m)):
        rms = float(jnp.sqrt(jnp.mean(jnp.square(want))))
        err = jnp.abs(got.astype(jnp.float32) - want)
        # bf16 x, B, C and low-rank step sizes through a 200-token state
        assert float(jnp.sqrt(jnp.mean(jnp.square(err)))) < 0.03 * rms


def test_the_op_infers_shapes_counts_itself_and_names_what_is_wrong():
    from mxnet_tpu.contrib import symbol as csym

    data = mx.sym.Variable("data")
    node = csym.Mamba1(data, channels=128, state_size=16, dt_rank=5,
                       name="m")
    assert node.list_outputs() == ["m_output", "m_memory"]
    args, outs, _ = node.infer_shape(data=(2, 30, 256))
    assert dict(zip(node.list_arguments(), args)) == {
        "data": (2, 30, 256), "m_conv_weight": (4, 128),
        "m_conv_bias": (128,), "m_x_proj_weight": (37, 128),
        "m_dt_proj_weight": (128, 5), "m_dt_bias": (128,),
        "m_a_log": (128, 16), "m_d": (128,)}
    assert outs == [(2, 30, 128), (2, 30, 128)]
    with pytest.raises(ValueError, match=r"data must be \[batch, time, 256\]"):
        node.infer_shape(data=(2, 30, 200))
    with pytest.raises(ValueError, match="must be positive"):
        csym.Mamba1(data, channels=128, state_size=16, dt_rank=0,
                    name="z").infer_shape(data=(2, 30, 256))

    telemetry.reset()
    telemetry.enable()
    try:
        ssm._mamba1_block.clear_cache()
        mamba1(*_block_inputs(0, 1, 16, 128, 5))
        mamba1(*_block_inputs(0, 1, 16, 24, 2))
        scan = telemetry.REGISTRY.get("ssm.selective_lowerings")
        assert scan.value(channels=128, state=16, dt_rank=5, conv=4,
                          impl="kernel") == 1
        assert scan.value(channels=24, state=16, dt_rank=2, conv=4,
                          impl="scan") == 1
        assert telemetry.total("ssm.selective_lowerings") == 2
    finally:
        telemetry.disable()
        telemetry.reset()


def test_log_of_index_draws_the_s4d_real_rule():
    arr = mx.nd.zeros((6, 16))
    mx.init.LogOfIndex()("layer0_mamba_a_log", arr)
    np.testing.assert_allclose(
        arr.asnumpy(), np.tile(np.log(np.arange(1, 17.0)), (6, 1)),
        rtol=1e-6)
