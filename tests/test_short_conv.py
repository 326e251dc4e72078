"""``ShortConv`` (the double-gated short causal convolution of the LFM2
family's ``conv`` mixer) against a loop over tokens, and the taps it
shares with ``Mamba2`` and ``GatedDeltaNet``.

The op: ``proj`` [B, T, 3 H] = ``B | C | x``; ``z = B * x``; ``c_t =
sum_j w_j z_{t - 2 + j}`` (tap 2 meets the current token, ``z`` zero
before the sequence); out ``C * c``. The numpy form below computes that
one token and one tap after another in float64. Float32 inputs: both
sides are exact products and a sum of three terms, so a few float32 ulps
of the tensor's scale. bf16 inputs: the op's gates and sum are float32,
so against the float64 form on the same bf16-rounded inputs the only
error is the ONE rounding of the result; the form computed in bf16
throughout rounds ``z``, every product and every partial sum too and is
more than twice as far off (the bf16 test gives both readings).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.ops.transformer import causal_taps, short_conv

B, H, TAPS = 2, 16, 3
NAMES = ("proj", "conv_weight")


def _inputs(seed, t, dtype=jnp.float32, taps=TAPS):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, t, 3 * H), dtype),
            jnp.asarray(rng.uniform(-1, 1, (taps, H)) * taps ** -0.5, dtype),
            jnp.asarray(rng.randn(B, t, H), jnp.float32))


def _loop(proj, w):
    """One token and one tap after another, float64."""
    proj, w = np.asarray(proj, np.float64), np.asarray(w, np.float64)
    b, t, _ = proj.shape
    taps, h = w.shape
    gate_b, gate_c, x = proj[..., :h], proj[..., h:2 * h], proj[..., 2 * h:]
    out = np.zeros((b, t, h))
    for i in range(t):
        for j in range(taps):
            src = i - (taps - 1) + j
            if src >= 0:
                out[:, i] += w[j] * gate_b[:, src] * x[:, src]
        out[:, i] *= gate_c[:, i]
    return out


def _close(got, want, what, rtol=1e-5, ulps=8):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


# 37: a length no tile of any size divides; 1 and 2: shorter than the taps
@pytest.mark.parametrize("t", [37, 32, 2, 1])
def test_forward_and_every_gradient_match_the_loop_over_tokens(t):
    proj, w, cot = _inputs(1, t)
    _close(short_conv(proj, w), _loop(proj, w), "output")

    def loss(proj, w):
        return jnp.sum(short_conv(proj, w, remat=True) * cot)

    got = jax.grad(loss, (0, 1))(proj, w)
    # the loop's gradients by its own linearity: d out / d w_j and the
    # three gates, one term at a time
    p, wn, c = (np.asarray(v, np.float64) for v in (proj, w, cot))
    gb, gc, x = p[..., :H], p[..., H:2 * H], p[..., 2 * H:]
    d_w = np.zeros_like(wn)
    d_z = np.zeros_like(gb)
    conv = np.zeros_like(gb)
    for i in range(t):
        for j in range(TAPS):
            src = i - (TAPS - 1) + j
            if src >= 0:
                zsrc = gb[:, src] * x[:, src]
                conv[:, i] += wn[j] * zsrc
                d_w[j] += np.sum(c[:, i] * gc[:, i] * zsrc, axis=0)
                d_z[:, src] += c[:, i] * gc[:, i] * wn[j]
    want_proj = np.concatenate([d_z * x, c * conv, d_z * gb], axis=-1)
    _close(got[0], want_proj, "d proj", ulps=16)
    _close(got[1], d_w, "d conv_weight", ulps=32)
    assert np.abs(d_w).max() > 1e-3


def test_remat_changes_no_value():
    proj, w, cot = _inputs(2, 37)

    def loss(remat):
        return lambda *a: jnp.sum(short_conv(*a, remat=remat) * cot)

    plain = jax.grad(loss(False), (0, 1))(proj, w)
    again = jax.grad(loss(True), (0, 1))(proj, w)
    for name, a, b in zip(NAMES, plain, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


def test_the_output_at_t_is_unmoved_by_what_comes_after_t():
    proj, w, _ = _inputs(3, 24)
    base = np.asarray(short_conv(proj, w))
    later = proj.at[:, 10:].set(proj[:, 10:] + 5.0)
    moved = np.asarray(short_conv(later, w))
    np.testing.assert_array_equal(moved[:, :10], base[:, :10])
    assert np.abs(moved[:, 10:] - base[:, 10:]).max() > 1.0


@pytest.mark.parametrize("tap", range(TAPS))
def test_tap_two_meets_the_current_token(tap):
    """With one tap alive the output is ``C_t w z_{t - (2 - tap)}``: tap
    2 reads the current token, tap 0 the token two back, and the first
    ``2 - tap`` outputs read the zeros before the sequence."""
    proj, w, _ = _inputs(4, 12)
    one = jnp.zeros_like(w).at[tap].set(w[tap])
    got = np.asarray(short_conv(proj, one), np.float64)
    p = np.asarray(proj, np.float64)
    z, gate_c = p[..., :H] * p[..., 2 * H:], p[..., H:2 * H]
    back = TAPS - 1 - tap
    want = np.zeros_like(z)
    want[:, back:] = z[:, :z.shape[1] - back]
    _close(got, gate_c * np.asarray(one[tap], np.float64) * want,
           "tap %d" % tap)
    assert not got[:, :back].any()


def test_in_bf16_the_gates_and_the_sum_stay_float32():
    """bf16 inputs: against the float64 loop on the same bf16-rounded
    inputs the op is off by the rounding of its result alone: rms error
    over the rms of the result 0.00146-0.00170 on seeds 0..4 (the first
    reading); the same loop with ``z``, every product and every partial
    sum rounded to bf16 reads 0.00355-0.00386 (the second). The limit
    0.0025 lies between, 1.47 times from the first and 1.42 from the
    second."""
    def bf(a):
        return np.asarray(jnp.asarray(a, jnp.bfloat16), np.float64)

    def rms(a):
        return float(np.sqrt(np.mean(a * a)))

    for seed in range(5):
        proj, w, _ = _inputs(seed, 64, jnp.bfloat16)
        want = _loop(proj, w)
        got = short_conv(proj, w)
        assert got.dtype == jnp.bfloat16
        err = rms(np.asarray(got, np.float64) - want) / rms(want)
        p, wn = np.asarray(proj, np.float64), np.asarray(w, np.float64)
        z = bf(p[..., :H] * p[..., 2 * H:])
        acc = np.zeros_like(z)
        for j in range(TAPS):
            back = TAPS - 1 - j
            shifted = np.zeros_like(z)
            shifted[:, back:] = z[:, :z.shape[1] - back]
            acc = bf(acc + bf(wn[j] * shifted))
        below = rms(bf(p[..., H:2 * H] * acc) - want) / rms(want)
        assert err < 0.0025 < below, (seed, err, below)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_taps_kernel_leaves_the_op_its_outputs_and_gradients(
        dtype, monkeypatch):
    """``ShortConv`` in training at a shape the kernel family takes (128
    channels, three time tiles), the pair through the Pallas interpreter,
    against ``gated_taps`` under its checkpoint (the op as it was): the
    output to the last bit, ``dproj`` (all three thirds, written by the
    backward kernel itself) and the taps' gradient to summation order
    (bf16: one bf16 ulp of the largest)."""
    from mxnet_tpu.ops import kernels as pk
    from mxnet_tpu.ops.transformer import gated_taps

    rng = np.random.RandomState(7)
    proj = jnp.asarray(rng.randn(B, 384, 3 * 128), dtype)
    w = jnp.asarray(rng.uniform(-1, 1, (TAPS, 128)) * TAPS ** -0.5, dtype)
    cot = jnp.asarray(rng.randn(B, 384, 128), jnp.float32)
    assert pk.taps_takes(128, 384, TAPS, dtype, "gates", 0, 3 * 128)
    monkeypatch.setattr(pk.common, "INTERPRET", True)

    def loss(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * cot)

    op = lambda *a: short_conv(*a, remat=True)
    was = jax.checkpoint(gated_taps)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(op)(proj, w), np.float32),
        np.asarray(jax.jit(was)(proj, w), np.float32))
    got = jax.jit(jax.grad(loss(op), (0, 1)))(proj, w)
    want = jax.jit(jax.grad(loss(was), (0, 1)))(proj, w)
    for name, g, e in zip(NAMES, got, want):
        assert g.dtype == e.dtype and g.shape == e.shape, name
        if dtype == jnp.bfloat16:
            scale = float(jnp.abs(e.astype(jnp.float32)).max())
            np.testing.assert_allclose(
                np.asarray(g, np.float64), np.asarray(e, np.float64),
                rtol=2.0 ** -7, atol=2.0 ** -8 * scale, err_msg=name)
        else:
            _close(g, e, "d " + name, ulps=64)


def test_the_scopes_and_the_counter():
    """Through the symbol and the executor: the node's ops are traced
    under ``sconv/<node>`` and, inside it, ``gate_in``, ``conv1d`` and
    ``gate_out`` (never ``conv``, the conv nets' class); the call site
    counts itself once a lowering."""
    from mxnet_tpu.contrib import symbol as csym

    sym = csym.ShortConv(mx.sym.Variable("data"), conv_kernel=TAPS,
                         name="layer0_conv")
    assert sym.list_arguments() == ["data", "layer0_conv_conv_weight"]
    shapes, out, _ = sym.infer_shape(data=(B, 10, 3 * H))
    assert shapes == [(B, 10, 3 * H), (TAPS, H)] and out == [(B, 10, H)]
    with pytest.raises(Exception, match="3 \\* channels"):
        sym.infer_shape(data=(B, 10, 3 * H + 1))
    assert mx.executor.op_class("_contrib_ShortConv") == "sconv"

    proj, w, _ = _inputs(5, 10)
    telemetry.reset()
    telemetry.enable()
    try:
        text = jax.jit(jax.grad(lambda p, w: jnp.sum(
            short_conv(p, w, remat=True)), (0, 1))).lower(
                proj, w).as_text(debug_info=True)
        counter = telemetry.REGISTRY.get("sconv.lowerings")
        assert counter.value(channels=H, taps=TAPS, impl="jnp") == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    for scope in ("gate_in", "conv1d", "gate_out"):
        assert "/%s/" % scope in text or "/%s\"" % scope in text, scope
    assert "/conv/" not in text

    exe = sym.simple_bind(mx.cpu(0), data=(B, 10, 3 * H))
    exe.arg_dict["data"][:] = np.asarray(proj)
    exe.arg_dict["layer0_conv_conv_weight"][:] = np.asarray(w)
    exe.forward(is_train=False)
    _close(exe.outputs[0].asnumpy(), _loop(proj, w), "through the executor")


# -- the taps Mamba2 and GatedDeltaNet share with it -------------------------

def _mamba2_conv_before(x, conv_weight, conv_bias):
    """``_mamba2_block``'s ``conv1d`` closure as it stood before the
    helper, to the letter (without its silu and cast)."""
    f32 = jnp.float32
    taps, t = conv_weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(f32)
    w = conv_weight.astype(f32)
    acc = conv_bias.astype(f32)
    for j in range(taps):
        acc = acc + padded[:, j:j + t] * w[j]
    return acc


def _gdn_conv_before(x, w):
    """``_gated_delta_block``'s ``conv1d`` closure as it stood before
    the helper, to the letter (without its silu and cast)."""
    f32 = jnp.float32
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(f32)
    w = w.astype(f32)
    acc = padded[:, :t] * w[0]
    for j in range(1, taps):
        acc = acc + padded[:, j:j + t] * w[j]
    return acc


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("caller", ["mamba2", "gated_delta_net"])
def test_the_shared_taps_give_the_two_old_callers_their_bits(caller, dtype):
    """Values and both gradients, bit for bit, jitted as the blocks are:
    the helper sums the same terms in the same order (the bias first
    where there is one)."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(B, 37, 24), dtype)
    w = jnp.asarray(0.5 * rng.randn(4, 24), dtype)
    bias = jnp.asarray(0.1 * rng.randn(24), dtype)
    if caller == "mamba2":
        before = lambda x, w: jax.nn.silu(_mamba2_conv_before(x, w, bias))
        after = lambda x, w: jax.nn.silu(causal_taps(x, w, bias))
    else:
        before = lambda x, w: jax.nn.silu(_gdn_conv_before(x, w))
        after = lambda x, w: jax.nn.silu(causal_taps(x, w))
    for f, g in ((before, after),
                 (jax.grad(lambda *a: jnp.sum(before(*a) ** 2), (0, 1)),
                  jax.grad(lambda *a: jnp.sum(after(*a) ** 2), (0, 1)))):
        was, now = jax.jit(f)(x, w), jax.jit(g)(x, w)
        for a, b in zip(jax.tree_util.tree_leaves(was),
                        jax.tree_util.tree_leaves(now)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
