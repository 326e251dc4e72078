"""The state-space scan's kernel pair (``ops/kernels/ssd.py::ssd_scan``:
``ssd_fwd_`` / ``ssd_bwd_`` behind a ``custom_vjp``) through the Pallas
interpreter (``interpret=True``: off the TPU the op's own branch is the
einsum form), at small shapes the kernels have tiles for (chunks of 128,
state 128, heads of 32, 64 or 128 on one or two groups, T 256 to 512 and
one T that is not whole chunks, batch 2), against the ``jnp.einsum`` form
(``ops/transformer.py::ssd_scan``, plus the skip ``d x`` that the kernels
take in) and against the token-by-token recurrence: the output and the
gradient of every input. Then ``mamba2`` at such a shape both ways (the
einsum branch a CPU step runs, the interpreted kernels), and what a
training step's program holds of the kernels.

Tolerances as in ``tests/test_nemotron_h.py``: float32 on both sides, so
only the order of summation differs (``_close``: rtol 1e-5 and a few
float32 ulps of the tensor's largest magnitude; more ulps for gradients,
which are long sums through several chunks); bf16 inside the rms band
``test_mamba2_in_bf16_keeps_its_decays_and_state_in_float32`` uses."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import nemotron_h_reference as ref
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops.transformer import ssm
from mxnet_tpu.ops.transformer import mamba2, ssd_scan

BATCH, N, CHUNK, TAPS = 2, 128, 128, 4
INPUTS = ("x", "B", "C", "dt", "a", "skip")


def _close(got, want, what, rtol=1e-5, ulps=8):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _dynamics(rng, heads):
    """``a_log`` and ``dt_bias`` by the published rule."""
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), heads))
    return (np.log(rng.uniform(1, 16, heads)).astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


def _scan_inputs(seed, t, heads, p, groups, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    a_log, dt_bias = _dynamics(rng, heads)
    x, bmat, cmat = (jnp.asarray(rng.randn(BATCH, t, *s), dtype)
                     for s in ((heads, p), (groups, N), (groups, N)))
    dt = jax.nn.softplus(
        jnp.asarray(rng.randn(BATCH, t, heads) + dt_bias, jnp.float32))
    skip = jnp.asarray(1 + 0.2 * rng.randn(heads), jnp.float32)
    return ((x, bmat, cmat, dt, -jnp.exp(jnp.asarray(a_log)), skip),
            jnp.asarray(rng.randn(BATCH, t, heads, p), jnp.float32))


def _kernels(*ins):
    """The kernel pair through the Pallas interpreter (off the TPU
    ``pk.ssd_scan`` is the einsum form unless told so)."""
    return pk.ssd_scan(*ins, CHUNK, interpret=True)


def _einsum_form(x, bmat, cmat, dt, a, skip):
    return (ssd_scan(x, bmat, cmat, dt, a, CHUNK)
            + skip[:, None] * x.astype(jnp.float32))


def _recurrence(x, bmat, cmat, dt, a, skip):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t +
    skip x_t``, a token at a time in float32."""
    heads, groups = x.shape[2], bmat.shape[2]

    def token(state, at):
        x_t, b_t, c_t, dt_t = at
        b_t, c_t = (jnp.repeat(v, heads // groups, axis=1)
                    for v in (b_t, c_t))
        state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(
        token, jnp.zeros((x.shape[0], heads, x.shape[3], N)),
        tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0)
              for v in (x, bmat, cmat, dt)))
    return jnp.moveaxis(y, 0, 1) + skip[:, None] * x.astype(jnp.float32)


def _grads(f, ins, cot):
    return jax.grad(lambda *a: jnp.sum(f(*a) * cot),
                    tuple(range(len(ins))))(*ins)


@pytest.mark.parametrize("t,heads,p,groups", [
    (256, 4, 64, 2), (300, 8, 64, 2), (512, 4, 64, 2), (256, 2, 128, 1),
    (256, 8, 32, 2)],
    ids=["two_chunks", "ragged_last_chunk", "four_chunks",
         "heads_a_lane_row_wide", "four_heads_a_lane_row"])
def test_the_kernels_match_the_einsum_form_and_the_recurrence(
        t, heads, p, groups):
    """Output and the gradient with respect to x, B, C, dt, a and the skip, float32
    to summation order, against both. ``a``'s gradient gets more ulps:
    it sums, over every token, a running sum of differences of two sums
    over a chunk's pairs, and float32 loses digits there in any order
    (against a float64 recurrence at these shapes the einsum form is
    0.6-1.7e-5 of the largest entry off, the kernels 0.8-3.2e-5, the
    float32 recurrence under 2e-6)."""
    assert pk.ssd_takes(heads, p, N, groups, CHUNK, jnp.float32)
    ins, cot = _scan_inputs(0, t, heads, p, groups)
    got = _kernels(*ins)
    assert got.shape == (BATCH, t, heads, p) and got.dtype == jnp.float32
    _close(got, _einsum_form(*ins), "out, einsum form", ulps=16)
    _close(got, _recurrence(*ins), "out, recurrence", ulps=16)
    ours = _grads(_kernels, ins, cot)
    for what, f in (("einsum form", _einsum_form),
                    ("recurrence", _recurrence)):
        for name, g, w in zip(INPUTS, ours, _grads(f, ins, cot)):
            assert g.shape == w.shape and g.dtype == w.dtype, name
            assert float(jnp.abs(w).max()) > 1e-4, name
            _close(g, w, "d%s, %s" % (name, what),
                   ulps=512 if name == "a" else 64)


def test_dropping_the_carried_state_is_caught_on_the_kernel_path():
    """The carried-state test of ``tests/test_nemotron_h.py`` on the
    kernels: chunks run from a zero state differ from the whole scan by
    more than a tenth of y's standard deviation past the first chunk."""
    t, heads, p, groups = 512, 4, 64, 2
    ins, _ = _scan_inputs(2, t, heads, p, groups)
    x, bmat, cmat, dt, a, skip = ins
    whole = _kernels(*ins)
    want = _recurrence(*ins)
    _close(whole, want, "the kernels", ulps=16)
    dropped = jnp.concatenate(
        [_kernels(x[:, s:s + CHUNK], bmat[:, s:s + CHUNK],
                  cmat[:, s:s + CHUNK], dt[:, s:s + CHUNK], a, skip)
         for s in range(0, t, CHUNK)], axis=1)
    _close(dropped[:, :CHUNK], whole[:, :CHUNK], "the first chunk", ulps=16)
    carried = float(jnp.sqrt(jnp.mean(
        (whole - dropped)[:, CHUNK:] ** 2)) / want[:, CHUNK:].std())
    assert carried > 0.1, carried
    with pytest.raises(AssertionError):
        _close(dropped, want, "the carried state dropped", ulps=16)


def test_bf16_operands_leave_the_gradients_where_the_einsum_form_has_them():
    """bf16 x, B and C: both forms round the same operands to bf16 and
    keep decays, state and sums float32, so their outputs and gradients
    differ by roundings of single products, a small share of each
    tensor's rms; the log decay's cotangent (dt, a) is a difference of
    two sums over a chunk's pairs and stays as close only because both
    sums see the same rounded products."""
    ins, cot = _scan_inputs(3, 384, 8, 64, 2, jnp.bfloat16)

    def rms(got, want):
        got, want = (np.asarray(v, np.float64) for v in (got, want))
        return float(np.sqrt(np.mean((got - want) ** 2))
                     / np.sqrt(np.mean(want ** 2)))

    exact = tuple(v.astype(jnp.float32) for v in ins)
    assert rms(_kernels(*ins), _recurrence(*exact)) < 0.004
    ours = _grads(_kernels, ins, cot)
    theirs = _grads(_einsum_form, ins, cot)
    want = _grads(_recurrence, exact, cot)
    for name, g, e, w in zip(INPUTS, ours, theirs, want):
        assert g.dtype == e.dtype, name
        # as near the float32 recurrence as the einsum form is
        assert rms(g, w) < max(1.5 * rms(e, w), 0.004), (
            name, rms(g, w), rms(e, w))


# -- the op at a shape the kernels take ---------------------------------------

HEADS, P, GROUPS = 8, 64, 2
SSM = dict(mamba_num_heads=HEADS, mamba_head_dim=P, n_groups=GROUPS,
           ssm_state_size=N, layer_norm_epsilon=1e-5)
OP_GRADS = ("dproj", "dconv_weight", "dconv_bias", "ddt_bias", "da_log",
            "dd", "dnorm_gamma")


@pytest.fixture(params=["einsum_branch", "kernels_interpreted"])
def scan_path(request, monkeypatch):
    """``mamba2`` both ways a CPU test can run it at such a shape: as a
    step lowered off the TPU runs it (the platform switch's einsum branch
    inside the ``custom_vjp``), and with the kernel pair put through the
    Pallas interpreter (what the TPU's branch computes). The block is one
    ``jax.jit`` a signature, so its cache is emptied round the switch."""
    ssm._mamba2_block.clear_cache()
    if request.param == "kernels_interpreted":
        monkeypatch.setattr(pk.common, "INTERPRET", True)
    yield request.param
    ssm._mamba2_block.clear_cache()


def _op_inputs(seed, t, dtype):
    rng = np.random.RandomState(seed)
    conv_dim = HEADS * P + 2 * GROUPS * N
    a_log, dt_bias = _dynamics(rng, HEADS)

    def draw(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(shift + scale * rng.randn(*shape), dtype)

    return (draw(BATCH, t, HEADS * P + conv_dim + HEADS),
            draw(TAPS, conv_dim, scale=0.3), draw(conv_dim, scale=0.1),
            jnp.asarray(dt_bias, dtype), jnp.asarray(a_log, dtype),
            draw(HEADS, scale=0.2, shift=1.0),
            draw(HEADS * P, scale=0.1, shift=1.0))


def _op(*ins, remat=False):
    return mamba2(*ins, num_heads=HEADS, head_dim=P, state_size=N,
                  num_groups=GROUPS, chunk_size=CHUNK, eps=1e-5, remat=remat)


def test_mamba2_matches_the_recurrence_in_float32(scan_path):
    ins = _op_inputs(0, 300, jnp.float32)
    assert pk.ssd_takes(HEADS, P, N, GROUPS, CHUNK, jnp.float32)
    _close(_op(*ins), ref.mamba2(*ins, SSM), "out", ulps=16)


def test_mamba2_in_bf16_is_inside_the_rms_band(scan_path):
    """The band of ``test_mamba2_in_bf16_keeps_its_decays_and_state_in_
    float32``: against the float32 reference on the same bf16-rounded
    inputs ours is under 0.004 of the output's standard deviation and
    the reference one precision below (decays and the state carried in
    bf16) is over it."""
    ins = _op_inputs(1, 256, jnp.bfloat16)
    want = ref.mamba2(*[a.astype(jnp.float32) for a in ins], SSM)

    def rms(out):
        return float(jnp.sqrt(jnp.mean(
            (out.astype(jnp.float32) - want) ** 2)) / want.std())

    got = _op(*ins)
    assert got.dtype == jnp.bfloat16
    ours, theirs = rms(got), rms(ref.mamba2(*ins, SSM))
    assert ours < 0.004 < theirs, (ours, theirs)


def test_mamba2_in_training_has_the_recurrences_gradients(scan_path):
    """``remat``: the convolution and the gate and norm under their
    checkpoints, the scan on its own residuals, every gradient against
    the token-by-token reference's."""
    ins = _op_inputs(2, 256, jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(_op(*a, remat=True) ** 2),
                   tuple(range(7)))(*ins)
    want = jax.grad(lambda *a: jnp.sum(ref.mamba2(*a, SSM) ** 2),
                    tuple(range(7)))(*ins)
    for name, g, w in zip(OP_GRADS, got, want):
        _close(g, w, name, ulps=64)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_taps_kernel_leaves_mamba2_its_outputs_and_gradients(
        dtype, monkeypatch):
    """``Mamba2`` in training with the convolution as the taps kernel pair
    (through the interpreter, the scan's pair with it) against the op as
    it was, the convolution ``causal_taps`` under its checkpoint
    (``taps_takes`` made to refuse): the output to the last bit, every
    gradient to summation order (bf16: one bf16 ulp of the largest)."""
    ins = _op_inputs(4, 256, dtype)
    every = tuple(range(7))

    def run():
        ssm._mamba2_block.clear_cache()
        out = _op(*ins, remat=True)
        return out, jax.grad(lambda *a: jnp.sum(
            _op(*a, remat=True).astype(jnp.float32) ** 2), every)(*ins)

    monkeypatch.setattr(pk.common, "INTERPRET", True)
    assert pk.taps_takes(HEADS * P + 2 * GROUPS * N, 256, TAPS, dtype,
                         "bias_silu", HEADS * P, ins[0].shape[2])
    out, grads = run()
    monkeypatch.setattr(pk, "taps_takes", lambda *a, **k: False)
    was, were = run()
    ssm._mamba2_block.clear_cache()
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(was, np.float32))
    for name, g, w in zip(OP_GRADS, grads, were):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        if dtype == jnp.bfloat16:
            scale = float(jnp.abs(w.astype(jnp.float32)).max())
            np.testing.assert_allclose(
                np.asarray(g, np.float64), np.asarray(w, np.float64),
                rtol=2.0 ** -6, atol=2.0 ** -7 * scale, err_msg=name)
        else:
            _close(g, w, name, ulps=64)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_a_training_step_holds_each_kernel_once_and_never_interpreted():
    """The gradient's program of the ``Mamba2`` op: ONE forward and one
    backward kernel of the scan, of the causal taps and of the gate and
    norm (each pair keeps its own residuals: no second forward under a
    checkpoint), all for
    Mosaic: the branch for every other platform is the einsum form and
    ``causal_taps``, so a step lowered for the TPU traces no interpreter
    copy of the bodies, and one lowered for the CPU holds no kernel at
    all and runs."""
    ssm._mamba2_block.clear_cache()
    ins = _op_inputs(2, 256, jnp.float32)
    attrs = dict(num_heads=HEADS, head_dim=P, state_size=N,
                 num_groups=GROUPS, chunk_size=CHUNK)

    def loss(*a):
        return jnp.sum(ssm._mamba2(attrs, list(a), True)[0] ** 2)

    grad = jax.jit(jax.grad(loss, tuple(range(7))))
    calls = list(_pallas_calls(grad.trace(*ins).jaxpr.jaxpr))
    names = sorted(str(c.params["name"]) for c in calls)
    assert names == ["gate_norm_bwd_f32_r256_g256_gate_first",
                     "gate_norm_fwd_f32_r256_g256_gate_first",
                     "ssd_bwd_f32_q128_p64_n128",
                     "ssd_fwd_f32_q128_p64_n128",
                     "taps_bwd_f32_t256_c512_k4_bias_silu",
                     "taps_fwd_f32_t256_c512_k4_bias_silu"], names
    assert not any(c.params["interpret"] for c in calls)
    lowered = grad.lower(*ins)
    text = lowered.as_text()
    assert "tpu_custom_call" not in text and "ssd_fwd" not in text
    got = lowered.compile()(*ins)
    want = jax.grad(lambda *a: jnp.sum(ref.mamba2(*a, SSM) ** 2),
                    tuple(range(7)))(*ins)
    for name, g, w in zip(OP_GRADS, got, want):
        _close(g, w, name, ulps=64)


def test_the_kernels_take_whole_lane_rows_only():
    take = lambda *a: pk.ssd_takes(*a)
    assert take(64, 64, 128, 8, 128, jnp.bfloat16)          # the cell's
    assert take(64, 64, 128, 8, 256, jnp.float32)
    assert take(16, 128, 256, 2, 128, jnp.bfloat16)
    for heads, p, n, groups, chunk, dtype in [
            (4, 8, 16, 2, 8, jnp.float32),       # the tiny symbol's
            (64, 64, 128, 8, 64, jnp.bfloat16),   # half a lane row of tokens
            (64, 64, 64, 8, 128, jnp.bfloat16),   # ... of state
            (6, 64, 128, 2, 128, jnp.bfloat16),   # three heads: 1.5 tiles
            (8, 48, 128, 2, 128, jnp.bfloat16),   # heads astride lane rows
            (9, 64, 128, 2, 128, jnp.bfloat16),   # groups do not divide
            (8, 64, 128, 2, 128, jnp.float16),    # not Mosaic's operand
            (128, 128, 1024, 1, 512, jnp.float32)]:  # a step over VMEM
        assert not take(heads, p, n, groups, chunk, dtype), (heads, p, n)
