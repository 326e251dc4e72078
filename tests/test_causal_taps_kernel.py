"""The causal taps' kernel pair (``ops/kernels/taps.py::causal_conv``:
``taps_fwd_`` / ``taps_bwd_`` behind a ``custom_vjp``) through the Pallas
interpreter (``interpret=True``: off the TPU the op's own branch is the
``jax.numpy`` form), against ``causal_taps`` with its epilogue
(``taps.plain_form``) and ``jax.grad`` of it, for the three forms (bias +
``silu``: ``Mamba2``; ``silu``: ``GatedDeltaNet``; the two gates:
``ShortConv``): taps 3 and 4, a tile boundary inside the sequence (the
halo both ways), the first ``taps - 1`` tokens, batch 2, a column window
of a wider array, a width that is no multiple of 128 taken whole, bf16
and float32. Then what ``taps_takes`` refuses, the counter the call sites
keep, and what a training step's program holds of the kernels.

Tolerances: the forward sums the same float32 terms in the same order, so
float32 outputs are equal to the last bit and bf16 ones after the one
cast (both sides under ``jax.jit``, as the ops' blocks are: XLA contracts
a product and a sum alike on both); gradients as
``tests/test_ssd_scan_kernel.py``'s (``_close``: rtol 1e-5 and a few
float32 ulps of the tensor's largest magnitude), bf16 ones inside one
bf16 ulp of it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import delta, ssm
from mxnet_tpu.ops.kernels import taps
from mxnet_tpu.ops.transformer import short_conv

F32, BF16 = jnp.float32, jnp.bfloat16


def _close(got, want, what, rtol=1e-5, ulps=8, eps=np.finfo(np.float32).eps):
    assert got.shape == want.shape and got.dtype == want.dtype, what
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _inputs(seed, form, taps_, dtype, batch, t, channels, offset=0,
            width=None):
    rng = np.random.RandomState(seed)
    width = width or (3 * channels if form == "gates" else channels)
    return (jnp.asarray(rng.randn(batch, t, width), dtype),
            jnp.asarray(rng.uniform(-1, 1, (taps_, channels)), dtype),
            jnp.asarray(0.1 * rng.randn(channels), dtype)
            if form == "bias_silu" else None,
            jnp.asarray(rng.randn(batch, t, channels), dtype))


def _pair(form, offset, channels):
    """(the kernels interpreted, the ``jax.numpy`` form) of one signature
    (src, weight, bias)."""
    def kernels(src, w, bias):
        return pk.causal_conv(src, w, bias, form=form, offset=offset,
                              channels=channels, interpret=True)

    def plain(src, w, bias):
        return taps.plain_form(
            src, w.astype(F32),
            None if bias is None else bias.astype(F32).reshape(1, -1),
            form=form, offset=offset, channels=channels)

    return kernels, plain


def _grads(f, ins, cot):
    ins = tuple(v for v in ins if v is not None)
    wrap = (lambda *a: f(*a)) if len(ins) == 3 else (lambda s, w: f(s, w, None))
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(wrap(*a).astype(F32) * cot.astype(F32)),
        tuple(range(len(ins)))))(*ins)


CASES = {
    # form, taps, dtype, batch, T, channels, offset, width
    "mamba2_window_three_tiles": ("bias_silu", 4, F32, 2, 384, 256, 256, 640),
    "mamba2_window_bf16": ("bias_silu", 4, BF16, 2, 384, 256, 256, 640),
    "mamba2_taps3": ("bias_silu", 3, F32, 1, 256, 128, 128, 384),
    "delta_rule_three_tiles": ("silu", 4, F32, 2, 384, 256, 0, None),
    "delta_rule_bf16": ("silu", 4, BF16, 2, 384, 256, 0, None),
    "delta_rule_2880_whole": ("silu", 4, F32, 1, 256, 2880, 0, None),
    "delta_rule_2880_bf16": ("silu", 4, BF16, 1, 128, 2880, 0, None),
    "delta_rule_taps3_columns": ("silu", 3, F32, 1, 128, 3072, 0, None),
    "short_conv_three_tiles": ("gates", 3, F32, 2, 384, 128, 0, None),
    "short_conv_bf16": ("gates", 3, BF16, 2, 384, 128, 0, None),
    "short_conv_taps4_lane_steps": ("gates", 4, F32, 1, 256, 640, 0, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_pair_matches_the_jnp_form(case):
    """Forward to the last bit (float32; bf16 after its one cast) and the
    gradient with respect to the input, the taps and the bias: the input's
    in the input's type, the whole array's where the op reads a window of
    it (zero outside the window)."""
    form, taps_, dtype, batch, t, channels, offset, width = CASES[case]
    src, w, bias, cot = _inputs(0, form, taps_, dtype, batch, t, channels,
                                offset, width)
    tiles = taps.taps_tiles(channels, t, taps_, dtype, form, offset,
                            src.shape[2])
    assert tiles is not None and t % tiles[0] == 0
    kernels, plain = _pair(form, offset, channels)
    got, want = jax.jit(kernels)(src, w, bias), jax.jit(plain)(src, w, bias)
    assert got.shape == (batch, t, channels) and got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    ours = _grads(kernels, (src, w, bias), cot)
    theirs = _grads(plain, (src, w, bias), cot)
    bf16 = dtype == BF16
    for name, g, e in zip(("dsrc", "dweight", "dbias"), ours, theirs):
        assert float(jnp.abs(e.astype(F32)).max()) > 1e-3, name
        if bf16:
            _close(g, e, name, rtol=2.0 ** -7, ulps=1, eps=2.0 ** -8)
        else:
            _close(g, e, name, ulps=64)
    if offset:
        outside = np.asarray(ours[0].astype(F32))
        assert not outside[..., :offset].any()
        assert not outside[..., offset + channels:].any()


@pytest.mark.parametrize("form", taps.FORMS)
def test_dropping_the_rows_carried_between_tiles_is_caught(form):
    """Each tile run from zero history differs from the whole in its first
    ``taps - 1`` rows (the causal halo), and its gradient in the LAST
    ``taps - 1`` rows of the tile before (the anti-causal one) and, through
    the moved sums, in those first rows again; everywhere else they
    agree."""
    t, tile, channels, taps_ = 256, 128, 128, 4
    src, w, bias, cot = _inputs(1, form, taps_, F32, 1, t, channels)
    assert taps.taps_tiles(channels, tile, taps_, F32, form, 0,
                           src.shape[2])[0] == tile
    kernels, plain = _pair(form, 0, channels)

    def by_tile(src, w, bias):
        return jnp.concatenate([kernels(src[:, s:s + tile], w, bias)
                                for s in range(0, t, tile)], axis=1)

    whole = jax.jit(kernels)(src, w, bias)
    cut = jax.jit(by_tile)(src, w, bias)
    differ = np.asarray(whole != cut).any(axis=(0, 2))
    assert differ[tile:tile + taps_ - 1].all()
    assert not np.delete(differ, range(tile, tile + taps_ - 1)).any()
    np.testing.assert_array_equal(np.asarray(whole),
                                  np.asarray(jax.jit(plain)(src, w, bias)))
    d_whole = _grads(kernels, (src, w, bias), cot)[0]
    d_cut = _grads(by_tile, (src, w, bias), cot)[0]
    differ = np.asarray(jnp.abs(d_whole - d_cut) > 1e-6).any(axis=(0, 2))
    assert differ[tile - taps_ + 1:tile].all()
    assert not np.delete(differ, range(tile - taps_ + 1,
                                       tile + taps_ - 1)).any()
    _close(d_whole, _grads(plain, (src, w, bias), cot)[0], "dsrc", ulps=64)


@pytest.mark.parametrize("taps_", [3, 4])
def test_the_first_tokens_read_zeros_before_the_sequence(taps_):
    """With tap 0 alone alive the first ``taps - 1`` outputs are
    ``silu(bias)``: what the tap reads there is the zero history, not the
    end of another tile or batch row."""
    src, w, bias, _ = _inputs(2, "bias_silu", taps_, F32, 2, 256, 128)
    first = jnp.zeros_like(w).at[0].set(w[0])
    kernels, plain = _pair("bias_silu", 0, 128)
    got = jax.jit(kernels)(src, first, bias)
    want = jnp.broadcast_to(jax.nn.silu(bias), (2, taps_ - 1, 128))
    _close(got[:, :taps_ - 1], want, "the first tokens", ulps=2)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jax.jit(plain)(src, first, bias)))
    assert float(jnp.abs(got[:, taps_ - 1:] - jax.nn.silu(bias)).max()) > 0.1


TAKES = {
    # channels, time, taps, dtype, form, offset, width -> tiles or None
    "nemotron_mamba2": ((6144, 8192, 4, BF16, "bias_silu", 4096, 10304),
                        (512, 2048)),
    "olmo_hybrid_query_key_whole": ((2880, 4096, 4, BF16, "silu", 0, 2880),
                                    (512, 2880)),
    "olmo_hybrid_value": ((5760, 4096, 4, BF16, "silu", 0, 5760),
                          (512, 1920)),
    "lfm2_short_conv": ((2048, 8192, 3, BF16, "gates", 0, 6144),
                        (256, 2048)),
    "float32": ((256, 256, 4, F32, "silu", 0, 256), (256, 256)),
    "narrow_divisor_taken_whole": ((2944, 1024, 4, BF16, "silu", 0, 2944),
                                   (512, 2944)),
    "time_no_tile_divides": ((256, 200, 4, F32, "silu", 0, 256), None),
    "time_under_a_tile": ((256, 64, 4, F32, "silu", 0, 256), None),
    "ragged_width_in_a_window": ((2880, 4096, 4, BF16, "silu", 0, 5760),
                                 None),
    "ragged_width_at_an_offset": ((200, 256, 4, F32, "bias_silu", 128, 328),
                                  None),
    "offset_off_the_lane_rows": ((256, 256, 4, F32, "bias_silu", 64, 320),
                                 None),
    "taps_outreach_the_halo": ((256, 256, 10, F32, "silu", 0, 256), None),
    "float16": ((256, 256, 4, jnp.float16, "silu", 0, 256), None),
    "gates_not_three_thirds": ((128, 256, 3, F32, "gates", 0, 512), None),
    "gates_thirds_astride_lane_rows": ((200, 256, 3, F32, "gates", 0, 600),
                                       None),
    "a_step_over_vmem": ((49152, 128, 3, F32, "gates", 0, 147456), None),
    "an_unknown_form": ((256, 256, 4, F32, "relu", 0, 256), None),
}


@pytest.mark.parametrize("case", list(TAKES))
def test_taps_takes_decides_from_the_shapes(case):
    args, tiles = TAKES[case]
    assert taps.taps_tiles(*args) == tiles
    assert pk.taps_takes(*args) == (tiles is not None)
    if tiles is not None:
        channels, _, taps_, dtype, form = args[:5]
        assert taps.taps_vmem_bytes(
            tiles[0], tiles[1], taps_, jnp.dtype(dtype).itemsize,
            form) <= pk.common.VMEM_RAISED_LIMIT
        assert args[5] % tiles[1] == 0 and channels % tiles[1] == 0


def test_a_refused_call_says_so():
    src, w, _, _ = _inputs(3, "silu", 4, F32, 1, 200, 256)
    with pytest.raises(ValueError, match="taps_takes"):
        pk.causal_conv(src, w, form="silu", interpret=True)
    with pytest.raises(ValueError, match="taps_takes"):
        pk.causal_conv(src[:, :128], w, form="bias_silu", interpret=True)


# -- the call sites: the counter, and what the ops run ------------------------

def _mamba2(proj, conv_weight, remat=False):
    heads, p, n, groups = 2, 64, 128, 1
    conv_dim = heads * p + 2 * groups * n
    rng = np.random.RandomState(7)
    rest = (jnp.asarray(0.1 * rng.randn(conv_dim), F32),
            jnp.asarray(rng.randn(heads), F32),
            jnp.asarray(np.log(rng.uniform(1, 16, heads)), F32),
            jnp.ones(heads, F32), jnp.ones(heads * p, F32))
    return tr.mamba2(proj, conv_weight, *rest, heads, p, n, groups, 128, 1e-5,
                     remat=remat)


def _gated_delta_net(qkvg, conv_weight, remat=False):
    heads, dk, dv = 2, 64, 128
    rng = np.random.RandomState(8)
    t = qkvg.shape[1]
    q, k, v, g = jnp.split(qkvg, [heads * dk, 2 * heads * dk,
                                  2 * heads * dk + heads * dv], axis=2)
    a, b = (jnp.asarray(rng.randn(1, t, heads), F32) for _ in range(2))
    rest = (jnp.asarray(np.log(rng.uniform(1, 16, heads)), F32),
            jnp.asarray(rng.randn(heads), F32), jnp.ones(dv, F32))
    return tr.gated_delta_net(q, k, v, g, a, b, conv_weight, *rest, heads,
                              64, 1e-6, remat=remat)


def _site_inputs(site, t, seed=4):
    rng = np.random.RandomState(seed)
    width, channels, taps_ = {"mamba2": (2 * 128 + 256 + 2, 384, 4),
                              "gated_delta_net": (2 * 128 + 2 * 256, 512, 4),
                              "short_conv": (3 * 128, 128, 3)}[site]
    return (jnp.asarray(rng.randn(1, t, width), F32),
            jnp.asarray(rng.uniform(-1, 1, (taps_, channels)) * 0.5, F32))


SITES = {"mamba2": (_mamba2, [dict(channels=384, taps=4)]),
         "gated_delta_net": (_gated_delta_net,
                             [dict(channels=128, taps=4),
                              dict(channels=128, taps=4),
                              dict(channels=256, taps=4)]),
         "short_conv": (short_conv, [dict(channels=128, taps=3)])}


@pytest.fixture
def registry():
    telemetry.reset()
    telemetry.enable()
    yield telemetry.REGISTRY
    telemetry.disable()
    telemetry.reset()


@pytest.mark.parametrize("site", list(SITES))
def test_a_call_site_counts_each_convolved_array_once_a_lowering(
        site, registry):
    """``causal_taps.lowerings``: one a convolved array, node and lowering
    (``GatedDeltaNet`` convolves three), labelled with the site, the
    width, the taps and which form runs; a time length no tile divides is
    the ``jax.numpy`` form's; nothing a step."""
    op, arrays = SITES[site]
    ssm._mamba2_block.clear_cache()
    delta._gated_delta_block.clear_cache()
    compiled = jax.jit(op).lower(*_site_inputs(site, 256)).compile()
    count = registry.get("causal_taps.lowerings")
    assert telemetry.total("causal_taps.lowerings") == len(arrays)
    for labels in {tuple(sorted(a.items())) for a in arrays}:
        n = sum(tuple(sorted(a.items())) == labels for a in arrays)
        assert count.value(site=site, impl="kernel", **dict(labels)) == n
    for _ in range(2):
        compiled(*_site_inputs(site, 256))
    assert telemetry.total("causal_taps.lowerings") == len(arrays)
    jax.jit(op).lower(*_site_inputs(site, 200))
    assert telemetry.total("causal_taps.lowerings") == 2 * len(arrays)
    for a in arrays:
        assert count.value(site=site, impl="jnp", **a) >= 1
    if site == "short_conv":
        sconv = registry.get("sconv.lowerings")
        assert sconv.value(channels=128, taps=3, impl="kernel") == 1
        assert sconv.value(channels=128, taps=3, impl="jnp") == 1


def test_a_time_length_no_tile_divides_runs_the_jnp_form():
    proj, w = _site_inputs("short_conv", 200)
    got = short_conv(proj, w, remat=True)
    f32 = np.float64
    z = np.asarray(proj[..., :128], f32) * np.asarray(proj[..., 256:], f32)
    want = np.zeros_like(z)
    for j in range(3):
        back = 2 - j
        want[:, back:] += np.asarray(w[j], f32) * z[:, :200 - back]
    _close(got, jnp.asarray(np.asarray(proj[..., 128:256], f32) * want, F32),
           "out", ulps=8)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("site", ["short_conv", "mamba2"])
def test_a_training_step_holds_each_kernel_once_and_never_interpreted(site):
    """The gradient's program of the op in training: ONE forward and one
    backward taps kernel (the pair keeps the op's inputs and recomputes in
    VMEM: no second forward under a checkpoint), both for Mosaic; a step
    lowered for the CPU holds no kernel at all, runs, and has the
    ``jax.numpy`` form's gradients."""
    ssm._mamba2_block.clear_cache()
    op = SITES[site][0]
    ins = _site_inputs(site, 256)

    def loss(*a):
        return jnp.sum(op(*a, remat=True).astype(F32) ** 2)

    grad = jax.jit(jax.grad(loss, (0, 1)))
    calls = [c for c in _pallas_calls(grad.trace(*ins).jaxpr.jaxpr)
             if str(c.params["name"]).startswith("taps_")]
    form, tiles = (("gates", "t256_c128_k3") if site == "short_conv"
                   else ("bias_silu", "t256_c128_k4"))
    assert sorted(str(c.params["name"]) for c in calls) == [
        "taps_%s_f32_%s_%s" % (which, tiles, form)
        for which in ("bwd", "fwd")]
    assert not any(c.params["interpret"] for c in calls)
    lowered = grad.lower(*ins)
    text = lowered.as_text()
    assert "tpu_custom_call" not in text and "taps_fwd" not in text
    got = lowered.compile()(*ins)
    if site == "short_conv":
        want = jax.grad(lambda *a: jnp.sum(tr.gated_taps(*a) ** 2),
                        (0, 1))(*ins)
        for name, g, w in zip(("dproj", "dconv_weight"), got, want):
            _close(g, w, name, ulps=64)
