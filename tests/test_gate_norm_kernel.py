"""The gate and grouped RMSNorm's kernel pair
(``ops/kernels/gate_norm.py::gated_rms_norm``: ``gate_norm_fwd_`` /
``gate_norm_bwd_`` behind a ``custom_vjp``) through the Pallas interpreter
(``interpret=True``: off the TPU the op's own branch is the ``jax.numpy``
form), against the ``gate_norm`` closures of ``_mamba2_block`` and
``_gated_delta_block`` (``gate_norm.plain_form``) and ``jax.grad`` of
them, for the three forms (``gate_first``: ``Mamba2``; ``norm_first``:
``GatedDeltaNet``, its ``o`` head-major; ``token_major``: the same order
on ``o`` token-major as the channel rule's pair writes it, 32 heads of
128 under a sigmoid and under a silu): the cells' groups (8 x 512, 1 x
2048 under a multiplier, 30 x 192 paired, 32 x 128), batch 2, two row
tiles, the gate a window of a wider array, bf16 and float32. Then what
``gate_norm_takes`` refuses, the counter the call sites keep, and what a
training step's program holds of the kernels.

Tolerances: the forward makes the same float32 values in the same order
but for the squares' sum, so a result is the form's to one ulp of its
type; gradients as ``tests/test_causal_taps_kernel.py``'s (``_close``),
bf16 ones inside one bf16 ulp of the tensor's largest magnitude. Gamma's
gradient in bf16 is compared with the form's on float32 values: the form
sums bf16 products where the kernel sums float32 ones."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import delta, ssm
from mxnet_tpu.ops.kernels import gate_norm

F32, BF16 = jnp.float32, jnp.bfloat16


def _close(got, want, what, rtol=1e-5, ulps=8, eps=np.finfo(np.float32).eps):
    assert got.shape == want.shape and got.dtype == want.dtype, what
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _inputs(seed, form, groups, width, dtype, batch, t, offset=0, extra=0):
    """y as the core leaves it, the gate's array, gamma, the result's
    cotangent."""
    rng = np.random.RandomState(seed)
    columns = groups * width
    shape = ((batch, groups, t, width) if form == "norm_first"
             else (batch, t, columns))
    return (jnp.asarray(rng.randn(*shape), F32),
            jnp.asarray(rng.randn(batch, t, offset + columns + extra), dtype),
            jnp.asarray(1 + 0.2 * rng.randn(
                columns if form == "gate_first" else width), dtype),
            jnp.asarray(rng.randn(batch, t, columns), dtype))


def _pair(form, groups, width, scale=None, offset=0, act="silu"):
    """(the kernels interpreted, the ``jax.numpy`` form) of one signature
    (y, src, gamma)."""
    def kernels(y, src, gamma):
        return pk.gated_rms_norm(y, src, gamma, form=form, eps=1e-5,
                                 groups=groups, scale=scale, offset=offset,
                                 act=act, interpret=True)

    def plain(y, src, gamma):
        return gate_norm.plain_form(y, src, gamma, form=form, width=width,
                                    eps=1e-5, scale=scale, offset=offset,
                                    act=act)

    return kernels, plain


def _grads(f, ins, cot):
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a).astype(F32) * cot.astype(F32)),
        (0, 1, 2)))(*ins)


CASES = {
    # form, groups, width, dtype, batch, T, scale, offset, columns after
    # (and the gate's activation where it is not silu)
    "nemotron_groups": ("gate_first", 8, 512, F32, 2, 256, None, 0, 0),
    "nemotron_groups_bf16": ("gate_first", 8, 512, BF16, 2, 256, None, 0, 0),
    "falcon_h1_one_group_scaled": ("gate_first", 1, 2048, F32, 2, 256, 0.7,
                                   0, 0),
    "falcon_h1_one_group_scaled_bf16": ("gate_first", 1, 2048, BF16, 2, 256,
                                        0.7, 0, 0),
    "a_window_of_in_proj": ("gate_first", 2, 128, F32, 2, 256, None, 0, 200),
    "a_window_at_an_offset_bf16": ("gate_first", 2, 128, BF16, 1, 256, None,
                                   256, 72),
    "olmo_hybrid_heads_paired": ("norm_first", 30, 192, F32, 2, 256, None, 0,
                                 0),
    "olmo_hybrid_heads_paired_bf16": ("norm_first", 30, 192, BF16, 2, 256,
                                      None, 0, 0),
    "heads_of_whole_lane_rows": ("norm_first", 3, 128, BF16, 2, 256, None, 0,
                                 0),
    # the Kimi Linear cell's heads (two column tiles of 16), token-major
    "kimi_heads_sigmoid": ("token_major", 32, 128, F32, 2, 256, None, 0, 0,
                           "sigmoid"),
    "kimi_heads_sigmoid_bf16": ("token_major", 32, 128, BF16, 2, 256, None,
                                0, 0, "sigmoid"),
    "kimi_heads_silu": ("token_major", 32, 128, F32, 1, 256, None, 0, 0),
    "kimi_heads_silu_bf16": ("token_major", 32, 128, BF16, 1, 256, None, 0,
                             0),
    "token_major_gate_a_window_bf16": ("token_major", 2, 256, BF16, 2, 256,
                                       None, 512, 72, "sigmoid"),
    "sigmoid_before_the_norm": ("gate_first", 2, 128, F32, 2, 256, 0.7, 0, 0,
                                "sigmoid"),
    "sigmoid_behind_paired_heads_bf16": ("norm_first", 2, 192, BF16, 1, 256,
                                         None, 0, 0, "sigmoid"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_pair_matches_the_jnp_form(case, monkeypatch):
    """Forward to one ulp of the result's type and the gradient with
    respect to the core's output (float32, laid out as the core left it),
    the gate's array (its type; zero outside the window) and gamma; two
    row tiles, so that gamma's gradient crosses a tile."""
    form, groups, width, dtype, batch, t, scale, offset, extra = (
        CASES[case][:9])
    act = (CASES[case] + ("silu",))[9]
    monkeypatch.setattr(gate_norm, "_ROW_TILES", (128,))
    y, src, gamma, cot = _inputs(0, form, groups, width, dtype, batch, t,
                                 offset, extra)
    tiles = gate_norm.gate_norm_tiles(form, groups, width, t, dtype, offset,
                                      src.shape[2])
    assert tiles is not None and tiles[0] == 128 and t // tiles[0] == 2
    kernels, plain = _pair(form, groups, width, scale, offset, act)
    got, want = jax.jit(kernels)(y, src, gamma), jax.jit(plain)(y, src, gamma)
    assert got.shape == (batch, t, groups * width) and got.dtype == dtype
    bf16 = dtype == BF16
    one_ulp = 2.0 ** -7 if bf16 else 2.0 ** -22
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=one_ulp,
                               atol=0)
    ours = _grads(kernels, (y, src, gamma), cot)
    theirs = _grads(plain, (y, src, gamma), cot)
    if bf16:
        exact = _grads(plain, (y, src.astype(F32), gamma.astype(F32)), cot)
        theirs = theirs[:2] + (exact[2].astype(BF16),)
    for name, g, e in zip(("dy", "dsrc", "dgamma"), ours, theirs):
        assert float(jnp.abs(e.astype(F32)).max()) > 1e-3, name
        if e.dtype == BF16:
            _close(g, e, name, rtol=2.0 ** -7, ulps=1, eps=2.0 ** -8)
        else:
            _close(g, e, name, ulps=64)
    assert ours[0].shape == y.shape and ours[0].dtype == F32
    outside = np.asarray(ours[1].astype(F32))
    assert not outside[..., :offset].any()
    assert not outside[..., offset + groups * width:].any()


def test_a_group_is_normed_by_its_own_columns_alone():
    """Scaling one group's ``y`` leaves every other group's result where
    it was (and its own too, but for ``eps``): the statistics are a
    group's, not the row's."""
    y, src, gamma, _ = _inputs(1, "gate_first", 4, 128, F32, 1, 128)
    kernels, _ = _pair("gate_first", 4, 128)
    scaled = y.at[..., 128:256].multiply(8.0)
    a, b = jax.jit(kernels)(y, src, gamma), jax.jit(kernels)(scaled, src,
                                                             gamma)
    same = np.asarray(a == b).all(axis=(0, 1))
    assert same[:128].all() and same[256:].all()
    _close(b[..., 128:256], a[..., 128:256], "the scaled group", rtol=1e-3)


def test_a_head_is_normed_by_its_own_128_columns_alone():
    """``token_major``: a head is a 128-lane range of a token's row and
    its statistics are that range's. Scaling one head's ``o`` scales no
    other head's result, in either column tile, and its own only through
    ``eps``; the gradient of a loss over one head's columns with respect
    to ``o`` is zero outside that head."""
    y, src, gamma, _ = _inputs(2, "token_major", 32, 128, F32, 1, 128)
    kernels, _ = _pair("token_major", 32, 128, act="sigmoid")
    head = slice(17 * 128, 18 * 128)  # in the second column tile
    scaled = y.at[..., head].multiply(8.0)
    a, b = jax.jit(kernels)(y, src, gamma), jax.jit(kernels)(scaled, src,
                                                             gamma)
    same = np.asarray(a == b).all(axis=(0, 1))
    assert same[:head.start].all() and same[head.stop:].all()
    _close(b[..., head], a[..., head], "the scaled head", rtol=1e-3)
    do = jax.jit(jax.grad(lambda y: jnp.sum(
        kernels(y, src, gamma)[..., head] ** 2)))(y)
    reached = np.asarray(do != 0).any(axis=(0, 1))
    assert reached[head].all()
    assert not reached[:head.start].any() and not reached[head.stop:].any()


TAKES = {
    # form, groups, width, time, dtype, offset, the array's width -> tiles
    "nemotron_mamba2": (("gate_first", 8, 512, 8192, BF16, 0, 10304),
                        (256, 2048)),
    "falcon_h1_mamba2": (("gate_first", 1, 2048, 4096, BF16, 0, 4624),
                         (256, 2048)),
    "olmo_hybrid_delta_net": (("norm_first", 30, 192, 4096, BF16, 0, 5760),
                              (1024, 384)),
    "kimi_channel_delta_net": (("token_major", 32, 128, 8192, BF16, 0, 4096),
                               (256, 2048)),
    "kimi_heads_float32_gate": (("token_major", 32, 128, 8192, F32, 0, 4096),
                                (256, 2048)),
    "token_major_short_sequence": (("token_major", 4, 128, 128, BF16, 0, 512),
                                   (128, 512)),
    "token_major_heads_of_96": (("token_major", 4, 96, 256, BF16, 0, 384),
                                None),
    "token_major_heads_of_192": (("token_major", 4, 192, 256, BF16, 0, 768),
                                 None),
    "token_major_time_no_tile_divides": (
        ("token_major", 32, 128, 8200, BF16, 0, 4096), None),
    "float32": (("gate_first", 2, 128, 256, F32, 0, 256), (256, 256)),
    "a_window_further_on": (("gate_first", 2, 128, 256, F32, 256, 600),
                            (256, 256)),
    "a_window_only_one_group_reaches": (
        ("gate_first", 2, 128, 256, F32, 128, 600), (256, 128)),
    "group_off_the_lane_rows": (("gate_first", 8, 192, 256, F32, 0, 1536),
                                None),
    "window_off_the_groups": (("gate_first", 2, 128, 256, F32, 64, 600),
                              None),
    "window_past_the_array": (("gate_first", 2, 128, 256, F32, 0, 200),
                              None),
    "heads_no_pair_makes_lane_rows": (
        ("norm_first", 4, 96, 256, F32, 0, 384), None),
    "an_odd_count_of_half_heads": (("norm_first", 3, 192, 256, F32, 0, 576),
                                   None),
    "a_gate_wider_than_the_heads": (("norm_first", 4, 192, 256, F32, 0, 800),
                                    None),
    "time_no_tile_divides": (("gate_first", 2, 128, 200, F32, 0, 256), None),
    "time_under_a_tile": (("gate_first", 2, 128, 64, F32, 0, 256), None),
    "float16": (("gate_first", 2, 128, 256, jnp.float16, 0, 256), None),
    "a_group_over_vmem": (("gate_first", 1, 65536, 128, F32, 0, 65536), None),
    "an_unknown_form": (("gate_last", 2, 128, 256, F32, 0, 256), None),
}


@pytest.mark.parametrize("case", list(TAKES))
def test_gate_norm_takes_decides_from_the_shapes(case):
    args, tiles = TAKES[case]
    assert gate_norm.gate_norm_tiles(*args) == tiles
    assert pk.gate_norm_takes(*args) == (tiles is not None)
    if tiles is not None:
        form, groups, width, time, dtype, offset = args[:6]
        assert gate_norm.gate_norm_vmem_bytes(
            *tiles, width, jnp.dtype(dtype).itemsize,
            form) <= pk.common.VMEM_RAISED_LIMIT
        assert time % tiles[0] == 0 and offset % tiles[1] == 0
        assert tiles[1] % width == 0 and (groups * width) % tiles[1] == 0


def test_a_refused_call_says_so():
    y, src, gamma, _ = _inputs(3, "gate_first", 2, 128, F32, 1, 200)
    with pytest.raises(ValueError, match="gate_norm_takes"):
        pk.gated_rms_norm(y, src, gamma, form="gate_first", groups=2,
                          eps=1e-5, interpret=True)
    y, src, gamma, _ = _inputs(3, "norm_first", 3, 192, F32, 1, 256)
    with pytest.raises(ValueError, match="gate_norm_takes"):
        pk.gated_rms_norm(y, src, gamma, form="norm_first", eps=1e-5,
                          interpret=True)


# -- the call sites: the counter, and what the ops run ------------------------

def _mamba2(proj, gamma, remat=False, multipliers=None):
    heads, p, n, groups = 4, 64, 128, 2
    conv_dim = heads * p + 2 * groups * n
    rng = np.random.RandomState(7)
    rest = (jnp.asarray(rng.uniform(-0.5, 0.5, (4, conv_dim)), F32),
            jnp.asarray(0.1 * rng.randn(conv_dim), F32),
            jnp.asarray(rng.randn(heads), F32),
            jnp.asarray(np.log(rng.uniform(1, 16, heads)), F32),
            jnp.ones(heads, F32))
    return tr.mamba2(proj, *rest, gamma, heads, p, n, groups, 128, 1e-5,
                     remat=remat, multipliers=multipliers)


def _gated_delta_net(qkvg, gamma, remat=False):
    heads, dk, dv = 2, 64, 192
    rng = np.random.RandomState(8)
    t = qkvg.shape[1]
    q, k, v, g = jnp.split(qkvg, [heads * dk, 2 * heads * dk,
                                  2 * heads * dk + heads * dv], axis=2)
    a, b = (jnp.asarray(rng.randn(1, t, heads), F32) for _ in range(2))
    rest = (jnp.asarray(rng.uniform(-0.5, 0.5,
                                    (4, 2 * heads * dk + heads * dv)), F32),
            jnp.asarray(np.log(rng.uniform(1, 16, heads)), F32),
            jnp.asarray(rng.randn(heads), F32))
    return tr.gated_delta_net(q, k, v, g, a, b, *rest, gamma, heads, 64,
                              1e-6, remat=remat)


def _channel_delta_net(qkvga, gamma, remat=False):
    """Kimi Delta Attention's signature at two heads of 128 / 128: a decay
    a channel (``a`` as wide as the keys), a sigmoid gate."""
    heads, d = 2, 128
    rng = np.random.RandomState(9)
    t = qkvga.shape[1]
    q, k, v, g, a = jnp.split(qkvga, 5, axis=2)
    b = jnp.asarray(rng.randn(1, t, heads), F32)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), heads * d))
    rest = (jnp.asarray(rng.uniform(-0.5, 0.5, (4, 3 * heads * d)), F32),
            jnp.asarray(np.log(rng.uniform(1, 16, heads)), F32),
            jnp.asarray(step + np.log(-np.expm1(-step)), F32))
    return tr.gated_delta_net(q, k, v, g, a, b, *rest, gamma, heads, 64,
                              1e-5, allow_neg_eigval=False, remat=remat,
                              gate_act="sigmoid")


def _site_inputs(site, t, seed=4):
    rng = np.random.RandomState(seed)
    width, gamma = {"mamba2": (2 * 256 + 2 * 256 + 4, 256),
                    "gated_delta_net": (2 * 128 + 2 * 384, 192),
                    "channel_delta_net": (5 * 256, 128)}[site]
    return (jnp.asarray(rng.randn(1, t, width), F32),
            jnp.asarray(1 + 0.2 * rng.randn(gamma), F32))


# the op, the counter's labels, what the kernels' names end in
SITES = {"mamba2": (_mamba2, dict(site="mamba2", groups=2, width=128),
                    "gate_first"),
         "gated_delta_net": (
             _gated_delta_net,
             dict(site="gated_delta_net", groups=2, width=192), "norm_first"),
         "channel_delta_net": (
             _channel_delta_net,
             dict(site="gated_delta_net", groups=2, width=128,
                  gate="sigmoid"), "token_major_sigmoid")}


def _clear_blocks():
    for block in (ssm._mamba2_block, delta._gated_delta_block,
                  delta._channel_delta_block):
        block.clear_cache()


@pytest.fixture
def registry():
    telemetry.reset()
    telemetry.enable()
    yield telemetry.REGISTRY
    telemetry.disable()
    telemetry.reset()


@pytest.mark.parametrize("site", list(SITES))
def test_a_call_site_counts_itself_once_a_lowering(site, registry):
    """``gate_norm.lowerings``: one a node and lowering, labelled with the
    site, the groups, their width, the gate where it is no ``silu`` and
    which form runs; a time length no tile divides is the ``jax.numpy``
    form's; nothing a step."""
    op, labels, _ = SITES[site]
    _clear_blocks()
    compiled = jax.jit(op).lower(*_site_inputs(site, 256)).compile()
    count = registry.get("gate_norm.lowerings")
    assert telemetry.total("gate_norm.lowerings") == 1
    assert count.value(impl="kernel", **labels) == 1
    for _ in range(2):
        compiled(*_site_inputs(site, 256))
    assert telemetry.total("gate_norm.lowerings") == 1
    jax.jit(op).lower(*_site_inputs(site, 192))
    assert telemetry.total("gate_norm.lowerings") == 2
    assert count.value(impl="jnp", **labels) == 1


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("site", list(SITES))
def test_a_training_step_holds_each_kernel_once_and_never_interpreted(
        site, monkeypatch):
    """The gradient's program of the op in training: ONE forward and one
    backward kernel of the gate and norm (the pair keeps the op's inputs
    and computes the statistics again in VMEM: no second forward under a
    checkpoint), both for Mosaic; ``GatedDeltaNet``'s reads ``o``
    head-major as the scalar rule's kernel wrote it and token-major as
    the channel rule's pair did. A step lowered for the CPU
    holds no kernel at all, runs, and has the ``jax.numpy`` closures'
    values and gradients to the bit."""
    _clear_blocks()
    op, labels, form = SITES[site]
    ins = _site_inputs(site, 256)

    def loss(*a):
        return jnp.sum(op(*a, remat=True).astype(F32) ** 2)

    grad = jax.jit(jax.value_and_grad(loss, (0, 1)))
    calls = [c for c in _pallas_calls(grad.trace(*ins).jaxpr.jaxpr)
             if str(c.params["name"]).startswith("gate_norm_")]
    assert sorted(str(c.params["name"]) for c in calls) == [
        "gate_norm_%s_f32_r256_g%d_%s" % (which, labels["width"], form)
        for which in ("bwd", "fwd")]
    assert not any(c.params["interpret"] for c in calls)
    fwd = [c for c in calls if "fwd" in str(c.params["name"])][0]
    if site != "mamba2":
        assert fwd.invars[0].aval.shape == {
            "gated_delta_net": (1, 2, 256, 192),
            "channel_delta_net": (1, 256, 256)}[site]
    lowered = grad.lower(*ins)
    text = lowered.as_text()
    assert "tpu_custom_call" not in text and "gate_norm_fwd" not in text
    got = lowered.compile()(*ins)

    # the same lowering with the gate and norm refused: the closures
    monkeypatch.setattr(gate_norm, "gate_norm_tiles", lambda *args: None)
    _clear_blocks()
    want = jax.jit(jax.value_and_grad(loss, (0, 1)))(*ins)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_falcon_h1s_multiplier_sits_inside_the_gates_silu(monkeypatch):
    """``Mamba2(multipliers=)`` through the kernels interpreted: ``z``'s
    multiplier reaches the kernel as its static scalar, and the op's
    values and gradients are those of the closures."""
    proj, gamma = _site_inputs("mamba2", 256)
    multipliers = (0.7, 1.1, 0.9, 1.2, 0.8)

    def grads():
        ssm._mamba2_block.clear_cache()
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(_mamba2(
                *a, remat=True, multipliers=multipliers) ** 2),
            (0, 1)))(proj, gamma)

    monkeypatch.setattr(pk.common, "INTERPRET", True)
    got = grads()
    monkeypatch.setattr(pk.common, "INTERPRET", False)
    monkeypatch.setattr(gate_norm, "gate_norm_tiles", lambda *args: None)
    want = grads()
    for name, g, w in zip(("loss", "dproj", "dgamma"),
                          jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        _close(g, w, name, rtol=1e-4, ulps=256)
