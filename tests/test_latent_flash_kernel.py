"""Latent attention's flash pair (``ops/kernels/latent.py::latent_flash``:
``flash2_fwd_`` / ``flash2_bwd_`` behind a ``custom_vjp``, two key
operands on token-major arrays) through the Pallas interpreter (off the
TPU the pair's own branch is ``reference_attention`` over the concatenated
key), at small shapes the kernels take (a head's own key and its values
one lane row each, 64 rotary lanes, a latent of 32, batch 2, 1 or 4
heads, T one tile, several tiles and one that is not whole tiles),
against the composition the op ran before and still runs on every other
shape (``_latent_composed_path``: the rotation over the whole query, the
rotary key broadcast and concatenated, ``attention``): the output and the
gradient of every input, the shared rotary key's lanes of the latent
among them. Then which shapes take the pair, what a training step's
program holds of it, and how often three layers trace it.

Tolerances as in ``tests/test_kanana2.py``: float32 on both sides, so only
the order of summation differs (``_close``: rtol 1e-5 and a few float32
ulps of the tensor's largest magnitude; more for gradients, which are
sums over every query of a key); bf16 by the error against the float32
composition on the same rounded inputs, beside the bf16 composition's."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import latent

BATCH, NOPE, ROPE, DV, LATENT = 2, 128, 64, 128, 32
THETA, EPS = 1e6, 1e-6
GRADS = ("dq", "dlatent", "dgamma", "dw_up")
_JITTED = ("latent_fwd_call", "latent_bwd_call", "latent_forward",
           "latent_backward")


def _close(got, want, what, rtol=1e-5, ulps=8):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _inputs(seed, t, heads, dtype=jnp.float32, nope=NOPE):
    rng = np.random.RandomState(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    return ((draw(BATCH, t, heads * (nope + ROPE)),
             draw(BATCH, t, LATENT + ROPE),
             (1 + draw(LATENT, scale=0.1)).astype(dtype),
             draw(heads * (nope + DV), LATENT, scale=0.2)),
            jnp.asarray(rng.randn(BATCH, t, heads * DV), jnp.float32))


def _op(heads, interleave=True):
    def op(*ins):
        return tr.latent_attention(
            *ins, num_heads=heads, rope_dim=ROPE, v_head_dim=DV,
            theta=THETA, eps=EPS, interleave=interleave)
    return op


@pytest.fixture
def kernels(monkeypatch):
    """The op's kernel path through the Pallas interpreter."""
    monkeypatch.setattr(pk.common, "INTERPRET", True)


def _composed(monkeypatch, op, *ins):
    """``op`` with every shape refused by the pair: the composition."""
    with monkeypatch.context() as m:
        m.setattr(pk, "latent_flash_takes", lambda *a: False)
        return op(*ins)


def _out_and_grads(fn, ins, cot):
    @jax.jit
    def both(ins, cot):
        out, vjp = jax.vjp(fn, *ins)
        return out, vjp(cot.astype(out.dtype))
    return both(ins, cot)


# one tile; a T that is not whole tiles (200 in one tile of 256: padding
# keys masked, padding queries cut off); three tiles of 128 a side (the
# causal walk, dK_rope over tiles and heads); one head and four; both
# rotations
CASES = {
    "one_tile_one_head": (128, 1, True),
    "one_tile_four_heads_rotate_half": (128, 4, False),
    "padded_four_heads": (200, 4, True),
    "three_tiles_one_head": (384, 1, True),
    "three_tiles_four_heads": (384, 4, True),
    "three_tiles_four_heads_rotate_half": (384, 4, False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_match_the_composition(case, kernels, monkeypatch):
    t, heads, interleave = CASES[case]
    ins, cot = _inputs(len(case), t, heads)
    op = _op(heads, interleave)
    got, got_g = _out_and_grads(op, ins, cot)
    want, want_g = _out_and_grads(
        lambda *a: _composed(monkeypatch, op, *a), ins, cot)
    _close(got, want, "out")
    for name, g, w in zip(GRADS, got_g, want_g):
        assert g.shape == w.shape and g.dtype == w.dtype
        _close(g, w, name, ulps=64)
    # every head's gradient reaches the one rotary key
    assert float(jnp.abs(want_g[1][..., LATENT:]).max()) > 1e-3


# tiles pinned on the pair itself: q tiles twice and half the k tiles, so
# the diagonal crosses a tile off its corner and a row's live range ends
# inside the walk
@pytest.mark.parametrize("block_q,block_k", [(256, 128), (128, 256)],
                         ids=["q256_k128", "q128_k256"])
def test_uneven_tiles_walk_the_same_triangle(block_q, block_k):
    heads = 2                           # 600 real positions of 768 padded
    rng = np.random.RandomState(block_q)
    q, kv, kr = (jnp.asarray(rng.randn(BATCH, 600, width), jnp.float32)
                 for width in (heads * 256, heads * (NOPE + DV), 128))
    zero = jnp.arange(256) < NOPE + ROPE      # the lanes behind the rotary
    q = (q.reshape(BATCH, 600, heads, 256) * zero).reshape(BATCH, 600, -1)
    kr = kr * zero[NOPE:]
    cot = jnp.asarray(rng.randn(BATCH, 600, heads * DV), jnp.float32)
    scale = (NOPE + ROPE) ** -0.5
    got, got_g = _out_and_grads(
        lambda *a: pk.latent_flash(*a, heads, NOPE, scale, block_q=block_q,
                                   block_k=block_k, interpret=True),
        (q, kv, kr), cot)
    want, want_g = _out_and_grads(
        lambda *a: pk.latent.latent_composed(*a, heads, NOPE, scale),
        (q, kv, kr), cot)
    assert got.shape == want.shape == (BATCH, 600, heads * DV)
    _close(got, want, "out")
    for name, g, w in zip(("dq", "dkv", "dk_rope"), got_g, want_g):
        _close(g, w, name, ulps=64)


# tiles of 512 pinned on the pair and the floor lowered to their quarters
# of 256 (the cells' tiles of 1,024 scaled down), so a diagonal tile runs
# its three live quarters (``flash.cut_steps``); 1,300 positions pad to
# 1,536 and the last diagonal tile, which holds padding keys, keeps the
# whole mask
@pytest.mark.parametrize("t,heads", [(1024, 2), (1300, 1)],
                         ids=["two_tiles", "padded_last_tile"])
def test_diagonal_tiles_by_quarters_match_the_composition(t, heads,
                                                          monkeypatch):
    monkeypatch.setattr(pk.flash, "FLASH_MIN_EDGE", 256)
    rng = np.random.RandomState(t)
    q, kv, kr = (jnp.asarray(rng.randn(1, t, width), jnp.float32)
                 for width in (heads * 256, heads * (NOPE + DV), 128))
    zero = jnp.arange(256) < NOPE + ROPE      # the lanes behind the rotary
    q = (q.reshape(1, t, heads, 256) * zero).reshape(1, t, -1)
    kr = kr * zero[NOPE:]
    cot = jnp.asarray(rng.randn(1, t, heads * DV), jnp.float32)
    scale = (NOPE + ROPE) ** -0.5
    assert pk.flash.cut_half(512, 512, True) == 256
    telemetry.reset()
    telemetry.enable()
    try:
        fn = lambda *a: pk.latent_flash(*a, heads, NOPE, scale, block_q=512,
                                        block_k=512, interpret=True)
        names = [str(e.params["name"]) for e in _pallas_calls(
            jax.make_jaxpr(lambda *a: jax.vjp(fn, *a)[1](cot))(
                q, kv, kr).jaxpr)]
        assert sorted(names) == ["flash2_bwd_f32_q512_k512_e256",
                                 "flash2_fwd_f32_q512_k512_e256"]
        c = telemetry.REGISTRY.get("attention.latent_kernel_traces")
        assert c.value(**{"pass": "fwd"}, edge=256) == 1
        assert c.value(**{"pass": "bwd"}, edge=256) == 1
        assert telemetry.total("attention.latent_kernel_traces") == 2
    finally:
        telemetry.disable()
        telemetry.reset()
    got, got_g = _out_and_grads(fn, (q, kv, kr), cot)
    want, want_g = _out_and_grads(
        lambda *a: pk.latent.latent_composed(*a, heads, NOPE, scale),
        (q, kv, kr), cot)
    _close(got, want, "out")
    for name, g, w in zip(("dq", "dkv", "dk_rope"), got_g, want_g):
        _close(g, w, name, ulps=64)


@pytest.mark.parametrize("t,heads", [(128, 4), (384, 1)],
                         ids=["one_tile", "three_tiles"])
def test_bf16_operands_are_one_rounding_from_the_composition(
        t, heads, kernels, monkeypatch):
    """bf16 operands, float32 scores, softmax and accumulators: against
    the composition in float32 on the same rounded inputs, the pair's
    output and gradients are as far as the bf16 composition's own (rms
    over the tensor's; the pair rounds dK_rope once where the composition
    sums 32 rounded heads, so it may be nearer), never 1.3 times that."""
    ins, cot = _inputs(5, t, heads, jnp.bfloat16)
    op = _op(heads)
    exact = _out_and_grads(
        lambda *a: _composed(monkeypatch, op, *a),
        tuple(a.astype(jnp.float32) for a in ins), cot)
    low = _out_and_grads(lambda *a: _composed(monkeypatch, op, *a), ins, cot)
    got = _out_and_grads(op, ins, cot)
    assert got[0].dtype == jnp.bfloat16

    def rms(x, want):
        x, want = (np.asarray(v, np.float64) for v in (x, want))
        return np.sqrt(np.mean((x - want) ** 2)) / want.std()

    for name, g, l, e in zip(("out",) + GRADS, (got[0],) + got[1],
                             (low[0],) + low[1], (exact[0],) + exact[1]):
        ours, theirs = rms(g, e), rms(l, e)
        assert 0 < ours < 1.3 * theirs and theirs < 0.02, (name, ours,
                                                           theirs)


# the pass over the query round the pair: two heads of 128 + 64 lanes a
# step (the cell's), four of 128 + 32, one of 128 + 128 (nothing to pad)
@pytest.mark.parametrize("rope,interleave", [
    (64, True), (64, False), (32, True), (128, False)],
    ids=["r64_interleaved", "r64_rotate_half", "r32_interleaved",
         "r128_rotate_half"])
def test_the_query_pass_is_the_rotation_and_its_transpose(
        rope, interleave, monkeypatch):
    """``kernels.latent_query`` against ``rope`` on the rotary
    lanes (``_query_pass``'s ``jax.numpy`` form): the padded query, and
    the cotangent taken back, lanes behind the rotary ones unread."""
    heads, t, pad = 4, 24, -rope % 128
    assert pk.latent_query_takes(t, heads, NOPE, rope)
    rng = np.random.RandomState(rope)
    x = jnp.asarray(rng.randn(BATCH, t, heads * (NOPE + rope)), jnp.float32)
    cot = jnp.asarray(rng.randn(BATCH, t, heads * (NOPE + rope + pad)),
                      jnp.float32)

    def both(x, cot):
        out, vjp = jax.vjp(lambda x: latent._kernel_query(
            x, heads, rope, THETA, interleave, True), x)
        return out, vjp(cot)[0]

    got = both(x, cot)
    with monkeypatch.context() as m:
        m.setattr(pk, "latent_query_takes", lambda *a: False)
        want = both(x, cot)
    for name, g, w in zip(("padded query", "cotangent"), got, want):
        assert g.shape == w.shape
        _close(g, w, name)
    padded = np.asarray(got[0]).reshape(BATCH, t, heads, -1)
    assert not padded[..., NOPE + rope:].any()


def test_the_query_pass_takes_whole_steps_of_heads():
    take = pk.latent_query_takes
    assert take(8192, 32, 128, 64)                          # the cell's
    assert take(200, 4, 128, 64) and take(8, 1, 256, 128)
    for t, heads, nope, rope in [
            (8192, 3, 128, 64),     # a step is two heads
            (8192, 32, 128, 48),    # 48 does not divide a lane row
            (8192, 32, 64, 64),     # keys half a lane row
            (100, 4, 128, 64)]:     # not whole sublane rows of tokens
        assert not take(t, heads, nope, rope), (t, heads, nope, rope)


def test_a_head_under_a_lane_row_takes_the_composition(kernels):
    """``nope`` 64: the pair has no block for it, the call site says
    ``composed`` and no kernel is traced, even when told to interpret."""
    ins, _ = _inputs(1, 128, 2, nope=64)
    for name in _JITTED:
        getattr(pk.latent, name).clear_cache()
    telemetry.reset()
    telemetry.enable()
    try:
        out = _op(2)(*ins)
        sites = telemetry.REGISTRY.get("attention.latent_lowerings")
        labels = dict(heads=2, latent=LATENT, rope=ROPE, nope=64, dv=DV)
        assert sites.value(impl="composed", **labels) == 1
        assert sites.value(impl="kernel", **labels) == 0
        assert telemetry.total("attention.latent_kernel_traces") == 0
    finally:
        telemetry.disable()
        telemetry.reset()
    assert out.shape == (BATCH, 128, 2 * DV)


def test_the_pair_takes_whole_lane_rows_and_a_resident_head():
    take = pk.latent_flash_takes
    assert take(8192, 128, 64, 128, jnp.bfloat16)           # the cell's
    assert take(128, 128, 64, 128, jnp.float32)
    assert take(4096, 256, 32, 128, jnp.bfloat16)
    for t, nope, rope, dv, dtype in [
            (32, 16, 8, 16, jnp.float32),         # the tiny symbol's
            (127, 128, 64, 128, jnp.bfloat16),    # under a tile
            (8192, 64, 64, 128, jnp.bfloat16),    # keys half a lane row
            (8192, 128, 64, 64, jnp.bfloat16),    # values half a lane row
            (8192, 192, 64, 128, jnp.bfloat16),   # keys astride lane rows
            (8192, 128, 0, 128, jnp.bfloat16),    # no rotary key
            (8192, 128, 64, 128, jnp.float16),    # not Mosaic's operand
            (16384, 128, 64, 128, jnp.bfloat16),  # dK / dV over VMEM
            (8192, 128, 64, 128, jnp.float32)]:   # ... in float32 too
        assert not take(t, nope, rope, dv, dtype), (t, nope, rope, dv)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_a_training_step_holds_each_kernel_once_and_never_interpreted(
        monkeypatch):
    """The gradient's program of the op as a step traces it (not told to
    interpret): ONE forward and one backward kernel, both for Mosaic; the
    branch for every other platform is the reference over the same
    operands, so a step lowered for the CPU holds no kernel and runs, to
    the composition's values."""
    ins, cot = _inputs(2, 384, 2)
    op = _op(2)

    def loss(*a):
        return jnp.sum(op(*a) * cot)

    grad = jax.jit(jax.grad(loss, (0, 1, 2, 3)))
    calls = list(_pallas_calls(grad.trace(*ins).jaxpr.jaxpr))
    names = sorted(str(c.params["name"]) for c in calls)
    assert names == ["flash2_bwd_f32_q128_k128", "flash2_fwd_f32_q128_k128",
                     "latent_query_bwd_f32", "latent_query_fwd_f32"], names
    assert not any(c.params["interpret"] for c in calls)
    lowered = grad.lower(*ins)
    text = lowered.as_text()
    assert not any(word in text for word in (
        "tpu_custom_call", "flash2", "latent_query"))
    got = lowered.compile()(*ins)
    want = jax.grad(
        lambda *a: jnp.sum(_composed(monkeypatch, op, *a) * cot),
        (0, 1, 2, 3))(*ins)
    for name, g, w in zip(GRADS, got, want):
        _close(g, w, name, ulps=64)


def test_three_layers_trace_each_body_once_over_two_steps(kernels):
    """Three call sites of one signature (each layer its own weights) and
    two steps: three ``impl="kernel"`` lowerings, the forward and the
    backward body traced once each."""
    heads, t = 2, 128
    layers = [_inputs(seed, t, heads)[0] for seed in range(3)]
    op = _op(heads)

    def loss(layers):
        return sum(jnp.sum(op(*ins) ** 2) for ins in layers)

    for name in _JITTED:
        getattr(pk.latent, name).clear_cache()  # another test's trace is not ours
    telemetry.reset()
    telemetry.enable()
    try:
        step = jax.jit(jax.grad(loss))
        for _ in range(2):
            grads = step(layers)
        sites = telemetry.REGISTRY.get("attention.latent_lowerings")
        assert sites.value(heads=heads, latent=LATENT, rope=ROPE, nope=NOPE,
                           dv=DV, impl="kernel") == 3
        traces = telemetry.REGISTRY.get("attention.latent_kernel_traces")
        assert traces.value(**{"pass": "fwd"}) == 1
        assert traces.value(**{"pass": "bwd"}) == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    assert all(np.isfinite(np.asarray(g)).all() for ins in grads
               for g in ins)


# -- the selected variant: every live tile also masked by a keep-mask --------

def _select_inputs(t, heads, seed):
    rng = np.random.RandomState(seed)
    q, kv, kr = (jnp.asarray(rng.randn(BATCH, t, width), jnp.float32)
                 for width in (heads * 256, heads * (NOPE + DV), 128))
    zero = jnp.arange(256) < NOPE + ROPE      # the lanes behind the rotary
    q = (q.reshape(BATCH, t, heads, 256) * zero).reshape(BATCH, t, -1)
    kr = kr * zero[NOPE:]
    keep = rng.rand(BATCH, t, t) < 0.5
    # a row that drops its own key, and rows that keep nothing of their
    # first tile but key 3 (the running maximum starts late)
    keep[:, np.arange(t), np.arange(t)] = False
    keep[:, :, :128] = False
    keep[:, :, 3] = True
    cot = jnp.asarray(rng.randn(BATCH, t, heads * DV), jnp.float32)
    return (q, kv, kr), jnp.asarray(keep, jnp.int8), cot


@pytest.mark.parametrize("t,block_q,block_k", [
    (512, 128, 256), (600, 256, 128), (384, 128, 128)],
    ids=["q128_k256", "padded_q256_k128", "square"])
def test_the_selected_pair_matches_the_composed_form(t, block_q, block_k):
    """``latent_flash(keep=)`` through the interpreter against
    ``kept_attention`` over the concatenated key, output and every
    gradient: pairs past the diagonal stay dead whatever the mask says,
    padding keys are masked by the padded mask's zeros."""
    heads = 2
    ins, keep, cot = _select_inputs(t, heads, t)
    keep = keep.at[:, 5, 9].set(1)    # past the diagonal: never live
    scale = (NOPE + ROPE) ** -0.5
    got, got_g = _out_and_grads(
        lambda *a: pk.latent_flash(*a, heads, NOPE, scale, block_q=block_q,
                                   block_k=block_k, interpret=True,
                                   keep=keep), ins, cot)
    want, want_g = _out_and_grads(
        lambda *a: pk.latent.latent_composed(*a, heads, NOPE, scale, keep),
        ins, cot)
    _close(got, want, "out")
    for name, g, w in zip(("dq", "dkv", "dk_rope"), got_g, want_g):
        _close(g, w, name, ulps=64)
    # the mask is data: a mask of ones is plain causal attention
    ones = jnp.ones_like(keep)
    _close(pk.latent_flash(*ins, heads, NOPE, scale, block_q=block_q,
                           block_k=block_k, interpret=True, keep=ones),
           pk.latent.latent_composed(*ins, heads, NOPE, scale), "all kept")


def test_a_selection_names_its_own_pair_and_none_keeps_todays_names():
    """A call with a keep-mask traces ``flash2sel_*`` (no tile by
    quarters, counted with ``select=1``); the same call without one still
    traces the ``flash2_*`` pair under its name of before, cut tiles and
    all."""
    heads, t = 1, 2048      # traced only: the cells' tiles of 1,024
    ins, keep, cot = _select_inputs(t, heads, 7)
    ins = tuple(x.astype(jnp.bfloat16) for x in ins)
    cot = cot.astype(jnp.bfloat16)

    def names(**extra):
        fn = lambda *a: pk.latent_flash(*a, heads, NOPE, 192 ** -0.5,
                                        **extra)
        return sorted(str(e.params["name"]) for e in _pallas_calls(
            jax.make_jaxpr(lambda *a: jax.vjp(fn, *a)[1](cot))(*ins).jaxpr))

    for name in _JITTED:
        getattr(pk.latent, name).clear_cache()
    telemetry.reset()
    telemetry.enable()
    try:
        bq, bk = pk.flash.flash_tiles(t, 256, jnp.bfloat16)
        edge = pk.flash.cut_half(bq, bk, True)
        tiles = "bf16_q%d_k%d" % (bq, bk)
        assert edge and names(keep=keep) == ["flash2sel_bwd_" + tiles,
                                             "flash2sel_fwd_" + tiles]
        c = telemetry.REGISTRY.get("attention.latent_kernel_traces")
        assert c.value(**{"pass": "fwd"}, select=1) == 1
        assert c.value(**{"pass": "bwd"}, select=1) == 1
        assert names() == ["flash2_bwd_%s_e%d" % (tiles, edge),
                           "flash2_fwd_%s_e%d" % (tiles, edge)]
        assert telemetry.total("attention.latent_kernel_traces") == 4
    finally:
        telemetry.disable()
        telemetry.reset()
