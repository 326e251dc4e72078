"""The gated delta rule's kernel pair (``ops/kernels/gdn.py::
gated_delta_rule``: ``gdn_fwd_`` / ``gdn_bwd_`` behind a ``custom_vjp``)
through the Pallas interpreter (``interpret=True``: off the TPU the op's
own branch is the ``jax.numpy`` chunk form), at small shapes the kernels
have tiles for (keys and values in multiples of 32, chunks of 16 and 64;
the Olmo-Hybrid cell's head of 96 keys and 192 values on three heads; two
head groups; a chunk of 128; T that is not whole chunks; batch 2), against
the chunk form (``ops/transformer.py::gated_delta_rule``) and against the
token-by-token recurrence: the output and the gradient of every input. Then keys that
repeat inside a chunk (what a power series of the system's inverse cannot
take), the carried state, ``gdn_takes``, ``gated_delta_net`` at such a
shape both ways against ``models/olmo_hybrid_reference.py``'s layer, and
what a training step's program holds of the kernels.

Tolerances as in ``tests/test_gated_delta_rule.py``: float32 on both
sides, so only the order of summation differs (``_close``: rtol 1e-5 and a
few float32 ulps of the tensor's largest magnitude; more ulps for
gradients, which are long sums through the system and several chunks);
bf16 inside the rms band ``tests/test_ssd_scan_kernel.py`` uses."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import olmo_hybrid, olmo_hybrid_reference as ref
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import delta
from mxnet_tpu.ops.transformer import gated_delta_net, gated_delta_rule
from mxnet_tpu.parallel import make_mesh

GRADS = ("dq", "dk", "dv", "dg", "dbeta")
EVERY = tuple(range(5))


def _close(got, want, what, rtol=1e-5, ulps=8):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _rms(got, want):
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def recurrence(q, k, v, g, beta):
    """The rule, one token after another, in float32: g [B, T, H], a decay
    a head, or [B, T, H, K], a decay a key channel."""
    def token(state, at):                                     # [B, H, K, V]
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t if g.ndim == 4 else g_t[..., None])[
            ..., None] * state
        u_t = beta_t[..., None] * (
            v_t - jnp.sum(state * k_t[..., None], axis=2))
        state = state + k_t[..., None] * u_t[:, :, None, :]
        return state, jnp.sum(state * q_t[..., None], axis=2)

    _, o = jax.lax.scan(
        token, jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[3:]),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _inputs(seed, batch, t, heads, dk, dv, dtype=jnp.float32, repeat=0):
    """Unit keys, scaled unit queries, write strengths over (0, 2) and log
    decays over (-0.5, 0). ``repeat``: every token's key is one of
    ``repeat`` directions but for a part in a thousand, strengths within
    0.02 of 2 and decays within 1e-3 of 1."""
    rng = np.random.RandomState(seed)
    q, k = rng.randn(2, batch, t, heads, dk)
    if repeat:
        few = rng.randn(batch, repeat, heads, dk)
        k = few[:, rng.randint(0, repeat, t)] + 1e-3 * k
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    u = rng.rand(2, batch, t, heads)
    beta, g = ((2 - 0.02 * u[0], -1e-3 * u[1]) if repeat
               else (2 * u[0], -0.5 * u[1]))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return ((jnp.asarray(q, dtype), jnp.asarray(k, dtype),
             jnp.asarray(rng.randn(batch, t, heads, dv), dtype), f32(g),
             f32(beta)), f32(rng.randn(batch, t, heads, dv)))


@functools.lru_cache(maxsize=None)
def _both_ways(form, chunk):
    """(o, gradients) of ``sum(o * cot)`` as one compiled program."""
    f = {"kernels": functools.partial(pk.gated_delta_rule, chunk=chunk,
                                      interpret=True),
         "chunked": functools.partial(gated_delta_rule, chunk=chunk),
         "channel_kernels": functools.partial(
             pk.gdn.channel_delta_rule, chunk=chunk, interpret=True),
         "channel_chunked": functools.partial(tr.channel_delta_rule,
                                              chunk=chunk),
         "recurrence": recurrence}[form]

    def loss(*a):
        o = f(*a[:5])
        return jnp.sum(o * a[5]), o

    def run(ins, cot):
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, EVERY, has_aux=True))(*ins, cot)
        return o, grads
    return run


# (batch, T, heads, K, V, chunk): a ragged last chunk of 16 (three heads:
# a pair and a head beside zeros); the cell's head (keys not a lane row,
# values one and a half) on three heads in chunks of 64, ragged; eighteen
# heads, so three groups of six a step; a chunk of a whole lane row (two
# heads: one pair); fifteen heads, which have no even group (seven pairs
# and a head beside zeros a step); ten, five pairs a step
SHAPES = {
    "ragged_small": (2, 70, 3, 32, 64, 16),
    "the_cells_head": (1, 130, 3, 96, 192, 64),
    "two_head_groups": (2, 48, 18, 32, 32, 16),
    "a_lane_row_of_tokens": (1, 150, 2, 32, 32, 128),
    "fifteen_heads": (1, 40, 15, 32, 32, 16),
    "ten_heads": (1, 40, 10, 32, 32, 16),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernels_match_the_chunk_form_and_the_recurrence(shape):
    """Output and the gradient of q, k, v, g and beta in float32, to
    summation order, against both."""
    *sizes, chunk = SHAPES[shape]
    assert pk.gdn_takes(*sizes[2:], chunk, jnp.float32)
    ins, cot = _inputs(0, *sizes)
    o, grads = _both_ways("kernels", chunk)(ins, cot)
    for other in ("chunked", "recurrence"):
        o_w, grads_w = _both_ways(other, chunk)(ins, cot)
        _close(o, o_w, "o against " + other, ulps=16)
        for name, got, want in zip(GRADS, grads, grads_w):
            assert got.shape == want.shape and got.dtype == want.dtype
            assert float(jnp.abs(want).max()) > 1e-6, name
            _close(got, want, name + " against " + other, ulps=128)


@pytest.mark.parametrize("shape", ["ragged_small", "the_cells_head"])
def test_bf16_operands_leave_the_gradients_where_the_chunk_form_has_them(
        shape):
    """bf16 q, k and v: both forms round the same operands to bf16 and
    keep decays, the system, its solution and the state float32, so each
    is a small share of a tensor's rms from the float32 recurrence, the
    kernels no farther than the chunk form."""
    *sizes, chunk = SHAPES[shape]
    assert pk.gdn_takes(*sizes[2:], chunk, jnp.bfloat16)
    ins, cot = _inputs(3, *sizes, dtype=jnp.bfloat16)
    exact = tuple(v.astype(jnp.float32) for v in ins)
    o, ours = _both_ways("kernels", chunk)(ins, cot)
    o_c, theirs = _both_ways("chunked", chunk)(ins, cot)
    o_w, want = _both_ways("recurrence", chunk)(exact, cot)
    assert o.dtype == jnp.float32
    assert _rms(o, o_w) < max(1.5 * _rms(o_c, o_w), 0.004)
    for name, g, c, w in zip(GRADS, ours, theirs, want):
        assert g.dtype == c.dtype and g.shape == c.shape, name
        assert _rms(g, w) < max(1.5 * _rms(c, w), 0.004), (
            name, _rms(g, w), _rms(c, w))


def _series_inverse(low):
    """``(I + L)^-1`` as ``(I - L)(I + L^2)(I + L^4)...``: what the
    kernels must NOT do."""
    n = low.shape[-1]
    eye = jnp.eye(n, dtype=low.dtype)
    inv, power = eye - low, low @ low
    for _ in range(int(np.ceil(np.log2(n))) - 1):
        inv, power = inv @ (eye + power), power @ power
    return inv


def test_repeated_keys_are_solved_by_substitution_not_by_a_series():
    """THE REPEATED-KEYS TEST. A chunk of 64 tokens whose keys are four
    directions over and over (but for a part in a thousand), strengths
    within 0.02 of 2 and nothing forgotten: ``L``'s entries are near 2
    and its powers grow to 1e20 before they vanish, so a series in them
    cancels to nothing, while forward substitution loses a few digits.
    The kernels are as near the recurrence as the chunk form's
    ``solve_triangular``; the series on the same system is far outside,
    so the case would catch it."""
    chunk = 64
    ins, cot = _inputs(7, 1, 128, 3, 32, 64, repeat=4)
    o_w, want = _both_ways("recurrence", chunk)(ins, cot)
    o, ours = _both_ways("kernels", chunk)(ins, cot)
    o_c, theirs = _both_ways("chunked", chunk)(ins, cot)
    scale = float(jnp.abs(o_w).max())
    err, err_c = (float(jnp.abs(x - o_w).max()) / scale for x in (o, o_c))
    assert err <= max(2 * err_c, 1e-4), (err, err_c)
    for name, g, c, w in zip(GRADS, ours, theirs, want):
        top = float(jnp.abs(w).max())
        e, e_c = (float(jnp.abs(x - w).max()) / top for x in (g, c))
        assert e <= max(2 * e_c, 1e-3), (name, e, e_c)
    # the series on the first chunk's system of the first head
    q, k, v, g, beta = (x[0, :chunk, 0] for x in ins)
    cum = jnp.cumsum(g)
    decay = jnp.exp(cum[:, None] - cum[None, :])
    low = jnp.tril(beta[:, None] * decay * (k @ k.T), -1)
    exact = np.linalg.inv(np.eye(chunk) + np.asarray(low, np.float64))
    sub = jax.scipy.linalg.solve_triangular(
        jnp.eye(chunk) + low, jnp.eye(chunk), lower=True)
    top = np.abs(exact).max()
    assert np.abs(np.asarray(sub) - exact).max() / top < 1e-3
    assert not np.abs(np.asarray(_series_inverse(low))
                      - exact).max() / top < 1e-3


def test_dropping_the_carried_state_is_caught_on_the_kernel_path():
    """The kernels run chunk by chunk from a zero state (the scratch
    state never carried) differ from the whole by more than a tenth of
    o's standard deviation past the first chunk; the whole meets the
    recurrence."""
    chunk, t = 16, 64
    ins, _ = _inputs(2, 2, t, 3, 32, 64)
    run = functools.partial(pk.gated_delta_rule, chunk=chunk, interpret=True)
    whole, want = run(*ins), recurrence(*ins)
    _close(whole, want, "the kernels", ulps=16)
    dropped = jnp.concatenate(
        [run(*(x[:, s:s + chunk] for x in ins))
         for s in range(0, t, chunk)], axis=1)
    _close(dropped[:, :chunk], whole[:, :chunk], "the first chunk", ulps=16)
    carried = float(jnp.sqrt(jnp.mean(
        (whole - dropped)[:, chunk:] ** 2)) / want[:, chunk:].std())
    assert carried > 0.1, carried
    with pytest.raises(AssertionError):
        _close(dropped, want, "the carried state dropped", ulps=16)


def test_the_kernels_take_the_cells_shape_and_refuse_what_has_no_tiles():
    take = pk.gdn_takes
    assert take(30, 96, 192, 64, jnp.bfloat16)              # the cell's
    assert take(30, 96, 192, 64, jnp.float32)
    assert take(3, 32, 64, 16, jnp.float32)
    assert take(16, 128, 128, 128, jnp.bfloat16)
    assert pk.gdn.gdn_group(30) == 10 and pk.gdn.gdn_group(34) == 2
    for heads, dk, dv, chunk, dtype in [
            (3, 8, 16, 8, jnp.float32),          # the tiny symbol's
            (30, 8, 16, 64, jnp.float32),
            (30, 96, 192, 64, jnp.float16),      # not Mosaic's operand
            (30, 96, 192, 8, jnp.bfloat16),      # half a bf16 tile of tokens
            (30, 96, 192, 256, jnp.bfloat16),    # a chunk over a lane row
            (30, 100, 192, 64, jnp.bfloat16),    # keys astride sublane tiles
            (6, 2048, 4096, 128, jnp.float32),   # a step over VMEM
            (0, 96, 192, 64, jnp.bfloat16)]:
        assert not take(heads, dk, dv, chunk, dtype), (heads, dk, dv, chunk)
    assert (pk.gdn.gdn_vmem_bytes(128, 6, 2048, 4096, 4)
            > pk.common.VMEM_RAISED_LIMIT)


def _one_head(form, seed, t, dk, dv, key_norm=1.0, decay=1.0):
    """One head's inputs and cotangent [1, T, 1, .], its keys ``key_norm``
    long and its log decays ``decay`` times ``_inputs``'s."""
    make = _channel_inputs if form == "channel_kernels" else _inputs
    (q, k, v, g, beta), cot = make(seed, 1, t, 1, dk, dv)
    return (q, k * key_norm, v, g * decay, beta), cot


@pytest.mark.parametrize("form,dk,dv", [("kernels", 32, 64),
                                        ("channel_kernels", 128, 128)])
def test_nothing_crosses_between_the_two_heads_of_a_trip(form, dk, dv):
    """A PAIR OF HEADS A TRIP SHARES LANE ROWS AND MXU PASSES AND NOTHING
    ELSE. A head beside one whose keys are ten times as long and which
    forgets twenty times as fast, beside one whose keys are a hundredth
    and which forgets nothing, beside a head of zeros, in the pair's first
    half and in its second, and alone (the group of one head: the body's
    own zeros beside it): its output and every gradient are the same BIT
    FOR BIT, and the chunk form's of that head alone at the file's
    tolerances."""
    chunk, t = 16, 40
    run = _both_ways(form, chunk)
    mine, cot = _one_head(form, 11, t, dk, dv)
    others = [_one_head(form, 12, t, dk, dv, key_norm=10.0, decay=20.0),
              _one_head(form, 13, t, dk, dv, key_norm=0.01, decay=1e-4),
              (tuple(jnp.zeros_like(x) for x in mine), jnp.zeros_like(cot))]

    def beside(other, first):
        """The head's output and gradients from a step of two heads."""
        def two(a, b):
            return jnp.concatenate((a, b) if first else (b, a), axis=2)

        o, grads = run(tuple(two(a, b) for a, b in zip(mine, other[0])),
                       two(cot, other[1]))
        at = 0 if first else 1
        return [np.asarray(x[:, :, at:at + 1]) for x in (o,) + tuple(grads)]

    o, grads = run(mine, cot)
    alone = [np.asarray(x) for x in (o,) + tuple(grads)]
    for first in (True, False):
        seen = [beside(other, first) for other in others]
        for got in seen[1:] + ([alone] if first else []):
            for name, a, b in zip(("o",) + GRADS, seen[0], got):
                np.testing.assert_array_equal(a, b, name)
        assert np.isfinite(seen[0][0]).all()
    o_w, grads_w = _both_ways(form.replace("kernels", "chunked"), chunk)(
        mine, cot)
    _close(alone[0], o_w, "o", ulps=32)
    for name, got, want in zip(GRADS, alone[1:], grads_w):
        _close(got, want, name, ulps=128)


# -- the op at a shape the kernels take ---------------------------------------

H, DK, DV, TAPS, CHUNK, BATCH, T = 3, 32, 64, 4, 16, 2, 40
GDN = dict(linear_num_key_heads=H, linear_key_head_dim=DK,
           linear_value_head_dim=DV, linear_allow_neg_eigval=True,
           rms_norm_eps=1e-6)
OP_GRADS = ("dquery", "dkey", "dvalue", "dgate", "da", "db", "dconv_weight",
            "da_log", "ddt_bias", "dnorm_gamma")


def _op_inputs(seed, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), H))

    def draw(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(shift + scale * rng.randn(*shape), dtype)

    return (draw(BATCH, T, H * DK), draw(BATCH, T, H * DK),
            draw(BATCH, T, H * DV), draw(BATCH, T, H * DV),
            draw(BATCH, T, H, scale=2.0), draw(BATCH, T, H),
            draw(TAPS, 2 * H * DK + H * DV, scale=0.3),
            jnp.asarray(np.log(rng.uniform(1, 16, H)), dtype),
            jnp.asarray(step + np.log(-np.expm1(-step)), dtype),
            draw(DV, scale=0.1, shift=1.0),
            jnp.asarray(rng.randn(BATCH, T, H * DV), jnp.float32))


@pytest.fixture(params=["chunk_form_branch", "kernels_interpreted"])
def rule_path(request, monkeypatch):
    """``gated_delta_net`` both ways a CPU test can run it at such a
    shape: as a step lowered off the TPU runs it (the platform switch's
    chunk-form branch inside the ``custom_vjp``) and with the kernel pair
    put through the Pallas interpreter (what the TPU's branch computes).
    The block is one ``jax.jit`` a signature, so its cache is emptied
    round the switch."""
    delta._gated_delta_block.clear_cache()
    if request.param == "kernels_interpreted":
        monkeypatch.setattr(pk.common, "INTERPRET", True)
    yield request.param
    delta._gated_delta_block.clear_cache()


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_op_matches_the_reference_layer(rule_path, remat):
    """Forward and the gradient of every input against the layer as the
    token recurrence (``olmo_hybrid_reference.gated_delta_net``), in
    float32; in training (``remat``) too, where the prologue is under a
    checkpoint and the kernel pair is not."""
    *ins, cot = _op_inputs(0)
    assert pk.gdn_takes(H, DK, DV, CHUNK, jnp.float32)

    def op(*a):
        return gated_delta_net(*a, num_heads=H, chunk_size=CHUNK, eps=1e-6,
                               remat=remat)

    _close(op(*ins), ref.gated_delta_net(*ins, GDN), "out", ulps=16)
    every = tuple(range(len(ins)))
    got = jax.grad(lambda *a: jnp.sum(op(*a) * cot), every)(*ins)
    want = jax.grad(lambda *a: jnp.sum(
        ref.gated_delta_net(*a, GDN) * cot), every)(*ins)
    for name, g, w in zip(OP_GRADS, got, want):
        assert g.shape == w.shape
        _close(g, w, name, ulps=128)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_off_the_tpu_the_ops_values_are_the_chunk_forms_bit_for_bit(remat):
    """A step lowered for the CPU at a shape the kernels take computes
    what the op computed before there were kernels: the same chunk form
    on the same values, output and every gradient equal to the bit (as
    one program, which a step is: dispatched piece by piece the two
    autodiffs fuse the decay's gradient differently, to an ulp)."""
    *ins, cot = _op_inputs(1)
    delta._gated_delta_block.clear_cache()
    kw = dict(heads=H, chunk=CHUNK, eps=1e-6, beta_scale=2.0, remat=remat,
              taps_kernel=(False,) * 3, norm_kernel=False)

    def grads(kernel):
        def loss(*a):
            o = delta._gated_delta_block(*a, kernel=kernel, interpret=False,
                                      **kw)
            return jnp.sum(o * cot), o
        return jax.jit(jax.value_and_grad(loss, tuple(range(len(ins))),
                                          has_aux=True))(*ins)

    (_, o), got = grads(True)
    (_, o_w), want = grads(False)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_w))
    for name, g, w in zip(OP_GRADS, got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_a_training_step_holds_each_kernel_once_and_never_interpreted():
    """The gradient's program of the ``GatedDeltaNet`` op in training: ONE
    forward and one backward kernel (the pair keeps its own residuals: no
    second forward under the checkpoint that recomputes the unit norms),
    both for Mosaic; lowered for the CPU it holds no kernel at all and
    runs."""
    delta._gated_delta_block.clear_cache()
    *ins, cot = _op_inputs(2)
    attrs = dict(num_heads=H, chunk_size=CHUNK)

    def loss(*a):
        return jnp.sum(delta._gated_delta_net(attrs, list(a), True)[0] * cot)

    grad = jax.jit(jax.grad(loss, tuple(range(len(ins)))))
    calls = list(_pallas_calls(grad.trace(*ins).jaxpr.jaxpr))
    names = sorted(str(c.params["name"]) for c in calls)
    assert names == ["gdn_bwd_f32_c16_k32_v64",
                     "gdn_fwd_f32_c16_k32_v64"], names
    assert not any(c.params["interpret"] for c in calls)
    lowered = grad.lower(*ins)
    text = lowered.as_text()
    assert "tpu_custom_call" not in text and "gdn_fwd" not in text
    got = lowered.compile()(*ins)
    want = jax.grad(lambda *a: jnp.sum(
        ref.gated_delta_net(*a, GDN) * cot), tuple(range(len(ins))))(*ins)
    for name, g, w in zip(OP_GRADS, got, want):
        _close(g, w, name, ulps=128)


def test_a_fit_of_three_linear_layers_traces_each_kernel_once():
    """``Module.fit`` of one period ``L L L F`` at widths the kernels
    take: three call sites count themselves under ``impl="kernel"``, the
    forward and the backward kernel are traced once each whatever the
    depth (``linear_attn.kernel_traces``), and the loss falls."""
    lin, full = "linear_attention", "full_attention"
    t = 32
    cfg = dict(
        model_type="olmo_hybrid", vocab_size=256, hidden_size=48,
        intermediate_size=40, num_hidden_layers=4,
        layer_types=[lin, lin, lin, full], num_attention_heads=4,
        num_key_value_heads=4, hidden_act="silu", max_position_embeddings=t,
        attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=32, linear_value_head_dim=32,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None})
    sym = olmo_hybrid.from_config(cfg, seq_len=t, chunk_size=CHUNK)
    rng = np.random.RandomState(3)
    tokens = rng.randint(0, cfg["vocab_size"], (1, t + 1))
    steps = 4
    for jitted in (delta._gated_delta_block, pk.gdn.gdn_fwd_call,
                   pk.gdn.gdn_bwd_call, pk.gdn.gdn_forward):
        jitted.clear_cache()    # another test's trace is not this one's
    telemetry.reset()
    telemetry.enable()
    try:
        seen = []
        mx.random.seed(4)
        np.random.seed(4)
        mod = mx.mod.Module(sym, context=mx.cpu(0), mesh=make_mesh(dp=1))
        mod.fit(mx.io.NDArrayIter(
            np.tile(tokens[:, :-1].astype(np.float32), (steps, 1)),
            np.tile(tokens[:, 1:].astype(np.float32), (steps, 1)),
            batch_size=1),
            num_epoch=1, eval_metric="loss", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            kvstore="device", initializer=mx.init.Normal(sigma=0.05),
            batch_end_callback=lambda b: (
                seen.append(b.eval_metric.get()[1]),
                b.eval_metric.reset()))
        assert mod._fused_trainer is not None
        sites = telemetry.REGISTRY.get("linear_attn.lowerings")
        assert sites.value(heads=2, key_dim=32, value_dim=32, chunk=CHUNK,
                           conv=4, impl="kernel") == 3
        assert telemetry.total("linear_attn.lowerings") == 3
        traces = telemetry.REGISTRY.get("linear_attn.kernel_traces")
        assert [traces.value(mode=mode, heads_a_trip=2)
                for mode in ("fwd", "bwd")] == [1, 1]
        assert telemetry.total("linear_attn.kernel_traces") == 2
        # 32 tokens: no row tile of the gate and norm's kernel divides them
        norm = telemetry.REGISTRY.get("gate_norm.lowerings")
        assert norm.value(site="gated_delta_net", groups=2, width=32,
                          impl="jnp") == 3
        assert telemetry.total("gate_norm.lowerings") == 3
    finally:
        telemetry.disable()
        telemetry.reset()
    assert np.isfinite(seen).all() and seen[-1] < seen[0], seen


def _kimi_linear(t):
    from mxnet_tpu.models import kimi_linear
    return kimi_linear.from_config(dict(
        model_type="kimi_linear", hidden_size=48, num_hidden_layers=2,
        first_k_dense_replace=1, moe_layer_freq=1,
        linear_attn_config=dict(kda_layers=[1, 2], full_attn_layers=[],
                                num_heads=2, head_dim=128,
                                short_conv_kernel_size=4),
        num_attention_heads=4, num_key_value_heads=4, head_dim=12,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=32, q_lora_rank=None, mla_use_nope=True,
        rope_theta=10000, rope_scaling=None, intermediate_size=96,
        moe_intermediate_size=32, num_experts=4, num_shared_experts=1,
        num_experts_per_token=2, moe_renormalize=True,
        moe_router_activation_func="sigmoid", num_expert_group=1,
        topk_group=1, use_grouped_topk=True, routed_scaling_factor=2.446,
        rms_norm_eps=1e-5, vocab_size=256, hidden_act="silu",
        tie_word_embeddings=False, num_nextn_predict_layers=0,
        model_max_length=t), seq_len=t, chunk_size=CHUNK)


def _solar_open2(t):
    from mxnet_tpu.models import solar_open2
    return solar_open2.from_config(dict(
        model_type="solar_open2", hidden_size=48, num_hidden_layers=3,
        first_k_dense_replace=0, gqa_interval=3, gqa_layers=[0],
        linear_attn_config=dict(num_heads=2, head_dim=128, num_kv_heads=None,
                                short_conv_kernel_size=4),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
        kda_allow_neg_eigval=True, partial_rotary_factor=1, rope_theta=10000,
        intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4,
        n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=1, rms_norm_eps=1e-5, vocab_size=256,
        tie_word_embeddings=False, max_position_embeddings=t),
        seq_len=t, chunk_size=CHUNK)


@pytest.mark.parametrize("model", [_kimi_linear, _solar_open2],
                         ids=["kimi_linear", "solar_open2"])
def test_a_fit_of_a_channel_model_says_its_body_takes_two_heads_a_trip(model):
    """``Module.fit`` of two KDA layers of Kimi Linear's and of
    Solar-Open2's symbol at heads the channel pair takes (two of 128 /
    128): both call sites count themselves ``impl="kernel",
    decay="channel"``, ``kda_fwd_`` and ``kda_bwd_`` are traced once each
    and ``linear_attn.kernel_traces`` says which body that was:
    ``heads_a_trip=2`` (Olmo-Hybrid's fit is the test above)."""
    t, steps = 32, 3
    sym = model(t)
    tokens = np.random.RandomState(5).randint(0, 256, (1, t + 1))
    for jitted in (delta._channel_delta_block, pk.gdn.kda_fwd_call,
                   pk.gdn.kda_bwd_call, pk.gdn.kda_net_forward):
        jitted.clear_cache()    # another test's trace is not this one's
    telemetry.reset()
    telemetry.enable()
    try:
        seen = []
        mx.random.seed(4)
        np.random.seed(4)
        mod = mx.mod.Module(sym, context=mx.cpu(0), mesh=make_mesh(dp=1))
        mod.fit(mx.io.NDArrayIter(
            np.tile(tokens[:, :-1].astype(np.float32), (steps, 1)),
            np.tile(tokens[:, 1:].astype(np.float32), (steps, 1)),
            batch_size=1),
            num_epoch=1, eval_metric="loss", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            kvstore="device", initializer=mx.init.Normal(sigma=0.05),
            batch_end_callback=lambda b: (
                seen.append(b.eval_metric.get()[1]),
                b.eval_metric.reset()))
        assert mod._fused_trainer is not None
        sites = telemetry.REGISTRY.get("linear_attn.lowerings")
        assert sum(v for k, v in sites._values.items()
                   if ("impl", "kernel") in k
                   and ("decay", "channel") in k) == 2
        assert telemetry.total("linear_attn.lowerings") == 2
        traces = telemetry.REGISTRY.get("linear_attn.kernel_traces")
        assert [traces.value(mode=mode, heads_a_trip=2)
                for mode in ("fwd", "bwd")] == [1, 1]
        assert telemetry.total("linear_attn.kernel_traces") == 2
    finally:
        telemetry.disable()
        telemetry.reset()
    assert np.isfinite(seen).all(), seen


# -- a decay a channel (Kimi Delta Attention): the pair kda_fwd_ / kda_bwd_ ---

def _channel_inputs(seed, batch, t, heads, dk, dv, dtype=jnp.float32,
                    g_min=-0.5, repeat=0):
    """``_inputs`` with a log decay a channel, uniform in (``g_min``, 0)."""
    ins, cot = _inputs(seed, batch, t, heads, dk, dv, dtype, repeat)
    rng = np.random.RandomState(seed + 100)
    g = (-1e-3 if repeat else g_min) * rng.rand(batch, t, heads, dk)
    return ins[:3] + (jnp.asarray(g, jnp.float32), ins[4]), cot


def _channel_both_ways(form, chunk):
    """``_both_ways`` of the forms whose g is [B, T, H, K]."""
    return _both_ways(form if form == "recurrence" else "channel_" + form,
                      chunk)


# (batch, T, heads, K, V, chunk): the Kimi Linear cell's head (a lane row
# of keys, one of values) in chunks of 64 over a ragged length (three
# heads: a pair and a head beside zeros); ten heads, so five groups of a
# pair a step, in chunks of one sub-block; values of two lane rows in
# chunks of two sub-blocks, ragged (two heads: one pair); fifteen heads,
# three groups of two pairs and a head beside zeros
CHANNEL_SHAPES = {
    "the_cells_head_ragged": (1, 130, 3, 128, 128, 64),
    "two_head_groups": (2, 32, 10, 128, 128, 16),
    "values_of_two_lane_rows": (1, 70, 2, 128, 256, 32),
    "fifteen_heads": (1, 24, 15, 128, 128, 16),
}


@pytest.mark.parametrize("shape", list(CHANNEL_SHAPES))
def test_the_channel_pair_matches_the_chunk_form_and_the_recurrence(shape):
    """Output and the gradient of q, k, v, g ([B, T, H, K]) and beta in
    float32, to summation order, against both."""
    *sizes, chunk = CHANNEL_SHAPES[shape]
    assert pk.gdn_takes(*sizes[2:], chunk, jnp.float32, "channel")
    ins, cot = _channel_inputs(0, *sizes)
    o, grads = _channel_both_ways("kernels", chunk)(ins, cot)
    for other in ("chunked", "recurrence"):
        o_w, grads_w = _channel_both_ways(other, chunk)(ins, cot)
        _close(o, o_w, "o against " + other, ulps=32)
        for name, got, want in zip(GRADS, grads, grads_w):
            assert got.shape == want.shape and got.dtype == want.dtype
            assert float(jnp.abs(want).max()) > 1e-6, name
            _close(got, want, name + " against " + other, ulps=128)


def test_the_channel_pair_in_bf16_is_where_the_chunk_form_is():
    """bf16 q, k and v at the cell's head: both forms round the same
    operands to bf16 and keep decays, tables, the system, its solution and
    the state float32, so each is a small share of a tensor's rms from the
    float32 recurrence, the kernels no farther than the chunk form."""
    *sizes, chunk = CHANNEL_SHAPES["the_cells_head_ragged"]
    assert pk.gdn_takes(*sizes[2:], chunk, jnp.bfloat16, "channel")
    ins, cot = _channel_inputs(3, *sizes, dtype=jnp.bfloat16)
    exact = tuple(v.astype(jnp.float32) for v in ins)
    o, ours = _channel_both_ways("kernels", chunk)(ins, cot)
    o_c, theirs = _channel_both_ways("chunked", chunk)(ins, cot)
    o_w, want = _channel_both_ways("recurrence", chunk)(exact, cot)
    assert o.dtype == jnp.float32
    assert _rms(o, o_w) < max(1.5 * _rms(o_c, o_w), 0.004)
    for name, g, c, w in zip(GRADS, ours, theirs, want):
        assert g.dtype == c.dtype and g.shape == c.shape, name
        assert _rms(g, w) < max(1.5 * _rms(c, w), 0.004), (
            name, _rms(g, w), _rms(c, w))


@pytest.mark.parametrize("g_min", [-20.0, -3.0])
def test_the_channel_pair_takes_no_exponential_of_a_positive_number(g_min):
    """LOG DECAYS DOWN TO -20 A TOKEN (the range of ``tests/
    test_kimi_linear.py::test_the_factored_form_would_overflow_where_the_
    chunk_form_is_exact``): over a chunk of 64 the running sum reaches
    -1,280 and the factored operands ``k exp(-b)`` are inf from the fifth
    token on. The pair's output and gradients are finite and the chunk
    form's, and both are the recurrence's."""
    chunk = 64
    ins, cot = _channel_inputs(5, 1, 128, 2, 128, 128, g_min=g_min)
    if g_min < -10:
        cum = jnp.cumsum(ins[3][:, :chunk], axis=1)
        assert not np.isfinite(np.asarray(ins[1][:, :chunk]
                                          * jnp.exp(-cum))).all()
    o, grads = _channel_both_ways("kernels", chunk)(ins, cot)
    assert np.isfinite(np.asarray(o)).all()
    for other in ("chunked", "recurrence"):
        o_w, grads_w = _channel_both_ways(other, chunk)(ins, cot)
        _close(o, o_w, "o against " + other, ulps=32)
        for name, got, want in zip(GRADS, grads, grads_w):
            assert np.isfinite(np.asarray(got)).all(), name
            _close(got, want, name + " against " + other, ulps=128)


def test_one_decay_a_head_through_the_channel_pair_is_the_scalar_pair():
    """Every channel of a head given one decay: the channel pair computes
    what the scalar pair does, output and gradients (g's summed over the
    channels it was spread to)."""
    chunk = 32
    ins, cot = _inputs(6, 1, 70, 2, 128, 128)
    spread = jax.jit(jax.value_and_grad(
        lambda q, k, v, g, beta: jnp.sum(pk.gdn.channel_delta_rule(
            q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, chunk,
            interpret=True) * cot), EVERY))
    (_, got), (o_w, want) = spread(*ins), _both_ways("kernels", chunk)(
        ins, cot)
    _close(pk.gdn.channel_delta_rule(
        *ins[:3], jnp.broadcast_to(ins[3][..., None], ins[0].shape),
        ins[4], chunk, interpret=True), o_w, "o", ulps=32)
    for name, g, w in zip(GRADS, got, want):
        _close(g, w, name, ulps=128)


def test_the_channel_pair_solves_repeated_keys_by_substitution():
    """The repeated-keys case on the channel pair: four directions over
    and over, strengths near 2, nothing forgotten. As near the recurrence
    as the chunk form's ``solve_triangular``."""
    chunk = 64
    ins, cot = _channel_inputs(7, 1, 128, 2, 128, 128, repeat=4)
    o_w, want = _channel_both_ways("recurrence", chunk)(ins, cot)
    o, ours = _channel_both_ways("kernels", chunk)(ins, cot)
    o_c, theirs = _channel_both_ways("chunked", chunk)(ins, cot)
    scale = float(jnp.abs(o_w).max())
    err, err_c = (float(jnp.abs(x - o_w).max()) / scale for x in (o, o_c))
    assert err <= max(2 * err_c, 1e-4), (err, err_c)
    for name, g, c, w in zip(GRADS, ours, theirs, want):
        top = float(jnp.abs(w).max())
        e, e_c = (float(jnp.abs(x - w).max()) / top for x in (g, c))
        assert e <= max(2 * e_c, 1e-3), (name, e, e_c)


def test_the_channel_pair_takes_the_kimi_cells_shape_and_refuses_the_rest():
    take = functools.partial(pk.gdn_takes, decay="channel")
    assert take(32, 128, 128, 64, jnp.bfloat16)             # the cell's
    assert take(32, 128, 128, 64, jnp.float32)
    assert take(3, 128, 256, 16, jnp.float32)
    assert take(16, 256, 128, 128, jnp.bfloat16)
    assert pk.gdn.kda_group(32) == 8 and pk.gdn.kda_group(10) == 2
    for heads, dk, dv, chunk, dtype in [
            (3, 8, 12, 16, jnp.float32),         # the tiny symbol's
            (32, 96, 192, 64, jnp.bfloat16),     # a head no whole lane rows
            (32, 128, 192, 64, jnp.bfloat16),
            (32, 128, 128, 24, jnp.bfloat16),    # no sub-block divides it
            (32, 128, 128, 8, jnp.bfloat16),
            (32, 128, 128, 256, jnp.bfloat16),   # a chunk over a lane row
            (32, 128, 128, 64, jnp.float16),     # not Mosaic's operand
            (8, 2048, 4096, 128, jnp.float32),   # a step over VMEM
            (0, 128, 128, 64, jnp.bfloat16)]:
        assert not take(heads, dk, dv, chunk, dtype), (heads, dk, dv, chunk)
    assert not pk.gdn_takes(32, 128, 128, 64, jnp.bfloat16, "matrix")
    assert (pk.gdn.kda_vmem_bytes(128, 8, 8, 2048, 4096, 4)
            > pk.common.VMEM_RAISED_LIMIT)


# the op in its channel form at a shape the pair takes
CH, CD, CCHUNK, CT = 2, 128, 16, 40


def _channel_op_inputs(seed, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), CH * CD))

    def draw(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(shift + scale * rng.randn(*shape), dtype)

    return (draw(BATCH, CT, CH * CD), draw(BATCH, CT, CH * CD),
            draw(BATCH, CT, CH * CD), draw(BATCH, CT, CH * CD),
            draw(BATCH, CT, CH * CD, scale=2.0), draw(BATCH, CT, CH),
            draw(TAPS, 3 * CH * CD, scale=0.3),
            jnp.asarray(np.log(rng.uniform(1, 16, CH)), dtype),
            jnp.asarray(step + np.log(-np.expm1(-step)), dtype),
            draw(CD, scale=0.1, shift=1.0),
            jnp.asarray(rng.randn(BATCH, CT, CH * CD), jnp.float32))


def _channel_block(kernel, interpret, remat, ins, cot):
    """Output and gradients of the block the op hands such a call to
    (``kernel``: ``_channel_delta_block``, the pair from the taps' outputs
    on) or of ``_gated_delta_block`` in the ``jax.numpy`` chunk form."""
    kw = dict(heads=CH, chunk=CCHUNK, eps=1e-6, beta_scale=1.0, remat=remat,
              taps_kernel=(False,) * 3, interpret=interpret,
              gate_act="sigmoid", norm_kernel=False)
    block = (delta._channel_delta_block if kernel else functools.partial(
        delta._gated_delta_block, kernel=False))

    def loss(*a):
        o = block(*a, **kw)
        return jnp.sum(o * cot), o
    return jax.jit(jax.value_and_grad(loss, tuple(range(len(ins))),
                                      has_aux=True))(*ins)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_channel_op_through_the_pair_is_the_op_in_the_chunk_form(remat):
    """``GatedDeltaNet`` with ``a`` as wide as the keys and a sigmoid gate,
    the rule's pair through the interpreter (what the TPU's branch
    computes) against the op in the ``jax.numpy`` chunk form: forward and
    the gradient of all ten inputs, in training too."""
    *ins, cot = _channel_op_inputs(0)
    assert pk.gdn_takes(CH, CD, CD, CCHUNK, jnp.float32, "channel")
    delta._channel_delta_block.clear_cache()
    (_, o), got = _channel_block(True, True, remat, ins, cot)
    (_, o_w), want = _channel_block(False, False, remat, ins, cot)
    _close(o, o_w, "out", ulps=32)
    for name, g, w in zip(OP_GRADS, got, want):
        assert g.shape == w.shape
        _close(g, w, name, ulps=256)
    delta._channel_delta_block.clear_cache()


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_off_the_tpu_the_channel_ops_values_are_the_chunk_forms_bit_for_bit(
        remat):
    """A step lowered for the CPU at a shape the channel pair takes
    computes what the op computed before there was a pair: output and
    every gradient equal to the bit, as one program."""
    *ins, cot = _channel_op_inputs(1)
    delta._channel_delta_block.clear_cache()
    (_, o), got = _channel_block(True, False, remat, ins, cot)
    (_, o_w), want = _channel_block(False, False, remat, ins, cot)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_w))
    for name, g, w in zip(OP_GRADS, got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)


def test_a_channel_training_step_holds_each_kernel_once_never_interpreted():
    """The gradient's program of the ``GatedDeltaNet`` op in its channel
    form in training: ONE ``kda_fwd_`` and one ``kda_bwd_`` (the pair keeps
    its own residuals and makes the unit norms and decays itself: ``_pre``),
    both for Mosaic and neither the scalar pair;
    lowered for the CPU it holds no kernel at all and runs, and the call
    site counted itself ``impl="kernel", decay="channel"``."""
    delta._channel_delta_block.clear_cache()
    *ins, cot = _channel_op_inputs(2)
    attrs = dict(num_heads=CH, chunk_size=CCHUNK, allow_neg_eigval=False,
                 gate_act="sigmoid")

    def loss(*a):
        return jnp.sum(delta._gated_delta_net(attrs, list(a), True)[0] * cot)

    telemetry.reset()
    telemetry.enable()
    try:
        grad = jax.jit(jax.grad(loss, tuple(range(len(ins)))))
        calls = list(_pallas_calls(grad.trace(*ins).jaxpr.jaxpr))
        sites = telemetry.REGISTRY.get("linear_attn.lowerings")
        assert sites.value(heads=CH, key_dim=CD, value_dim=CD, chunk=CCHUNK,
                           conv=TAPS, impl="kernel", decay="channel",
                           gate="sigmoid") == 1
        assert telemetry.total("linear_attn.lowerings") == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    names = sorted(str(c.params["name"]) for c in calls
                   if "taps_" not in str(c.params["name"]))
    assert names == ["kda_bwd_f32_c16_k128_v128_pre",
                     "kda_fwd_f32_c16_k128_v128_pre"], names
    assert not any(c.params["interpret"] for c in calls)
    lowered = grad.lower(*ins)
    text = lowered.as_text()
    assert "tpu_custom_call" not in text and "kda_fwd" not in text
    got = lowered.compile()(*ins)
    (_, _), want = _channel_block(False, False, True, ins, cot)
    for name, g, w in zip(OP_GRADS, got, want):
        _close(g, w, name, ulps=256)
