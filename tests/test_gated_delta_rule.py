"""The gated delta rule in its chunk form against the rule itself.

``ops/transformer.gated_delta_rule`` (one unit-triangular system a
chunk and head, three products with the state inside a ``lax.scan`` over
the chunks) and ``gated_delta_net`` (the convolution, the unit keys and
queries, write strengths and decays round it, the norm and its gate
after it) against the token-by-token recurrence ``S_t = a_t S_{t-1} +
k_t u_t^T``, ``u_t = beta_t (v_t - a_t S_{t-1}^T k_t)``, ``o_t = S_t^T
q_t`` written out here and in ``models/olmo_hybrid_reference.py``, at a
tiny size: 3 heads with keys of 8 and values of 16, 4 taps, T 37 (not a
multiple of any chunk), chunks of 8 and 16.

Tolerances as in ``tests/test_nemotron_h.py``: both sides are float32
and only the order of summation differs (the chunk form solves a chunk's
tokens at once and crosses chunks through ``exp`` of summed log decays
where the recurrence multiplies decay by decay), so rtol 1e-5 with an
atol of a few float32 ulps of the tensor's own scale (``_close``);
``ulps`` is raised for gradients, which are long sums of such terms
through the triangular solve and five chunks. A dropped carried state is
off by orders of magnitude more: the carried-state case measures that
distance.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import olmo_hybrid_reference as ref
from mxnet_tpu.ops.transformer import gated_delta_net, gated_delta_rule

BATCH, T, H, DK, DV, TAPS = 2, 37, 3, 8, 16, 4
CFG = dict(linear_num_key_heads=H, linear_key_head_dim=DK,
           linear_value_head_dim=DV, linear_allow_neg_eigval=True,
           rms_norm_eps=1e-6)


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def recurrence(q, k, v, g, beta):
    """The rule, one token after another: q and k [B, T, H, K], v [B, T,
    H, V], g and beta [B, T, H] -> o [B, T, H, V]."""
    def token(state, at):                                     # [B, H, K, V]
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        u_t = beta_t[..., None] * (
            v_t - jnp.sum(state * k_t[..., None], axis=2))
        state = state + k_t[..., None] * u_t[:, :, None, :]
        return state, jnp.sum(state * q_t[..., None], axis=2)

    _, o = jax.lax.scan(
        token, jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[3:]),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _core_inputs(seed, t=T, beta="spread", g="spread"):
    """Unit keys, scaled unit queries, and write strengths and log
    decays of the kind asked for: ``beta`` spread over (0, 2) or within
    0.02 of 2 (the reflection ``I - 2 k k^T``), ``g`` spread over (-0.5,
    0), within 1e-3 of 0 (nothing forgotten) or in (-6, -3) (nearly
    everything forgotten every token)."""
    rng = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    q, k = rng.randn(2, BATCH, t, H, DK)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    u = rng.rand(2, BATCH, t, H)
    beta = {"spread": 2 * u[0], "near_2": 2 - 0.02 * u[0]}[beta]
    g = {"spread": -0.5 * u[1], "near_0": -1e-3 * u[1],
         "strong": -3 - 3 * u[1]}[g]
    return (f32(q), f32(k), f32(rng.randn(BATCH, t, H, DV)), f32(g),
            f32(beta), f32(rng.randn(BATCH, t, H, DV)))


@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("beta, g", [
    ("spread", "spread"), ("near_2", "spread"), ("spread", "near_0"),
    ("near_2", "near_0"), ("spread", "strong")])
def test_the_chunk_form_matches_the_token_recurrence(chunk, beta, g):
    """Output and the gradient of q, k, v, g and beta, float32 to
    summation order, at two chunk sizes that do not divide T; with write
    strengths near 2 and nothing forgotten the state is reflected, not
    shrunk, token after token, and the triangular system is at its worst
    conditioned."""
    *ins, cot = _core_inputs(0, beta=beta, g=g)
    _close(gated_delta_rule(*ins, chunk), recurrence(*ins), "o", ulps=16)
    every = tuple(range(5))
    got = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a, chunk) * cot),
                   every)(*ins)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * cot), every)(*ins)
    for name, got_g, want_g in zip(("dq", "dk", "dv", "dg", "dbeta"), got,
                                   want):
        assert got_g.shape == want_g.shape
        assert float(jnp.abs(want_g).max()) > 1e-6, name
        _close(got_g, want_g, name, ulps=128)


def test_the_chunk_size_changes_nothing_but_the_order_of_summation():
    *ins, _ = _core_inputs(1, t=32)
    want = recurrence(*ins)
    for chunk in (4, 8, 32, 64):   # 64: one chunk, mostly padding
        _close(gated_delta_rule(*ins, chunk), want, "chunk %d" % chunk,
               ulps=16)


def test_dropping_the_state_carried_between_chunks_is_caught():
    """THE CARRIED-STATE TEST. With decays of the published spread (a
    token keeps between 0.6 and all of the state), what a chunk inherits
    from the chunks before it is a measurable share of ``o``: the rule
    run chunk by chunk from a zero state (the carried state dropped)
    differs from the whole by more than a tenth of o's standard
    deviation past the first chunk, far outside the tolerance the whole
    meets against the recurrence."""
    chunk, t = 8, 32
    *ins, _ = _core_inputs(2, t=t)
    whole = gated_delta_rule(*ins, chunk)
    want = recurrence(*ins)
    _close(whole, want, "the chunk form", ulps=16)
    dropped = jnp.concatenate(
        [gated_delta_rule(*(x[:, s:s + chunk] for x in ins), chunk)
         for s in range(0, t, chunk)], axis=1)
    _close(dropped[:, :chunk], whole[:, :chunk], "the first chunk", ulps=16)
    carried = float(jnp.sqrt(jnp.mean(
        (whole - dropped)[:, chunk:] ** 2)) / want[:, chunk:].std())
    assert carried > 0.1, carried
    with pytest.raises(AssertionError):
        _close(dropped, want, "the carried state dropped", ulps=16)


# -- the op round it: convolution, norms, strengths, decays, gate -----------

def _dynamics(rng, heads):
    """``a_log`` and ``dt_bias`` by the published rule (what
    ``init.LogOfUniform`` and ``init.InverseSoftplus`` draw)."""
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), heads))
    return (np.log(rng.uniform(1, 16, heads)).astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


def _op_inputs(seed, t=T, dtype=jnp.float32, heads=H):
    rng = np.random.RandomState(seed)
    a_log, dt_bias = _dynamics(rng, heads)

    def draw(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(shift + scale * rng.randn(*shape), dtype)

    return (draw(BATCH, t, heads * DK), draw(BATCH, t, heads * DK),
            draw(BATCH, t, heads * DV), draw(BATCH, t, heads * DV),
            draw(BATCH, t, heads, scale=2.0), draw(BATCH, t, heads),
            draw(TAPS, 2 * heads * DK + heads * DV, scale=0.3),
            jnp.asarray(a_log, dtype), jnp.asarray(dt_bias, dtype),
            draw(DV, scale=0.1, shift=1.0),
            jnp.asarray(rng.randn(BATCH, t, heads * DV), jnp.float32))


def _op(*ins, chunk=8, heads=H, neg=True):
    return gated_delta_net(*ins, num_heads=heads, chunk_size=chunk, eps=1e-6,
                           allow_neg_eigval=neg)


def _plain(*ins, heads=H, neg=True):
    return ref.gated_delta_net(*ins, dict(
        CFG, linear_num_key_heads=heads, linear_allow_neg_eigval=neg))


NAMES = ("dquery", "dkey", "dvalue", "dgate", "da", "db", "dconv_weight",
         "da_log", "ddt_bias", "dnorm_gamma")


@pytest.mark.parametrize("t, neg", [(37, True), (32, True), (5, True),
                                    (37, False)],
                         ids=["ragged_last_chunk", "four_chunks",
                              "under_a_chunk", "no_negative_eigenvalues"])
def test_the_op_matches_the_reference_layer(t, neg):
    """Forward and the gradient with respect to every input (the six
    projections, the taps, the rates, the step sizes' bias, the gated
    norm's scale), float32 to summation order; ``allow_neg_eigval``
    false halves the write strengths, in the op and the reference
    alike."""
    *ins, cot = _op_inputs(0, t)
    _close(_op(*ins, neg=neg), _plain(*ins, neg=neg), "out", ulps=16)
    every = tuple(range(len(ins)))
    got = jax.grad(lambda *a: jnp.sum(_op(*a, neg=neg) * cot), every)(*ins)
    want = jax.grad(lambda *a: jnp.sum(_plain(*a, neg=neg) * cot),
                    every)(*ins)
    for name, got_g, want_g in zip(NAMES, got, want):
        assert got_g.shape == want_g.shape
        assert float(jnp.abs(want_g).max()) > 1e-5, name
        _close(got_g, want_g, name, ulps=128)
    # the factor 2 is part of the result
    assert float(jnp.abs(_op(*ins, neg=True)
                         - _op(*ins, neg=False)).max()) > 1e-2


def test_remat_changes_no_value():
    """Training recomputes the three scopes in the backward pass
    (``jax.checkpoint``): the same output and gradients."""
    *ins, cot = _op_inputs(3)

    def loss(remat):
        return lambda *a: jnp.sum(gated_delta_net(
            *a, num_heads=H, chunk_size=8, eps=1e-6, remat=remat) * cot)

    every = tuple(range(len(ins)))
    plain = jax.grad(loss(False), every)(*ins)
    again = jax.grad(loss(True), every)(*ins)
    for name, a, b in zip(NAMES, plain, again):
        _close(b, a, name, ulps=16)


def test_the_norm_comes_before_the_gate():
    """``RMSNorm(o) gamma * silu(gate)``: doubling the gate's
    pre-activation where it is large doubles the output (the gate is
    outside the norm); ``Mamba2``'s norm, gate first, would not."""
    *ins, _ = _op_inputs(4)
    ins = list(ins)
    ins[3] = jnp.full_like(ins[3], 20.0)        # silu(20) = 20
    once = _op(*ins)
    ins[3] = ins[3] * 2
    _close(_op(*ins), 2 * once, "gate doubled", ulps=16)


def test_in_bf16_the_decays_the_solve_and_the_state_stay_float32():
    """bf16 inputs; the op's convolution sum, unit norms, write
    strengths, decays, triangular solve, carried state, gate and norm
    statistics are float32: against the float32 reference on the same
    bf16-rounded inputs the output's rms error is that of rounding the
    products' operands and the result to bf16. The reference one
    precision below (all of those in bf16, the state carried through 256
    tokens in bf16) is further off on every seed; the limit 0.0095 lies
    between the two readings, 1.23x from either (measured here, seeds
    0..5: ours 0.0053-0.0077 of the output's standard deviation, the
    bf16 recurrence 0.0117-0.0193)."""
    for seed in range(3):
        *ins, _ = _op_inputs(seed, 256, jnp.bfloat16, heads=4)
        want = _plain(*[a.astype(jnp.float32) for a in ins], heads=4)

        def rms(out):
            return float(jnp.sqrt(jnp.mean(
                (out.astype(jnp.float32) - want) ** 2)) / want.std())

        got = _op(*ins, chunk=64, heads=4)
        assert got.dtype == jnp.bfloat16
        ours, theirs = rms(got), rms(_plain(*ins, heads=4))
        assert ours < 0.0095 < theirs, (seed, ours, theirs)


def test_the_op_infers_its_parameters_and_checks_its_inputs():
    def infer(q=(2, T, H * DK), v=(2, T, H * DV), **attrs):
        attrs = dict(dict(num_heads=H), **attrs)
        op = mx.contrib.sym.GatedDeltaNet(
            *(mx.sym.Variable(n) for n in ("q", "k", "v", "g", "a", "b")),
            name="gdn", **attrs)
        return op.list_arguments(), op.infer_shape(q=q, v=v)

    names, (ins, outs, _) = infer()
    assert names == ["q", "k", "v", "g", "a", "b", "gdn_conv_weight",
                     "gdn_a_log", "gdn_dt_bias", "gdn_norm_gamma"]
    assert ins == [(2, T, H * DK)] * 2 + [(2, T, H * DV)] * 2 \
        + [(2, T, H)] * 2 + [(TAPS, 2 * H * DK + H * DV), (H,), (H,), (DV,)]
    assert outs == [(2, T, H * DV)]
    assert infer(conv_kernel=3)[1][0][6] == (3, 2 * H * DK + H * DV)
    for bad, what in [(dict(q=(2, T, H * DK + 1)), "query must be"),
                      (dict(v=(2 * T, H * DV)), "value must be"),
                      (dict(v=(2, T + 1, H * DV)), "batch and time"),
                      (dict(chunk_size=0), "positive"),
                      (dict(num_heads=0), "positive")]:
        with pytest.raises(Exception, match=what):
            infer(**bad)
    assert mx.executor.op_class("_contrib_GatedDeltaNet") == "gdn"


def test_the_taps_kernel_leaves_the_op_its_outputs_and_gradients(
        monkeypatch):
    """``GatedDeltaNet`` in training at a shape the taps' kernel family
    takes (T 256; query and key 128 wide, value 256), the three
    convolutions as the kernel pair through the Pallas interpreter (the
    delta rule's own pair with them), against the op with ``causal_taps``
    under its checkpoints (``taps_takes`` made to refuse): the output to
    the last bit, every gradient to summation order."""
    from mxnet_tpu.ops import kernels as pk
    from mxnet_tpu.ops.transformer import delta

    heads, t = 2, 256
    rng = np.random.RandomState(9)
    a_log, dt_bias = _dynamics(rng, heads)

    def draw(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(shift + scale * rng.randn(*shape), jnp.float32)

    ins = (draw(1, t, heads * 64), draw(1, t, heads * 64),
           draw(1, t, heads * 128), draw(1, t, heads * 128),
           draw(1, t, heads, scale=2.0), draw(1, t, heads),
           draw(TAPS, 2 * heads * 64 + heads * 128, scale=0.3),
           jnp.asarray(a_log), jnp.asarray(dt_bias),
           draw(128, scale=0.1, shift=1.0))
    cot = draw(1, t, heads * 128)
    every = tuple(range(len(ins)))

    def op(*a):
        return gated_delta_net(*a, num_heads=heads, chunk_size=64, eps=1e-6,
                               remat=True)

    def run():
        delta._gated_delta_block.clear_cache()
        return op(*ins), jax.grad(lambda *a: jnp.sum(op(*a) * cot),
                                  every)(*ins)

    monkeypatch.setattr(pk.common, "INTERPRET", True)
    assert all(pk.taps_takes(x.shape[2], t, TAPS, x.dtype, "silu", 0,
                             x.shape[2]) for x in ins[:3])
    out, grads = run()
    monkeypatch.setattr(pk, "taps_takes", lambda *a, **k: False)
    was, were = run()
    delta._gated_delta_block.clear_cache()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(was))
    for name, g, w in zip(NAMES, grads, were):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        _close(g, w, name, ulps=64)


def test_a_call_site_counts_its_lowering():
    """``linear_attn.lowerings``: one a trace of a call site, labelled
    with what decides the lowering; nothing a step."""
    *ins, _ = _op_inputs(5)
    telemetry.reset()
    telemetry.enable()
    try:
        jitted = jax.jit(lambda *a: _op(*a, chunk=16))
        jitted(*ins)
        jitted(*ins)
        jax.eval_shape(lambda *a: _op(*a, chunk=8, neg=False), *ins)
        count = telemetry.REGISTRY.get("linear_attn.lowerings")
        labels = dict(heads=H, key_dim=DK, value_dim=DV, conv=TAPS,
                      impl="chunked")
        assert count.value(chunk=16, **labels) == 1
        assert count.value(chunk=8, **labels) == 1
        assert telemetry.total("linear_attn.lowerings") == 2
    finally:
        telemetry.disable()
        telemetry.reset()
