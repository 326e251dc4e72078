"""Kimi-Linear-48B-A3B on the normal path against its plain reference.

``ops/transformer.channel_delta_rule`` (the delta rule whose decay is a
vector a head, in chunks cut into sub-blocks so that no exponential of a
positive number is taken) against the token-by-token recurrence written
out here; ``GatedDeltaNet`` in its channel form (``a`` as wide as the
keys, a sigmoid gate) and ``LatentAttention(rotary=False)`` against
``models/kimi_linear_reference.py``; ``models/kimi_linear.py`` (an
``mx.sym`` graph of both over shared and sigmoid-routed experts) through
``Module.forward/backward`` and ``Module.fit``'s fused step against the
same reference (plain float32 ``jax.numpy``: KDA one token after another,
attention by an explicit mask, a loop over the experts held) on seeded
weights at a tiny size: hidden 48, 5 layers (KDA, KDA, KDA, latent, KDA;
the first dense), 3 KDA heads of 8, 4 latent heads of 16 + 8 / 16 from a
latent of 32, 16 experts top-3 of width 32, 1 shared, T 40 (no multiple
of the chunk of 16).

Tolerances as in ``tests/test_gated_delta_rule.py``: both sides are
float32 and only the order of summation differs (the chunk form solves a
chunk's tokens at once and crosses sub-blocks and chunks through ``exp``
of summed log decays where the recurrence multiplies decay by decay), so
rtol 1e-5 with an atol of a few float32 ulps of the tensor's own scale
(``_close``); ``ulps`` is raised for gradients, which are long sums of
such terms through the triangular solve and the chunks. The bf16 case
measures its tolerance, see there.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import kimi_linear, kimi_linear_reference as ref
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import delta
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import topk_moe

T, BATCH = 40, 2
H, D, TAPS, CHUNK = 3, 8, 4, 16
HEADS, NOPE, ROPE, DV, LATENT = 4, 16, 8, 16, 32
CFG = dict(
    model_type="kimi_linear", hidden_size=48, num_hidden_layers=5,
    first_k_dense_replace=1, moe_layer_freq=1,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                            num_heads=H, head_dim=D,
                            short_conv_kernel_size=TAPS),
    num_attention_heads=HEADS, num_key_value_heads=HEADS, head_dim=12,
    qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=DV,
    kv_lora_rank=LATENT, q_lora_rank=None, mla_use_nope=True,
    rope_theta=10000, rope_scaling=None, intermediate_size=96,
    moe_intermediate_size=32, num_experts=16, num_shared_experts=1,
    num_experts_per_token=3, moe_renormalize=True,
    moe_router_activation_func="sigmoid", num_expert_group=1, topk_group=1,
    use_grouped_topk=True, routed_scaling_factor=2.446, rms_norm_eps=1e-5,
    vocab_size=512, hidden_act="silu", tie_word_embeddings=False,
    num_nextn_predict_layers=0, model_max_length=T)
# one chip's share of the same model: 4 of the 16 experts from the 8th
# on, a buffer that holds every row
SHARE = dict(CFG, num_experts=4, share=dict(
    experts_of=16, expert_offset=8, share_rows_bound=BATCH * T * 3))
EXPERT_LAYERS = 4
FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench", "configs",
    "kimi_linear_48b_a3b.json")


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


# -- the rule: the chunk form against the recurrence -------------------------

def recurrence(q, k, v, g, beta):
    """The rule, one token after another: q and k [B, T, H, K], v [B, T,
    H, V], g [B, T, H, K] and beta [B, T, H] -> o [B, T, H, V]."""
    def token(state, at):                                     # [B, H, K, V]
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None] * state
        u_t = beta_t[..., None] * (
            v_t - jnp.sum(state * k_t[..., None], axis=2))
        state = state + k_t[..., None] * u_t[:, :, None, :]
        return state, jnp.sum(state * q_t[..., None], axis=2)

    _, o = jax.lax.scan(
        token, jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[3:]),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _core_inputs(seed, t, g_min, dtype=jnp.float32):
    """Unit keys, scaled unit queries, write strengths over (0, 1) and
    log decays uniform in (``g_min``, 0) a channel."""
    rng = np.random.RandomState(seed)
    q, k = rng.randn(2, BATCH, t, H, D)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(BATCH, t, H, D + 4)             # values wider than keys
    g = g_min * rng.rand(BATCH, t, H, D)
    beta = rng.rand(BATCH, t, H)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


def _out_and_grads(fn, ins, seed=9):
    out = fn(*ins)
    cot = jnp.asarray(np.random.RandomState(seed).randn(*out.shape),
                      jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot),
                     argnums=tuple(range(len(ins))))(*ins)
    return out, grads


# T a multiple of the chunk and not; chunks of whole sub-blocks (64 = 4 x
# 16, 32 = 2 x 16), one that no sub-block divides (24: every pair from the
# difference itself) and one sub-block a chunk (16); gentle decays, and
# LOG DECAYS DOWN TO -20 A TOKEN: over a chunk the running sum reaches
# -1,280, and the factored form's exp(-b_j) = exp(1280) is inf in
# float32 (exp(88.8) already is)
@pytest.mark.parametrize("t,chunk,g_min", [
    (128, 64, -1.0), (100, 64, -1.0), (128, 64, -20.0), (70, 32, -20.0),
    (48, 24, -3.0), (37, 16, -0.05)])
def test_channel_chunk_form_matches_the_recurrence(t, chunk, g_min):
    ins = _core_inputs(t, t, g_min)
    got, got_grads = _out_and_grads(
        lambda *a: tr.channel_delta_rule(*a, chunk), ins)
    want, want_grads = _out_and_grads(recurrence, ins)
    assert np.isfinite(np.asarray(got)).all()
    _close(got, want, "o", ulps=32)
    for name, g, w in zip("q k v g beta".split(), got_grads, want_grads):
        assert np.isfinite(np.asarray(g)).all(), name
        _close(g, w, "d" + name, ulps=128)


def test_the_factored_form_would_overflow_where_the_chunk_form_is_exact():
    """What the sub-blocks are for: at -20 a token the factored operands
    ``k exp(-b)`` are inf from the fifth token of a chunk on, and the
    chunk form's every exponential is of a non-positive number."""
    q, k, v, g, beta = _core_inputs(3, 64, -20.0)
    cum = jnp.cumsum(g, axis=1)
    assert not np.isfinite(np.asarray(k * jnp.exp(-cum))).all()
    seen = []
    real_exp = jnp.exp

    def exp(x):
        seen.append(x)
        return real_exp(x)

    try:
        jnp.exp = exp
        out = tr.channel_delta_rule(q, k, v, g, beta, 64)
    finally:
        jnp.exp = real_exp
    assert len(seen) >= 5
    for x in seen:   # -inf where masked, never above 0
        assert float(jnp.max(x)) <= 0.0
    assert np.isfinite(np.asarray(out)).all()


def test_one_decay_a_head_is_the_scalar_rule():
    """Ties the new form to the old: with every channel of a head given
    the same decay the channel form computes ``gated_delta_rule``."""
    q, k, v, g, beta = _core_inputs(4, 100, -1.0)
    one = g[..., 0]
    got, got_grads = _out_and_grads(
        lambda q, k, v, g1, beta: tr.channel_delta_rule(
            q, k, v, jnp.broadcast_to(g1[..., None], g.shape), beta, 32),
        (q, k, v, one, beta))
    want, want_grads = _out_and_grads(
        lambda *a: tr.gated_delta_rule(*a, 32), (q, k, v, one, beta))
    _close(got, want, "o", ulps=32)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        _close(a, b, "d" + name, ulps=128)


def test_bf16_operands_are_one_rounding_from_float32():
    """bf16 q, k, v (the cell's): decays, tables, the solve and the state
    stay float32, the products take bf16 operands. Measured: the largest
    error is 0.6% of the output's largest value; a float32 state rounded
    to bf16 each chunk reads 2-3%."""
    ins = _core_inputs(5, 128, -1.0)
    want = tr.channel_delta_rule(*ins, 64)
    low = tuple(x.astype(jnp.bfloat16) for x in ins[:3]) + ins[3:]
    got = tr.channel_delta_rule(*low, 64)
    assert got.dtype == jnp.float32
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert err < 0.015, err


# -- the op: the channel form, the sigmoid gate, and the scalar path ---------

def _op_inputs(seed, t=T, channel=True, dv=D):
    rng = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    wide = lambda w: f32(rng.randn(BATCH, t, w))
    return (wide(H * D), wide(H * D), wide(H * dv), wide(H * dv),
            wide(H * D if channel else H), wide(H),
            f32(rng.uniform(-0.5, 0.5, (TAPS, 2 * H * D + H * dv))),
            f32(np.log(rng.uniform(1, 16, H))),
            f32(rng.uniform(-4, 1, H * D if channel else H)),
            f32(1 + 0.1 * rng.randn(dv)))


def test_the_op_in_its_channel_form_matches_the_reference():
    ins = _op_inputs(6)
    cfg = dict(linear_attn_config=CFG["linear_attn_config"],
               rms_norm_eps=1e-5)
    for remat in (False, True):
        got, got_grads = _out_and_grads(
            lambda *a: tr.gated_delta_net(
                *a, num_heads=H, chunk_size=CHUNK, eps=1e-5,
                allow_neg_eigval=False, remat=remat, gate_act="sigmoid"),
            ins)
        want, want_grads = _out_and_grads(
            lambda *a: ref.delta_attention(*a, cfg), ins)
        _close(got, want, "y", ulps=32)
        for i, (g, w) in enumerate(zip(got_grads, want_grads)):
            _close(g, w, "gradient %d" % i, ulps=256)


def test_gate_act_and_the_factor_on_beta_are_what_they_say():
    """A silu gate or a doubled write strength is another function: the
    reference (sigmoid, no factor 2) is off by far more than rounding."""
    ins = _op_inputs(7)
    cfg = dict(linear_attn_config=CFG["linear_attn_config"],
               rms_norm_eps=1e-5)
    want = ref.delta_attention(*ins, cfg)
    for kwargs in (dict(gate_act="silu", allow_neg_eigval=False),
                   dict(gate_act="sigmoid", allow_neg_eigval=True)):
        got = tr.gated_delta_net(*ins, num_heads=H, chunk_size=CHUNK,
                                 eps=1e-5, **kwargs)
        assert float(jnp.abs(got - want).max()) > 1e-2
    with pytest.raises(ValueError, match="gate_act"):
        tr.gated_delta_net(*ins, num_heads=H, chunk_size=CHUNK, eps=1e-5,
                           gate_act="tanh")


def _parent_block(query, key, value, gate, a, b, conv_weight, a_log,
                  dt_bias, norm_gamma, heads, chunk, eps):
    """``_gated_delta_block`` as the parent commit traced it for the
    scalar signature without kernels (``remat`` off): the text of PR
    52's closures."""
    f32 = jnp.float32
    bsz, t, _ = query.shape
    dk, dv = query.shape[2] // heads, value.shape[2] // heads

    def conv1d(x, w):
        return jax.nn.silu(tr.causal_taps(x, w)).astype(x.dtype)

    def unit(x):
        x = x.astype(f32).reshape(bsz, t, heads, -1)
        return x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    with jax.named_scope("conv1d"):
        edges = (0, heads * dk, 2 * heads * dk, 2 * heads * dk + heads * dv)
        q, k, v = (conv1d(x, conv_weight[:, lo:hi])
                   for x, lo, hi in zip((query, key, value), edges,
                                        edges[1:]))
    with jax.named_scope("delta_rule"):
        beta = 2.0 * jax.nn.sigmoid(b.astype(f32))
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            a.astype(f32) + dt_bias.astype(f32))
        o = tr.gated_delta_rule(
            (unit(q) * dk ** -0.5).astype(value.dtype),
            unit(k).astype(value.dtype), v.reshape(bsz, t, heads, dv), g,
            beta, chunk)
    with jax.named_scope("gate_norm"):
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        normed = o * jax.lax.rsqrt(var + eps) * norm_gamma.astype(f32)
        gated = normed.reshape(bsz, t, heads * dv) * jax.nn.silu(
            gate.astype(f32))
        return gated.astype(gate.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_scalar_path_is_bit_equal_to_the_parents(dtype):
    """Olmo-Hybrid's signature (``a`` a scalar a head, values twice as
    wide as keys, write strengths up to 2, a silu gate): values and every
    gradient EQUAL, bit for bit, to the parent's block; the op counts
    the call with the labels it always had."""
    ins = tuple(x.astype(dtype) for x in _op_inputs(8, channel=False,
                                                    dv=2 * D))
    cot = jnp.asarray(np.random.RandomState(1).randn(BATCH, T, H * 2 * D),
                      jnp.float32)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot),
            argnums=tuple(range(10))))(*ins)

    telemetry.reset()
    telemetry.enable()
    try:
        got = both(lambda *a: tr.gated_delta_net(
            *a, num_heads=H, chunk_size=CHUNK, eps=1e-6))
        count = telemetry.REGISTRY.get("linear_attn.lowerings")
        assert count.value(heads=H, key_dim=D, value_dim=2 * D, chunk=CHUNK,
                           conv=TAPS, impl="chunked") == 1
        assert telemetry.total("linear_attn.lowerings") == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    want = both(lambda *a: _parent_block(*a, heads=H, chunk=CHUNK, eps=1e-6))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


# the gate and norm's calls that stood before the token-major form: the
# operands' shapes (y, the gate's array, gamma), the arguments, and what
# the kernels are named, their grid and their blocks of y and of the gate
SILU_NORMS = {
    "nemotron3_nano_fit_share_8k": (
        ((1, 8192, 4096), (1, 8192, 10304), (4096,)),
        dict(form="gate_first", groups=8), "r256_g512_gate_first",
        (1, 2, 32), (256, 2048), (256, 2048)),
    "falcon_h1_fit_share_4k": (
        ((1, 4096, 2048), (1, 4096, 4624), (2048,)),
        dict(form="gate_first", groups=1, scale=0.7),
        "r256_g2048_gate_first", (1, 1, 16), (256, 2048), (256, 2048)),
    "olmo_hybrid_fit_stage_4k": (
        ((1, 30, 4096, 192), (1, 4096, 5760), (192,)),
        dict(form="norm_first"), "r1024_g192_norm_first", (1, 15, 4),
        (2, 1024, 192), (1024, 384)),
}


@pytest.mark.parametrize("cell", sorted(SILU_NORMS))
def test_the_silu_norms_keep_their_kernels(cell):
    """Nemotron's, Falcon-H1's and Olmo-Hybrid's gate and norm, called
    with the arguments they always had: the pair's names (no gate in
    them: ``silu`` is the default), tiles, grids and blocks are what they
    were before the family learnt the token-major form and the sigmoid."""
    shapes, kwargs, name, grid, core, gate = SILU_NORMS[cell]
    ins = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in zip(
        shapes, (jnp.float32, jnp.bfloat16, jnp.bfloat16))]
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: jnp.sum(pk.gated_rms_norm(
            *a, eps=1e-5, interpret=True, **kwargs).astype(jnp.float32)),
        argnums=(0, 1, 2)))(*ins)
    calls = {str(c.params["name"]): c for c in _pallas_calls(jaxpr.jaxpr)}
    assert sorted(calls) == ["gate_norm_%s_bf16_%s" % (which, name)
                             for which in ("bwd", "fwd")]
    for call in calls.values():
        mapping = call.params["grid_mapping"]
        assert tuple(mapping.grid) == grid
        blocks = [tuple(d.block_size for d in m.block_shape
                        if hasattr(d, "block_size"))
                  for m in mapping.block_mappings[:2]]
        assert blocks == [core, gate], (call.params["name"], blocks)


def test_the_kernel_pairs_take_the_channel_form_and_the_sigmoid_gate():
    """What is true since the channel rule has its own Pallas pair and the
    gate and norm's pair its token-major form and the sigmoid (the test
    was PR 53's ``..._refuse_...``, when neither was taken): at the cell's
    shape (32 heads of 128 / 128, chunks of 64, bf16) the rule's pair
    takes a decay a channel (``impl="kernel", decay="channel"``) and
    refuses what it has no tiles for; the gate and norm's pair reads ``o``
    token-major where that pair wrote it, under the sigmoid
    (``impl="kernel", gate="sigmoid"``), and is the closure's for heads
    that are no whole lane rows; the taps' pair takes the three
    4,096-column convolutions as it stands."""
    assert pk.gdn_takes(32, 128, 128, 64, jnp.bfloat16)
    assert pk.gdn_takes(32, 128, 128, 64, jnp.bfloat16, "channel")
    assert not pk.gdn_takes(32, 96, 192, 64, jnp.bfloat16, "channel")
    assert not pk.gdn_takes(32, 128, 128, 24, jnp.bfloat16, "channel")
    assert not pk.gdn_takes(H, D, D, CHUNK, jnp.float32, "channel")
    assert pk.taps_takes(4096, 8192, 4, jnp.bfloat16, "silu", 0, 4096)
    assert pk.gate_norm_takes("token_major", 32, 128, 8192, jnp.bfloat16, 0,
                              4096)
    assert not pk.gate_norm_takes("token_major", 32, 96, 8192, jnp.bfloat16,
                                  0, 3072)
    telemetry.reset()
    telemetry.enable()
    try:
        shape = lambda w: jax.ShapeDtypeStruct((1, 8192, w), jnp.bfloat16)
        jax.eval_shape(
            lambda *a: tr.gated_delta_net(
                *a, num_heads=32, chunk_size=64, eps=1e-5,
                allow_neg_eigval=False, gate_act="sigmoid"),
            shape(4096), shape(4096), shape(4096), shape(4096), shape(4096),
            shape(32), jax.ShapeDtypeStruct((4, 12288), jnp.bfloat16),
            jax.ShapeDtypeStruct((32,), jnp.bfloat16),
            jax.ShapeDtypeStruct((4096,), jnp.bfloat16),
            jax.ShapeDtypeStruct((128,), jnp.bfloat16))
        rule = telemetry.REGISTRY.get("linear_attn.lowerings")
        assert rule.value(heads=32, key_dim=128, value_dim=128, chunk=64,
                          conv=4, impl="kernel", decay="channel",
                          gate="sigmoid") == 1
        assert telemetry.total("linear_attn.lowerings") == 1
        norm = telemetry.REGISTRY.get("gate_norm.lowerings")
        assert norm.value(site="gated_delta_net", groups=32, width=128,
                          impl="kernel", gate="sigmoid") == 1
        assert telemetry.total("gate_norm.lowerings") == 1
        taps = telemetry.REGISTRY.get("causal_taps.lowerings")
        assert taps.value(site="gated_delta_net", channels=4096, taps=4,
                          impl="kernel") == 3
    finally:
        telemetry.disable()
        telemetry.reset()


# -- LatentAttention(rotary=False) -------------------------------------------

MLA = {k: CFG[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                           "v_head_dim", "kv_lora_rank", "rms_norm_eps")}


def _latent_inputs(seed, t=T, heads=HEADS, nope=NOPE, rope=ROPE, dv=DV,
                   latent=LATENT):
    rng = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (f32(rng.randn(BATCH, t, heads * (nope + rope))),
            f32(rng.randn(BATCH, t, latent + rope)),
            f32(1 + 0.1 * rng.randn(latent)),
            f32(0.2 * rng.randn(heads * (nope + dv), latent)))


def _nope(theta=10000.0, heads=HEADS, rope=ROPE, dv=DV, interleave=True):
    def op(*ins):
        return tr.latent_attention(
            *ins, num_heads=heads, rope_dim=rope, v_head_dim=dv,
            theta=theta, eps=1e-5, interleave=interleave, rotary=False)
    return op


def test_latent_attention_without_rotation_matches_the_materialised_form():
    ins = _latent_inputs(10)
    got, got_grads = _out_and_grads(_nope(), ins)
    want, want_grads = _out_and_grads(
        lambda *a: ref.latent_attention(*a, MLA), ins)
    _close(got, want, "attention")
    for i, (g, w) in enumerate(zip(got_grads, want_grads)):
        _close(g, w, "gradient %d" % i, ulps=64)
    # theta and the pairing are read by nothing: bit-equal outputs
    other = _nope(theta=1e6, interleave=False)(*ins)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(other))
    # and the rotated op is another function
    rotated = tr.latent_attention(*ins, num_heads=HEADS, rope_dim=ROPE,
                                  v_head_dim=DV, theta=10000.0, eps=1e-5)
    assert float(jnp.abs(rotated - got).max()) > 1e-2


def test_the_latent_pair_takes_the_unrotated_call(monkeypatch):
    """Kanana's signature (heads of 128 + 64 / 128) without the rotation:
    the same flash pair through the Pallas interpreter, the query's 64
    lanes padded to a lane row by ``jax.numpy``; counted with
    ``rotary=0``."""
    monkeypatch.setattr(pk.common, "INTERPRET", True)
    ins = _latent_inputs(11, t=128, heads=2, nope=128, rope=64, dv=128,
                         latent=64)
    big = dict(MLA, qk_nope_head_dim=128, qk_rope_head_dim=64,
               v_head_dim=128, kv_lora_rank=64)
    assert pk.latent_flash_takes(128, 128, 64, 128, jnp.float32)
    telemetry.reset()
    telemetry.enable()
    try:
        got, got_grads = _out_and_grads(
            _nope(heads=2, rope=64, dv=128), ins)
        sites = telemetry.REGISTRY.get("attention.latent_lowerings")
        assert sites.value(heads=2, latent=64, rope=64, nope=128, dv=128,
                           impl="kernel", rotary=0) >= 1
        assert telemetry.total("attention.latent_kernel_traces") >= 2
    finally:
        telemetry.disable()
        telemetry.reset()
    want, want_grads = _out_and_grads(
        lambda *a: ref.latent_attention(*a, big), ins)
    _close(got, want, "attention", ulps=64)
    for i, (g, w) in enumerate(zip(got_grads, want_grads)):
        _close(g, w, "gradient %d" % i, ulps=512)


# -- the whole model, uncut and as a share -----------------------------------

def _params(sym, seed, sigma=0.08, t=T):
    """Seeded weights under the symbol's argument names: Normal(sigma), a
    unit embedding as the model states it, gammas near 1, selection
    biases away from 0 (so that their part is tested), and the delta
    rule's own parameters in their stated ranges."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(BATCH, t), softmax_label=(BATCH, t))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("a_log"):
            value = np.log(rng.uniform(1, 16, shape))
        elif name.endswith("dt_bias"):
            value = rng.uniform(-4, 1, shape)
        elif name.endswith("conv_weight"):
            value = rng.uniform(-0.5, 0.5, shape)
        else:
            scale = {"embed_weight": 1.0, "bias": 0.05}.get(
                name if name == "embed_weight" else name.rsplit("_", 1)[-1],
                sigma)
            value = scale * rng.randn(*shape) + name.endswith("_gamma")
        out[name] = value.astype(np.float32)
    return out


def _batch(seed, t=T):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, CFG["vocab_size"], (BATCH, t + 1))
    return tokens[:, :-1].astype(np.float32), tokens[:, 1:].astype(np.float32)


def _module(sym, params, t=T):
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH, t))],
             label_shapes=[("softmax_label", (BATCH, t))])
    mod.init_params(arg_params={k: mx.nd.array(v) for k, v in params.items()},
                    aux_params={})
    return mod


@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_logits_loss_and_every_gradient_match_the_reference(cfg):
    sym = kimi_linear.from_config(cfg, seq_len=T, chunk_size=CHUNK)
    params = _params(sym, 1)
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, cfg, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)

    mod = _module(sym, params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert len(outs) == 1 + EXPERT_LAYERS
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    for layer in range(EXPERT_LAYERS):
        # over all 16 of the router's experts, share or not
        np.testing.assert_array_equal(
            outs[1 + layer], np.asarray(want["expert_counts"][layer]))
        assert outs[1 + layer].sum() == BATCH * T * 3
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention)
        _close(got[name].asnumpy() / BATCH, want_g, name, ulps=64)
        if "select_bias" in name:
            assert not np.asarray(want_g).any()  # it moves the choice only
        elif any(part in name for part in (
                "kda_f_", "kda_g_", "kda_b_", "a_log", "dt_bias",
                "latent_gamma", "shared")):
            assert np.abs(np.asarray(want_g)).max() > 1e-7, name

    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    _close(logits, want["logits"], "logits", ulps=16)


def test_fused_fit_trains_the_share_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep — on the share: the first steps follow the
    reference's own SGD with momentum, and the loss falls."""
    sym = kimi_linear.from_config(SHARE, seq_len=T, chunk_size=CHUNK)
    params = _params(sym, 3)
    tokens, labels = _batch(4)
    lr, momentum, steps = 0.05, 0.9, 6

    want = {k: jnp.asarray(v) for k, v in params.items()}
    moms = {k: jnp.zeros_like(v) for k, v in want.items()}
    losses = []
    for _ in range(2):
        loss, grads = ref.loss_and_grads(want, tokens, labels, SHARE)
        losses.append(float(loss))
        want, moms = ref.sgd_momentum_step(want, moms, grads, lr, momentum)

    it = mx.io.NDArrayIter(np.tile(tokens, (steps, 1)),
                           np.tile(labels, (steps, 1)), batch_size=BATCH)
    seen = []
    mod = mx.mod.Module(sym, context=mx.cpu(0), mesh=make_mesh(dp=1))
    mod.fit(it, num_epoch=1, eval_metric="loss", optimizer="sgd",
            optimizer_params={"learning_rate": lr, "momentum": momentum},
            kvstore="device",
            arg_params={k: mx.nd.array(v) for k, v in params.items()},
            aux_params={}, initializer=None,
            batch_end_callback=lambda p: (
                seen.append(p.eval_metric.get()[1]),
                p.eval_metric.reset()))
    assert mod._fused_trainer is not None
    _close(seen[:2], losses, "loss of the first two steps", ulps=16)
    assert seen[-1] < seen[0] - 0.05, seen
    got, _ = mod.get_params()
    for name in params:  # no gradient and no rule moves the bias
        if "select_bias" in name:
            np.testing.assert_array_equal(got[name].asnumpy(), params[name])


def test_the_model_states_its_own_initialisation_and_counts_its_call_sites():
    telemetry.reset()
    telemetry.enable()
    try:
        sym = kimi_linear.from_config(SHARE, seq_len=T)
        mod = mx.mod.Module(sym, context=mx.cpu(0))
        mod.bind(data_shapes=[("data", (BATCH, T))],
                 label_shapes=[("softmax_label", (BATCH, T))],
                 for_training=False)
        mx.random.seed(5)
        np.random.seed(5)
        mod.init_params(initializer=mx.init.Normal(sigma=0.02))
        tokens, labels = _batch(6)
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                    label=[mx.nd.array(labels)]),
                    is_train=False)
        # one per layer's call site, nothing per step; from_config's
        # chunk is the program's 64; the impl is what gdn_takes says of
        # the tiny heads (8 columns are no lane row: the chunk form)
        impl = ("kernel" if pk.gdn_takes(H, D, D, 64, jnp.float32, "channel")
                else "chunked")
        assert impl == "chunked"
        rule = telemetry.REGISTRY.get("linear_attn.lowerings")
        assert rule.value(heads=H, key_dim=D, value_dim=D, chunk=64,
                          conv=TAPS, impl=impl, decay="channel",
                          gate="sigmoid") == 4
        assert telemetry.total("linear_attn.lowerings") == 4
        latent = telemetry.REGISTRY.get("attention.latent_lowerings")
        assert latent.value(heads=HEADS, latent=LATENT, rope=ROPE,
                            nope=NOPE, dv=DV, impl="composed",
                            rotary=0) == 1
        share = telemetry.REGISTRY.get("moe.share_lowerings")
        assert share.value(held=4, of=16, bound=BATCH * T * 3,
                           sum="segment_product", scale=2.446) == 4
    finally:
        telemetry.disable()
        telemetry.reset()
    got, _ = mod.get_params()
    got = {k: v.asnumpy() for k, v in got.items()}
    assert abs(got["embed_weight"].std() - 1.0) < 0.05
    assert abs(got["layer1_kda_q_proj_weight"].std() - 0.02) < 0.004
    for i in (0, 1, 2, 4):
        p = "layer%d_kda_" % i
        assert np.abs(got[p + "conv_weight"]).max() <= 0.5
        assert np.abs(got[p + "conv_weight"]).max() > 0.3
        rate = np.exp(got[p + "a_log"])
        assert got[p + "a_log"].shape == (H,)
        assert (rate >= 1).all() and (rate <= 16).all()
        step = np.log1p(np.exp(got[p + "dt_bias"]))      # softplus
        assert got[p + "dt_bias"].shape == (H * D,)      # a channel
        assert (step >= 1e-4 * 0.99).all() and (step <= 0.1 * 1.01).all()
        np.testing.assert_array_equal(got[p + "norm_gamma"], 1.0)
    for name, value in got.items():
        if name.endswith("select_bias"):
            np.testing.assert_array_equal(value, 0.0)
        if name.endswith("_gamma"):
            np.testing.assert_array_equal(value, 1.0)
    # no bias on any projection, the low-rank pairs among them
    assert not [n for n in got if n.endswith("_proj_bias")]
    assert got["layer0_kda_f_a_proj_weight"].shape == (D, 48)
    assert got["layer0_kda_f_b_proj_weight"].shape == (H * D, D)
    assert got["layer0_kda_g_a_proj_weight"].shape == (D, 48)
    assert got["layer0_kda_b_proj_weight"].shape == (H, 48)


# -- the share adds up -------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """THE SHARE-SUM TEST. One expert layer of the model at a 32-wide
    router: the residual, the shared expert and the routed ones. Four
    shares of eight experts each route over all 32 and compute their own
    experts' part; the shared expert (and the residual) are what every
    chip computes alike and count once. The sum is the uncut reference's
    layer."""
    rng = np.random.RandomState(5)
    d, hidden, experts, top_k, n = 48, 32, 32, 3, BATCH * T
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(rng.randn(n, d))
    w = {"gate_w": f32(rng.randn(d, experts)),
         "w_gate_up": f32(0.2 * rng.randn(experts, d, 2 * hidden)),
         "w_down": f32(0.2 * rng.randn(experts, hidden, d)),
         "select_bias": f32(0.05 * rng.randn(experts))}
    shared = [f32(0.1 * rng.randn(*s))
              for s in ((hidden, d), (hidden, d), (d, hidden))]
    whole, counts, _ = ref.moe(
        x, w["gate_w"], w["w_gate_up"], w["w_down"], w["select_bias"],
        top_k, True, "sigmoid", 0, 2.446)
    want = x + ref.swiglu(x, *shared) + whole

    total = x + ref.swiglu(x, *shared)          # counted once
    for offset in range(0, experts, 8):
        held = dict(w, w_gate_up=w["w_gate_up"][offset:offset + 8],
                    w_down=w["w_down"][offset:offset + 8])
        part, part_counts = topk_moe(
            held, x, top_k, norm_topk_prob=True, scoring="sigmoid",
            expert_offset=offset, share_rows_bound=n * top_k,
            routed_scale=2.446)
        np.testing.assert_array_equal(np.asarray(part_counts),
                                      np.asarray(counts))
        mine, _, _ = ref.moe(
            x, w["gate_w"], held["w_gate_up"], held["w_down"],
            w["select_bias"], top_k, True, "sigmoid", offset, 2.446)
        _close(part, mine, "share at %d" % offset)
        total = total + part
    _close(total, want, "sum of the four shares", ulps=32)
    # adding the shared expert in every share would count it 4 times
    assert float(jnp.abs(ref.swiglu(x, *shared)).max()) > 1e-2


# -- from_config on the published keys ---------------------------------------

def _published():
    with open(FILE) as f:
        held = json.load(f)
    return dict(held, **{k: held["published"][k]
                         for k in ("num_hidden_layers",
                                   "linear_attn_config", "num_experts",
                                   "vocab_size")})


def test_from_config_reads_the_published_keys():
    cfg = _published()
    kinds = kimi_linear.layer_kinds(cfg)
    assert len(kinds) == 27 and kinds.count(kimi_linear.FULL) == 7
    # the lists are 1-based: published layers 4, 8, ..., 24 and 27
    assert [i + 1 for i, k in enumerate(kinds)
            if k == kimi_linear.FULL] == [4, 8, 12, 16, 20, 24, 27]
    sym = kimi_linear.from_config(dict(cfg, share={}), seq_len=64)
    names = sym.list_arguments()
    assert "layer0_kda_q_proj_weight" in names      # published layer 1
    assert "layer0_gate_proj_weight" in names       # its dense SwiGLU
    assert "layer1_moe_gate_weight" in names
    assert "layer3_attn_up_weight" in names and \
        "layer3_kda_q_proj_weight" not in names     # published layer 4
    assert "layer26_attn_up_weight" in names        # published layer 27
    shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    shape = dict(zip(names, shapes))
    assert shape["layer1_moe_gate_weight"] == (2304, 256)
    assert shape["layer1_moe_gate_up_weight"] == (256, 2304, 2048)
    assert shape["layer0_kda_dt_bias"] == (4096,)
    assert shape["layer0_kda_a_log"] == (32,)
    assert shape["layer0_kda_conv_weight"] == (4, 12288)
    assert shape["layer3_q_proj_weight"] == (32 * 192, 2304)
    assert shape["layer3_kv_a_proj_weight"] == (512 + 64, 2304)
    assert shape["lm_head_weight"] == (163840, 2304)
    # the defaults of get_symbol are the published model
    assert kimi_linear.get_symbol(seq_len=64).list_arguments() == names


@pytest.mark.parametrize("change,match", [
    (dict(linear_attn_config=dict(CFG["linear_attn_config"],
                                  kda_layers=[1, 2, 3, 4, 5])), "partition"),
    (dict(linear_attn_config=dict(CFG["linear_attn_config"],
                                  kda_layers=[1, 2, 5])), "partition"),
    (dict(linear_attn_config=dict(CFG["linear_attn_config"],
                                  full_attn_layers=[0])), "partition"),
    (dict(linear_attn_config=dict(CFG["linear_attn_config"],
                                  full_attn_layers=[4, 6])), "partition"),
    (dict(mla_use_nope=False), "mla_use_nope"),
    (dict(q_lora_rank=64), "q_lora_rank"),
    (dict(num_expert_group=2), "num_expert_group"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(moe_router_activation_func="tanh"), "moe_router_activation_func"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
], ids=["overlap", "gap", "zero_based", "out_of_range", "rotated_mla",
        "query_latent", "grouped_routing", "layer_freq", "router_act",
        "tied", "mtp", "scaled_rope"])
def test_from_config_refuses_what_it_cannot_honour(change, match):
    with pytest.raises(ValueError, match=match):
        kimi_linear.from_config(dict(CFG, **change), seq_len=T)


def test_the_keys_listed_as_unread_are_read_by_nothing():
    import re

    def graph(cfg):  # auto-named nodes count up from one symbol to the next
        return re.sub(r'"([a-z_]*[a-z_])\d+"', r'"\1"',
                      kimi_linear.from_config(cfg, seq_len=T).tojson())

    base = graph(CFG)
    moved = dict(CFG, head_dim=64, num_key_value_heads=1, rope_theta=5e5,
                 use_grouped_topk=False, model_max_length=1 << 20)
    assert set(kimi_linear.ASSUMED_UNREAD) == {
        "head_dim", "num_key_value_heads", "rope_theta", "use_grouped_topk",
        "model_max_length"}
    assert graph(moved) == base
    assert graph(dict(CFG, rms_norm_eps=1e-6)) != base


# -- one test an ``assumed`` entry of the configuration's file ---------------

def _kda_node(attr):
    sym = kimi_linear.get_symbol(seq_len=64)
    nodes = json.loads(sym.tojson())["nodes"]
    node = [n for n in nodes if n["name"] == "layer0_kda"][0]
    return node["attr"][attr]


ASSUMED = {
    "chunk": lambda text: (
        "chunks of 64" in text and "sub-blocks of 16" in text
        and tr.KDA_SUB_BLOCK == 16 and str(_kda_node("chunk_size")) == "64"),
    "low_rank": lambda text: (
        "2304 -> 128 -> 4096" in text and "NO bias" in text
        and kimi_linear.get_symbol.__kwdefaults__ is None
        and "layer0_kda_f_a_proj_bias" not in kimi_linear.get_symbol(
            seq_len=64).list_arguments()),
    "beta": lambda text: (
        "NO factor 2" in text
        and str(_kda_node("allow_neg_eigval")) in ("False", "0")),
    "gate": lambda text: (
        "BEFORE the gate" in text and "sigmoid" in text
        and str(_kda_node("gate_act")) == "sigmoid"),
    "unit_norm": lambda text: (
        "1e-6" in text and "1e-6" in open(delta.__file__).read().split(
            "def unit(x)")[1][:300]),
    "router": lambda text: "sum + 1e-20" in text and "2.446" in text,
    "block": lambda text: "h += mixer(RMSNorm(h)); h += ffn(RMSNorm(h))"
    in text,
    "dtype": lambda text: "float32" in text and text.startswith("bfloat16"),
    "optimizer": lambda text: "SGD momentum 0.9" in text,
    "objective": lambda text: "no auxiliary loss" in text,
    "attention": lambda text: "1/sqrt(192)" in text and "rotary=False"
    in text,
    "weights": lambda text: "A_log = log(U(1, 16))" in text
    and "all 4096 channels" in text,
    "unread": lambda text: all(k in text
                               for k in kimi_linear.ASSUMED_UNREAD),
}


@pytest.mark.parametrize("entry", sorted(ASSUMED))
def test_an_assumed_entry_says_what_the_program_does(entry):
    """Each assumption of ``bench/configs/kimi_linear_48b_a3b.json`` is
    one entry, and it fails here if the file or the program moves."""
    with open(FILE) as f:
        assumed = json.load(f)["assumed"]
    assert ASSUMED[entry](assumed[entry]), assumed[entry]
