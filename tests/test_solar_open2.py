"""Solar-Open2-250B on the normal path against its plain reference.

``models/solar_open2.py`` (an ``mx.sym`` graph of Kimi Delta Attention
layers whose write strengths are ``2 sigmoid`` and gated grouped
attention without a rotary embedding, three to one, over one shared and
sigmoid-routed experts in every layer) through ``Module.forward/backward``
and ``Module.fit``'s fused step against ``models/solar_open2_reference.py``
(plain float32 ``jax.numpy``: KDA one token after another, attention by an
explicit mask, a loop over the experts held) on seeded weights at a tiny
size: hidden 48, 4 layers (GQA, KDA, KDA, KDA), 4 KDA heads of 8, 4 query
heads on 2 key/value heads of 16, 20 experts top-3 of width 32 (a router
that is no power of two wide), 1 shared, T 40 (no multiple of the chunk
of 16). Then the share of heads and experts summed back to the uncut
layer, the ``kda_`` kernel pair through the Pallas interpreter at write
strengths near 2 on keys nearly parallel inside a sub-block, a router 320
wide with 10 held, and ``from_config``'s refusals.

Tolerances as in ``tests/test_kimi_linear.py``: both sides are float32
and only the order of summation differs, so rtol 1e-5 with an atol of a
few float32 ulps of the tensor's own scale (``_close``); ``ulps`` is
raised for gradients. The bf16 case measures its tolerance, see there.
"""
import hashlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import kimi_linear, solar_open2
from mxnet_tpu.models import solar_open2_reference as ref
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import attention, delta
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import topk_moe

T, BATCH, CHUNK = 40, 2, 16
H, D, TAPS = 4, 8, 4
HEADS, KV, HD = 4, 2, 16
EXPERTS, TOP_K = 20, 3
LAYERS = 4
CFG = dict(
    model_type="solar_open2", hidden_size=48, num_hidden_layers=LAYERS,
    first_k_dense_replace=0, gqa_interval=3, gqa_layers=[0],
    linear_attn_config=dict(num_heads=H, head_dim=D, num_kv_heads=None,
                            short_conv_kernel_size=TAPS),
    num_attention_heads=HEADS, num_key_value_heads=KV, head_dim=HD,
    use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
    kda_allow_neg_eigval=True, partial_rotary_factor=1, rope_theta=10000,
    intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=EXPERTS, n_shared_experts=1, num_experts_per_tok=TOP_K,
    norm_topk_prob=True, routed_scaling_factor=1, rms_norm_eps=1e-5,
    vocab_size=512, tie_word_embeddings=False, max_position_embeddings=T)
# one chip's share of the same model: 5 of the 20 experts from the 10th
# on, half of each mixer's heads, a buffer that holds every row
SHARE = dict(CFG, n_routed_experts=5, num_attention_heads=2,
             num_key_value_heads=1,
             linear_attn_config=dict(CFG["linear_attn_config"], num_heads=2),
             share=dict(experts_of=EXPERTS, expert_offset=10,
                        share_rows_bound=BATCH * T * TOP_K))
HERE = os.path.dirname(os.path.abspath(__file__))
FILE = os.path.join(os.path.dirname(HERE), "bench", "configs",
                    "solar_open2_250b.json")


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, sigma=0.08, t=T):
    """Seeded weights under the symbol's argument names: Normal(sigma), a
    unit embedding as the model states it, gammas near 1, selection
    biases away from 0 (so that their part is tested), the write
    strength's projection wide enough that ``2 sigmoid`` leaves (0.5,
    1.5), and the delta rule's own parameters in their stated ranges."""
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
                0.5 if "kda_b_proj" in name else sigma)
            value = scale * rng.randn(*shape) + name.endswith("_gamma")
        out[name] = value.astype(np.float32)
    return out


def _batch(seed, t=T):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, CFG["vocab_size"], (BATCH, t + 1))
    return tokens[:, :-1].astype(np.float32), tokens[:, 1:].astype(np.float32)


def _module(sym, params, t=T, for_training=True):
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH, t))],
             label_shapes=[("softmax_label", (BATCH, t))],
             for_training=for_training)
    mod.init_params(arg_params={k: mx.nd.array(v) for k, v in params.items()},
                    aux_params={})
    return mod


# -- the whole model, uncut and as a share -----------------------------------

@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_logits_loss_and_every_gradient_match_the_reference(cfg, remat):
    """``remat``: the training pass (every ``GatedDeltaNet`` scope
    computed again in the backward pass) with the gradient of every
    parameter; without it the inference pass and its logits."""
    sym = solar_open2.from_config(cfg, seq_len=T, chunk_size=CHUNK)
    params = _params(sym, 1)
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, cfg, labels=labels)
    batch = mx.io.DataBatch(data=[mx.nd.array(tokens)],
                            label=[mx.nd.array(labels)])
    if not remat:
        internals = sym.get_internals()
        mod = _module(mx.sym.Group([internals["loss_output"],
                                    internals["lm_head_f32_output"]]),
                      params, for_training=False)
        mod.forward(batch, is_train=False)
        loss, logits = (o.asnumpy() for o in mod.get_outputs())
        _close(loss, want["per_sequence"], "per-sequence loss")
        _close(logits.reshape(want["logits"].shape), want["logits"],
               "logits", ulps=16)
        return
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)
    mod = _module(sym, params)
    mod.forward(batch, is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert len(outs) == 1 + LAYERS              # experts in EVERY layer
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    for layer in range(LAYERS):
        # over all 20 of the router's experts, share or not
        np.testing.assert_array_equal(
            outs[1 + layer], np.asarray(want["expert_counts"][layer]))
        assert outs[1 + layer].sum() == BATCH * T * TOP_K
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention)
        _close(got[name].asnumpy() / BATCH, want_g, name, ulps=64)
        if "select_bias" in name:
            assert not np.asarray(want_g).any()  # it moves the choice only
        elif any(part in name for part in (
                "kda_f_", "kda_g_", "kda_b_", "a_log", "dt_bias",
                "attn_gate", "k_proj", "shared")):
            assert np.abs(np.asarray(want_g)).max() > 1e-7, name


def _bf16_logit_error(seed, strength=True):
    """90th percentile over the tokens clear of a routing tie of the
    largest |logit difference| in standard deviations of the reference's
    logits: the bf16 share against the float32 reference on the rounded
    weights; ``strength`` false: the reference without the factor 2."""
    sym = solar_open2.from_config(SHARE, seq_len=T, dtype="bfloat16",
                                  chunk_size=CHUNK)
    params = _params(sym, seed)
    rounded = {k: np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32))
               for k, v in params.items()}
    tokens, _ = _batch(seed + 100)
    out = ref.forward(rounded, tokens,
                      dict(SHARE, kda_allow_neg_eigval=strength))
    want = np.asarray(out["logits"]).reshape(-1, CFG["vocab_size"])
    clear = np.asarray(out["router_gap"]).min(axis=0) > 1e-3
    mod = mx.mod.Module(sym.get_internals()["lm_head_f32_output"],
                        context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={
        k: mx.nd.array(v).astype("bfloat16") for k, v in rounded.items()},
        aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    got = mod.get_outputs()[0].asnumpy().reshape(want.shape)
    per_token = (np.abs(got - want).max(axis=1) / want.std())[clear]
    return float(np.percentile(per_token, 90))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_bf16_share_is_close_and_the_missing_factor_2_is_not(seed):
    """Measured here over seeds 0..5 (90th percentile): the bf16 share
    0.024-0.031 sd of the logits; held to a reference WITHOUT the factor
    2 on the write strengths 0.23-0.34. The limit 0.08 lies between the
    two, a factor of 2.6 from the one and 2.9 from the other."""
    ours = _bf16_logit_error(seed)
    other = _bf16_logit_error(seed, strength=False)
    assert ours < 0.08 < other, (seed, ours, other)


def test_fused_fit_trains_the_share_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep — on the share: the first steps follow the
    reference's own SGD with momentum, and the loss falls."""
    sym = solar_open2.from_config(SHARE, seq_len=T, chunk_size=CHUNK)
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
        sym = solar_open2.from_config(SHARE, seq_len=T)
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
        # one per layer's call site, nothing per step; the share's two
        # heads; the write strength's scale is on the label because it is
        # not the channel form's 1 (Kimi's call carries none)
        rule = telemetry.REGISTRY.get("linear_attn.lowerings")
        assert rule.value(heads=2, key_dim=D, value_dim=D, chunk=64,
                          conv=TAPS, impl="chunked", decay="channel",
                          gate="sigmoid", beta_scale=2) == 3
        assert telemetry.total("linear_attn.lowerings") == 3
        gated = telemetry.REGISTRY.get("attention.gated_lowerings")
        assert gated.value(heads=2, dv=HD) == 1
        share = telemetry.REGISTRY.get("moe.share_lowerings")
        assert share.value(held=5, of=EXPERTS, bound=BATCH * T * TOP_K,
                           sum="segment_product",
                           renorm_eps=1e-20) == LAYERS
    finally:
        telemetry.disable()
        telemetry.reset()
    got, _ = mod.get_params()
    got = {k: v.asnumpy() for k, v in got.items()}
    # the stream starts at the model's STREAM_RMS, not at 1
    assert solar_open2.STREAM_RMS == 8.0
    assert abs(got["embed_weight"].std() - 8.0) < 0.4
    assert abs(got["layer1_kda_q_proj_weight"].std() - 0.02) < 0.004
    for i in (1, 2, 3):
        p = "layer%d_kda_" % i
        assert 0.3 < np.abs(got[p + "conv_weight"]).max() <= 0.5
        rate = np.exp(got[p + "a_log"])
        assert got[p + "a_log"].shape == (2,)            # the heads held
        assert (rate >= 1).all() and (rate <= 16).all()
        step = np.log1p(np.exp(got[p + "dt_bias"]))      # softplus
        assert got[p + "dt_bias"].shape == (2 * D,)      # a channel
        assert (step >= 1e-4 * 0.99).all() and (step <= 0.1 * 1.01).all()
    for name, value in got.items():
        if name.endswith("select_bias"):
            np.testing.assert_array_equal(value, 0.0)
        if name.endswith("_gamma"):
            np.testing.assert_array_equal(value, 1.0)
    assert not [n for n in got if n.endswith("_proj_bias")]
    # a share holds its heads' columns; the low-rank pairs' first halves,
    # the router and the shared expert are whole
    assert got["layer1_kda_q_proj_weight"].shape == (2 * D, 48)
    assert got["layer1_kda_f_a_proj_weight"].shape == (D, 48)
    assert got["layer1_kda_f_b_proj_weight"].shape == (2 * D, D)
    assert got["layer1_kda_g_b_proj_weight"].shape == (2 * D, D)
    assert got["layer1_kda_b_proj_weight"].shape == (2, 48)
    assert got["layer1_kda_conv_weight"].shape == (TAPS, 3 * 2 * D)
    assert got["layer1_kda_o_proj_weight"].shape == (48, 2 * D)
    assert got["layer0_q_proj_weight"].shape == (2 * HD, 48)
    assert got["layer0_attn_gate_proj_weight"].shape == (2 * HD, 48)
    assert got["layer0_k_proj_weight"].shape == (1 * HD, 48)
    assert got["layer0_o_proj_weight"].shape == (48, 2 * HD)
    assert got["layer0_moe_gate_weight"].shape == (48, EXPERTS)
    assert got["layer0_moe_down_weight"].shape == (5, 32, 48)
    assert got["layer0_shared_gate_proj_weight"].shape == (32, 48)
    # experts in layer 0 and no dense layer anywhere
    assert "layer0_gate_proj_weight" not in got


# -- the share adds up -------------------------------------------------------

ADD_UP = dict(CFG, num_hidden_layers=1, num_attention_heads=8,
              num_key_value_heads=4, n_routed_experts=40,
              num_experts_per_tok=4,
              linear_attn_config=dict(CFG["linear_attn_config"], num_heads=8))


@pytest.mark.parametrize("kind", ["kda", "gqa"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """THE SHARE-SUM TEST, one layer of either kind at 8 KDA heads, 8
    query heads on 4 and 40 experts top-4. Four head shares run the
    PROGRAM's ops on their heads: their columns of every projection (the
    second halves of the low-rank pairs among them), their ``a_log``,
    their channels of ``dt_bias`` and their taps; the first halves of the
    low-rank pairs are whole in every share, and the ``o_proj`` outputs
    add. Then four expert shares of ten route over all 40 and compute
    their own experts' part; the shared expert and the router are what
    every chip computes alike and count once. The sum is the uncut
    reference's layer."""
    cfg = dict(ADD_UP, gqa_layers=[0] if kind == "gqa" else [],
               gqa_interval=None)
    sym = solar_open2.from_config(cfg, seq_len=T, chunk_size=CHUNK)
    p = {k: jnp.asarray(v) for k, v in _params(sym, 11).items()}
    rng = np.random.RandomState(12)
    h = jnp.asarray(rng.randn(BATCH, T, 48), jnp.float32)
    n, eps = "layer0_", cfg["rms_norm_eps"]

    # the uncut reference's layer
    x = ref.rms_norm(h, p[n + "attn_norm_gamma"], eps)
    mixer = (ref.gqa_layer if kind == "gqa" else ref.kda_layer)(
        x, p.__getitem__, n, cfg)
    mid = h + mixer
    x2 = ref.rms_norm(mid, p[n + "ffn_norm_gamma"], eps)
    routed, counts, _ = ref.moe(
        x2.reshape(BATCH * T, -1), p[n + "moe_gate_weight"],
        p[n + "moe_gate_up_weight"], p[n + "moe_down_weight"],
        p[n + "moe_select_bias"], 4, True)
    shared = ref.swiglu(x2, p[n + "shared_gate_proj_weight"],
                        p[n + "shared_up_proj_weight"],
                        p[n + "shared_down_proj_weight"])
    want = mid + shared + routed.reshape(BATCH, T, -1)

    def rows(name, heads, lo, per):  # a [heads x width, in] weight's heads
        w = p[n + name + "_proj_weight"]
        return w.reshape(heads, w.shape[0] // heads, -1)[
            lo:lo + per].reshape(-1, w.shape[1])

    def columns(name, heads, lo, per):  # o_proj's [out, heads x width]
        w = p[n + name + "_proj_weight"]
        return w.reshape(w.shape[0], heads, -1)[:, lo:lo + per].reshape(
            w.shape[0], -1)

    total = 0.0
    for j in range(0, 8, 2):                # four head shares of two
        if kind == "gqa":                   # 2 query heads on 1 of 4
            part = attention._attention(
                dict(num_heads=2, num_kv_heads=1, causal=True,
                     with_gate=True),
                [x @ rows("q", 8, j, 2).T, x @ rows("k", 4, j // 2, 1).T,
                 x @ rows("v", 4, j // 2, 1).T,
                 x @ rows("attn_gate", 8, j, 2).T], False)[0]
            total = total + part @ columns("o", 8, j, 2).T
            continue
        k = n + "kda_"
        taps = p[k + "conv_weight"].reshape(TAPS, 3, 8, D)[
            :, :, j:j + 2].reshape(TAPS, 3 * 2 * D)
        part = tr.gated_delta_net(
            x @ rows("kda_q", 8, j, 2).T, x @ rows("kda_k", 8, j, 2).T,
            x @ rows("kda_v", 8, j, 2).T,
            (x @ p[k + "g_a_proj_weight"].T) @ rows("kda_g_b", 8, j, 2).T,
            (x @ p[k + "f_a_proj_weight"].T) @ rows("kda_f_b", 8, j, 2).T,
            x @ p[k + "b_proj_weight"][j:j + 2].T, taps,
            p[k + "a_log"][j:j + 2], p[k + "dt_bias"][j * D:(j + 2) * D],
            p[k + "norm_gamma"], num_heads=2, chunk_size=CHUNK, eps=eps,
            allow_neg_eigval=True, gate_act="sigmoid")
        total = total + part @ columns("kda_o", 8, j, 2).T
    _close(total, mixer, "sum of the head shares' o_proj outputs", ulps=32)

    ffn = shared                            # counted once
    for offset in range(0, 40, 10):
        held = {"gate_w": p[n + "moe_gate_weight"],
                "select_bias": p[n + "moe_select_bias"],
                "w_gate_up": p[n + "moe_gate_up_weight"][offset:offset + 10],
                "w_down": p[n + "moe_down_weight"][offset:offset + 10]}
        part, part_counts = topk_moe(
            held, x2.reshape(BATCH * T, -1), 4, norm_topk_prob=True,
            scoring="sigmoid", expert_offset=offset,
            share_rows_bound=BATCH * T * 4, renorm_eps=1e-20)
        np.testing.assert_array_equal(np.asarray(part_counts),
                                      np.asarray(counts))
        ffn = ffn + part.reshape(BATCH, T, -1)
    _close(mid + ffn, want, "sum of the shares", ulps=32)
    # adding the shared expert in every share would count it 4 times
    assert float(jnp.abs(shared).max()) > 1e-2


# -- the 2 matters -----------------------------------------------------------

def _node(sym, name):
    return [n for n in json.loads(sym.tojson())["nodes"]
            if n["name"] == name][0]


def test_the_factor_2_matters_and_from_config_hands_it_to_the_op():
    """The reference with ``kda_allow_neg_eigval`` false differs from true
    by far more than any tolerance used here, and ``from_config`` hands
    the op the key (read off the node)."""
    sym = solar_open2.from_config(CFG, seq_len=T, chunk_size=CHUNK)
    params = _params(sym, 21)
    tokens, labels = _batch(22)
    with_2 = ref.forward(params, tokens, CFG, labels=labels)
    without = ref.forward(params, tokens,
                          dict(CFG, kda_allow_neg_eigval=False),
                          labels=labels)
    scale = float(np.asarray(with_2["logits"]).std())
    moved = float(np.abs(np.asarray(with_2["logits"])
                         - np.asarray(without["logits"])).max()) / scale
    assert moved > 0.05, moved              # a float32 ulp is 1e-7
    for i in (1, 2, 3):
        assert str(_node(sym, "layer%d_kda" % i)["attr"][
            "allow_neg_eigval"]) in ("True", "1")
    off = solar_open2.from_config(dict(CFG, kda_allow_neg_eigval=False),
                                  seq_len=T)
    assert str(_node(off, "layer1_kda")["attr"]["allow_neg_eigval"]) in (
        "False", "0")


# -- the kda_ pair at write strengths near 2 ---------------------------------

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


def _worst_case(seed, t, heads, d, sub=tr.KDA_SUB_BLOCK):
    """Unit keys that are ONE direction a sub-block of 16 tokens but for a
    part in a hundred, write strengths within 0.05 of 2 and decays within
    1e-2 of 1: ``L = beta D (k.k)`` of the chunk's system ``(I + L)`` has
    entries up to 2 throughout a sub-block's triangle."""
    rng = np.random.RandomState(seed)
    q, noise = rng.randn(2, 1, t, heads, d)
    few = rng.randn(1, -(-t // sub), heads, d)
    k = np.repeat(few, sub, axis=1)[:, :t] + 1e-2 * noise
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(1, t, heads, d)
    g = -1e-2 * rng.rand(1, t, heads, d)
    beta = 2 - 0.05 * rng.rand(1, t, heads)
    cot = rng.randn(1, t, heads, d)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return tuple(f32(a) for a in (q, k, v, g, beta)), f32(cot)


@pytest.mark.parametrize("t,chunk", [(128, 64), (70, 32)],
                         ids=["chunks_of_64", "ragged_chunks_of_32"])
def test_the_kda_pair_holds_the_recurrence_at_write_strengths_near_2(
        t, chunk):
    """The ``kda_fwd_`` / ``kda_bwd_`` pair through the Pallas interpreter
    (what the TPU's branch computes) on the worst case for ``(I + L)^-1``
    against the ``jax.numpy`` chunk form and the recurrence: the output
    and the gradient of q, k, v, g and beta. As near the recurrence as the
    chunk form's ``solve_triangular`` (within a factor 2 of its distance,
    or a few ulps)."""
    heads, d = 2, 128
    assert pk.gdn_takes(heads, d, d, chunk, jnp.float32, "channel")
    ins, cot = _worst_case(31, t, heads, d)
    assert float(ins[4].min()) > 1.94

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (lambda o: (jnp.sum(o * cot), o))(fn(*a)),
            tuple(range(5)), has_aux=True))(*ins)

    (_, o), ours = both(lambda *a: pk.gdn.channel_delta_rule(
        *a, chunk, interpret=True))
    (_, o_c), theirs = both(lambda *a: tr.channel_delta_rule(*a, chunk))
    (_, o_w), want = both(recurrence)
    assert np.isfinite(np.asarray(o)).all()
    scale = float(jnp.abs(o_w).max())
    err, err_c = (float(jnp.abs(x - o_w).max()) / scale for x in (o, o_c))
    assert err <= max(2 * err_c, 1e-4), (err, err_c)
    for name, g, c, w in zip("q k v g beta".split(), ours, theirs, want):
        top = float(jnp.abs(w).max())
        assert top > 1e-6, name
        e, e_c = (float(jnp.abs(x - w).max()) / top for x in (g, c))
        assert e <= max(2 * e_c, 1e-3), (name, e, e_c)


def _op_inputs(seed, t, heads, d):
    """The ten inputs of the op at a shape the pair takes, made so that
    the op's own arithmetic lands on the worst case: the key's
    pre-activation is one direction a sub-block of 16 tokens (positive,
    so that ``silu`` keeps it) under taps that pass the current token
    almost alone, and the write strength's pre-activation is 3 to 6
    (``2 sigmoid`` in 1.905-1.995)."""
    rng = np.random.RandomState(seed)
    width = heads * d
    few = 1 + rng.rand(1, -(-t // 16), width)
    key = np.repeat(few, 16, axis=1)[:, :t] + 1e-2 * rng.randn(1, t, width)
    taps = 0.02 * rng.randn(TAPS, 3 * width)
    taps[-1] += 1.0
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.01), width))
    draw = lambda *s: rng.randn(*s)
    ins = (draw(1, t, width), key, draw(1, t, width), draw(1, t, width),
           draw(1, t, width), rng.uniform(3, 6, (1, t, heads)), taps,
           np.log(rng.uniform(1, 4, heads)),
           step + np.log(-np.expm1(-step)), 1 + 0.1 * draw(d))
    return (tuple(jnp.asarray(a, jnp.float32) for a in ins),
            jnp.asarray(draw(1, t, width), jnp.float32))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_channel_op_at_beta_scale_2_is_the_chunk_form_and_the_reference(
        remat):
    """``GatedDeltaNet`` with ``allow_neg_eigval`` at a shape the pair
    takes (``_channel_delta_block`` at ``beta_scale`` 2, the pair through
    the interpreter) against the op in the ``jax.numpy`` chunk form (the
    gradient of all ten inputs) and against the reference's
    token-by-token layer."""
    heads, d, t, chunk = 2, 128, 48, 16
    ins, cot = _op_inputs(41, t, heads, d)
    kw = dict(heads=heads, chunk=chunk, eps=1e-5, beta_scale=2.0,
              remat=remat, taps_kernel=(False,) * 3, gate_act="sigmoid",
              norm_kernel=False)

    def both(block, **more):
        return jax.jit(jax.value_and_grad(
            lambda *a: (lambda o: (jnp.sum(o * cot), o))(
                block(*a, **kw, **more)),
            tuple(range(len(ins))), has_aux=True))(*ins)

    delta._channel_delta_block.clear_cache()
    (_, o), got = both(delta._channel_delta_block, interpret=True)
    (_, o_c), want = both(delta._gated_delta_block, kernel=False,
                          interpret=False)
    delta._channel_delta_block.clear_cache()
    # the inputs are the worst case: strengths over 1.9, a sub-block's
    # keys within a part in a hundred of one direction
    k = jax.nn.silu(ref.causal_conv(ins[1], ins[6][:, heads * d:2 * heads * d]
                                    )).reshape(1, t, heads, d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    assert float(jnp.einsum("bthd,bthd->bth", k[:, 1:16], k[:, :15]).min()) \
        > 0.99
    assert float(2 * jax.nn.sigmoid(ins[5]).min()) > 1.9
    _close(o, o_c, "out against the chunk form", ulps=64)
    for name, g, w in zip("query key value gate a b conv_weight a_log "
                          "dt_bias norm_gamma".split(), got, want):
        assert g.shape == w.shape
        assert float(jnp.abs(w).max()) > 1e-7, name
        _close(g, w, name + " against the chunk form", ulps=512)
    cfg = dict(CFG, linear_attn_config=dict(num_heads=heads, head_dim=d))
    o_w = ref.delta_attention(*ins, cfg)
    _close(o, o_w, "out against the reference's recurrence", ulps=64)
    # and the factor is on the op's path: without it another result
    o_1 = ref.delta_attention(*ins, dict(cfg, kda_allow_neg_eigval=False))
    assert float(jnp.abs(o_1 - o_w).max()) > 0.05 * float(jnp.abs(o_w).max())


# -- a router that is no whole lane row --------------------------------------

@pytest.mark.parametrize("experts,held,offset,top_k,d", [
    (320, 10, 0, 8, 64), (320, 10, 310, 8, 64), (20, 5, 10, 3, 48)],
    ids=["320_first_10", "320_last_10", "20_wide"])
def test_a_router_no_power_of_two_wide_routes_over_all_and_drops_no_row(
        experts, held, offset, top_k, d):
    """The cell's router: 320 outputs (2.5 lane rows), 10 experts held.
    The counts are 320 long and sum to tokens x 8, the held experts' rows
    fit the bound and every one of them is computed: the share's part is
    the reference's."""
    rng = np.random.RandomState(51)
    n, width = 192, 32
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(rng.randn(n, d))
    gate_w = f32(rng.randn(d, experts))
    bias = f32(0.05 * rng.randn(experts))
    up = f32(0.2 * rng.randn(held, d, 2 * width))
    down = f32(0.2 * rng.randn(held, width, d))
    want, counts, _ = ref.moe(x, gate_w, up, down, bias, top_k, True, offset)
    rows = int(np.asarray(counts)[offset:offset + held].sum())
    # a bound of three times the expected rows, as the cell's
    bound = max(3 * n * top_k * held // experts, rows)
    got, got_counts = topk_moe(
        {"gate_w": gate_w, "select_bias": bias, "w_gate_up": up,
         "w_down": down}, x, top_k, norm_topk_prob=True, scoring="sigmoid",
        expert_offset=offset, share_rows_bound=bound, renorm_eps=1e-20)
    assert got_counts.shape == (experts,)
    assert int(got_counts.sum()) == n * top_k
    np.testing.assert_array_equal(np.asarray(got_counts), np.asarray(counts))
    assert 0 < rows <= bound
    _close(got, want, "the held experts' part", ulps=32)


# -- from_config on the published keys ---------------------------------------

def _file():
    with open(FILE) as f:
        return json.load(f)


def _published():
    held = _file()
    return dict(held, share={}, **{k: held["published"][k] for k in (
        "num_hidden_layers", "gqa_layers", "n_routed_experts",
        "linear_attn_config", "num_attention_heads", "num_key_value_heads",
        "vocab_size")})


def test_from_config_reads_the_published_keys():
    cfg = _published()
    kinds = solar_open2.layer_kinds(cfg)
    assert len(kinds) == 48 and kinds.count(solar_open2.GQA) == 12
    assert [i for i, k in enumerate(kinds) if k == solar_open2.GQA] \
        == list(range(0, 48, 4))
    sym = solar_open2.from_config(cfg, seq_len=64)
    names = sym.list_arguments()
    assert "layer0_q_proj_weight" in names and \
        "layer0_kda_q_proj_weight" not in names     # layer 0 is GQA
    assert "layer0_moe_gate_weight" in names        # over experts
    assert "layer1_kda_q_proj_weight" in names
    assert "layer44_attn_gate_proj_weight" in names
    assert "layer47_kda_f_a_proj_weight" in names
    shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    shape = dict(zip(names, shapes))
    assert shape["layer0_moe_gate_weight"] == (4096, 320)
    assert shape["layer0_moe_gate_up_weight"] == (320, 4096, 2560)
    assert shape["layer0_shared_gate_proj_weight"] == (1280, 4096)
    assert shape["layer1_kda_q_proj_weight"] == (8192, 4096)
    assert shape["layer1_kda_f_a_proj_weight"] == (128, 4096)
    assert shape["layer1_kda_f_b_proj_weight"] == (8192, 128)
    assert shape["layer1_kda_dt_bias"] == (8192,)
    assert shape["layer1_kda_a_log"] == (64,)
    assert shape["layer1_kda_conv_weight"] == (4, 3 * 8192)
    assert shape["layer0_q_proj_weight"] == (8192, 4096)
    assert shape["layer0_attn_gate_proj_weight"] == (8192, 4096)
    assert shape["layer0_k_proj_weight"] == (1024, 4096)
    assert shape["lm_head_weight"] == (196608, 4096)
    # the defaults of get_symbol are the published model
    assert solar_open2.get_symbol(seq_len=64).list_arguments() == names


def test_the_file_is_the_share_the_cell_trains():
    """The configuration's file: the held counts, the published ones
    beside them, and the parameter count the issue's arithmetic gives."""
    cfg = _file()
    assert cfg["published"]["n_routed_experts"] == 320
    assert cfg["share"]["experts_of"] == 320
    sym = solar_open2.from_config(cfg, **cfg["kwargs"])
    t = cfg["kwargs"]["seq_len"]
    names = sym.list_arguments()
    shapes, outs, _ = sym.infer_shape(data=(1, t), softmax_label=(1, t))
    count = sum(int(np.prod(s)) for n, s in zip(names, shapes)
                if n not in ("data", "softmax_label"))
    heads = cfg["linear_attn_config"]["num_heads"]
    kda = (4 * 4096 * heads * 128 + 2 * (4096 * 128 + 128 * heads * 128)
           + 4096 * heads + 4 * 3 * heads * 128 + heads + heads * 128 + 128)
    q, kv = cfg["num_attention_heads"] * 128, cfg["num_key_value_heads"] * 128
    gqa = 3 * 4096 * q + 2 * 4096 * kv
    ffn = ((cfg["n_routed_experts"] + 1) * 3 * 4096 * 1280 + 4096 * 320
           + 320)
    assert count == (gqa + 3 * kda + 4 * (ffn + 2 * 4096) + 4096
                     + 2 * cfg["vocab_size"] * 4096), count
    assert outs == [(1,)] + [(320,)] * 4


@pytest.mark.parametrize("change,match", [
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(use_rope=True), "use_rope"),
    (dict(use_gqa_gate=False), "use_gqa_gate"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(linear_attn_config=dict(CFG["linear_attn_config"],
                                  num_kv_heads=2)), "num_kv_heads"),
    (dict(gqa_layers=[1]), "gqa_interval"),
    (dict(gqa_layers=[0, 2]), "gqa_interval"),
    (dict(gqa_layers=[4]), "gqa_layers"),
    (dict(gqa_layers=[0, 0]), "gqa_layers"),
], ids=["full_proj", "rope", "no_gate", "dense_layer", "tied", "kv_heads",
        "off_interval", "extra_layer", "out_of_range", "repeated"])
def test_from_config_refuses_what_it_cannot_honour(change, match):
    with pytest.raises(ValueError, match=match):
        solar_open2.from_config(dict(CFG, **change), seq_len=T)


def _graph(sym):  # auto-named nodes count up from one symbol to the next
    return re.sub(r'"([a-z_]*[a-z_])\d+"', r'"\1"', sym.tojson())


def test_the_keys_listed_as_unread_are_read_by_nothing():
    base = _graph(solar_open2.from_config(CFG, seq_len=T))
    moved = dict(CFG, partial_rotary_factor=0.5, rope_theta=5e5,
                 intermediate_size=7, max_position_embeddings=1 << 20)
    assert set(solar_open2.ASSUMED_UNREAD) == {
        "partial_rotary_factor", "rope_theta", "intermediate_size",
        "max_position_embeddings"}
    assert _graph(solar_open2.from_config(moved, seq_len=T)) == base
    assert _graph(solar_open2.from_config(
        dict(CFG, rms_norm_eps=1e-6), seq_len=T)) != base


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(HERE, "..", "mxnet_tpu", "models",
                           "solar_open2_reference.py")) as a, \
            open(os.path.join(HERE, "..", "bench", "reference",
                              "solar_open2.py")) as b:
        assert a.read() == b.read()


def test_kimis_symbol_is_what_it_was_before_its_mixer_moved():
    """``lm_blocks.kda_mixer`` builds Kimi Linear's KDA layers too: the
    symbol of its configuration's file is, node for node, the one
    ``kimi_linear.py`` built itself at PR 61 (argument names and shapes,
    and the whole graph with auto-numbered names levelled; the digests
    were taken on that commit)."""
    from test_pick_log_softmax import the_old_head

    with open(os.path.join(os.path.dirname(FILE),
                           "kimi_linear_48b_a3b.json")) as f:
        cfg = json.load(f)
    with the_old_head():  # the digests' head: two nodes where PR 65 put one
        sym = kimi_linear.from_config(cfg, **cfg["kwargs"])
    t = cfg["kwargs"]["seq_len"]
    names = sym.list_arguments()
    shapes, _, _ = sym.infer_shape(data=(1, t), softmax_label=(1, t))
    text = json.dumps([[n, list(s)] for n, s in zip(names, shapes)])
    assert len(names) == 103
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "86b0533411c0e10213d4056eacb4905147eee57e4984af775f71150065f7c340")
    assert hashlib.sha256(_graph(sym).encode()).hexdigest() == (
        "0e9c9a073e66295a1396b39cd07c1678b8e2d7a3f8cf5ab5725cbb382647622a")


# -- one test an ``assumed`` entry of the configuration's file ---------------

ASSUMED = {
    "gqa_gate": lambda text: (
        "sigmoid" in text and "arXiv:2505.06708" in text
        and str(_node(solar_open2.get_symbol(seq_len=64), "layer0_attn")[
            "attr"]["with_gate"]) in ("True", "1")),
    "router": lambda text: "sum + 1e-20" in text and "glm4_moe" in text
    and str(_node(solar_open2.get_symbol(seq_len=64), "layer0_moe")[
        "attr"]["renorm_eps"]) == "1e-20",
    "attention": lambda text: "1/sqrt(128)" in text
    and "no query/key norm" in text and "layer0_q_norm_gamma" not in
    solar_open2.get_symbol(seq_len=64).list_arguments(),
    "beta": lambda text: "2 sigmoid" in text and str(_node(
        solar_open2.get_symbol(seq_len=64), "layer1_kda")["attr"][
            "allow_neg_eigval"]) in ("True", "1"),
    "chunk": lambda text: (
        "chunks of 64" in text and "sub-blocks of 16" in text
        and tr.KDA_SUB_BLOCK == 16 and str(_node(
            solar_open2.get_symbol(seq_len=64), "layer1_kda")["attr"][
                "chunk_size"]) == "64"),
    "low_rank": lambda text: "4096 -> 128 ->" in text and "NO bias" in text,
    "block": lambda text: "h += mixer(RMSNorm(h)); h += ffn(RMSNorm(h))"
    in text,
    "dtype": lambda text: "float32" in text and text.startswith("bfloat16"),
    "optimizer": lambda text: "SGD momentum 0.9" in text,
    "objective": lambda text: "no auxiliary loss" in text,
    "weights": lambda text: "A_log = log(U(1, 16))" in text,
    "unread": lambda text: all(k in text
                               for k in solar_open2.ASSUMED_UNREAD),
}


@pytest.mark.parametrize("entry", sorted(ASSUMED))
def test_an_assumed_entry_says_what_the_program_does(entry):
    """Each assumption of ``bench/configs/solar_open2_250b.json`` is one
    entry, and it fails here if the file or the program moves."""
    assumed = _file()["assumed"]
    assert ASSUMED[entry](assumed[entry]), assumed[entry]
