"""LFM2-24B-A2B on the normal path against its plain reference.

``models/lfm2.py`` (an ``mx.sym`` graph: ``ShortConv`` between
``conv_in_proj`` and ``conv_out_proj`` or ``Attention`` over grouped
heads whose queries and keys are normed a head and then rotated, a dense
SwiGLU or ``TopKMoE`` with sigmoid scores, a selection bias and the
``+ 1e-6`` renormalisation, a head that reads the embedding's matrix)
through ``Module.forward/backward`` and ``Module.fit``'s fused step,
against ``models/lfm2_reference.py`` (plain float32 ``jax.numpy``: the
convolution a loop over taps, attention by an explicit mask, a loop over
the experts held) on seeded weights at a tiny size: hidden 48, 3 taps, 4
query heads on 2 key/value heads of 12, a dense layer of 40 then 16
experts top-3 of width 24, vocabulary 512, T 30.

Tolerances as in ``tests/test_kanana2.py``: both sides are float32 and
only the order of summation differs, so rtol 1e-5 with an atol of a few
float32 ulps of the tensor's own scale (``_close``). A tap in the wrong
order, a norm over the whole projection instead of a head, a rotation
before the norm or a head with a matrix of its own is off by orders of
magnitude more.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import (kanana2, lfm2, lfm2_reference as ref, mimo_v2,
                              nemotron_h)
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import _route, topk_moe

T, BATCH = 30, 2
TYPES = ["conv", "full_attention", "conv", "conv"]
CFG = dict(
    model_type="lfm2_moe", hidden_size=48, num_hidden_layers=4,
    layer_types=TYPES, num_dense_layers=1, conv_L_cache=3, conv_bias=False,
    num_attention_heads=4, num_key_value_heads=2,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    intermediate_size=40, moe_intermediate_size=24, num_experts=16,
    num_experts_per_tok=3, norm_topk_prob=True, use_expert_bias=True,
    routed_scaling_factor=1, norm_eps=1e-5, vocab_size=512,
    max_position_embeddings=T)
# one chip's share of the same model: 4 of the 16 experts from the 8th
# on, a buffer that holds every row
SHARE = dict(CFG, num_experts=4, share=dict(
    experts_of=16, expert_offset=8, share_rows_bound=BATCH * T * 3))
EXPERT_LAYERS, CONV_LAYERS = 3, 3


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, sigma=0.08, data=(BATCH, T)):
    """Seeded weights under the symbol's argument names: Normal(sigma),
    an embedding of 0.3 (large enough that the tied head's logits and
    the stream both carry it), gammas near 1, taps of the published
    spread, selection biases away from 0 (so that their part is
    tested)."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=data, softmax_label=data)
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = (0.3 if name in ("embed_weight",) or
                 name.endswith("conv_weight")
                 else 0.05 if name.endswith("bias") else sigma)
        out[name] = (scale * rng.randn(*shape)
                     + name.endswith("_gamma")).astype(np.float32)
    return out


def _batch(seed):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, CFG["vocab_size"], (BATCH, T + 1))
    return tokens[:, :-1].astype(np.float32), tokens[:, 1:].astype(np.float32)


def _module(sym, params):
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH, T))],
             label_shapes=[("softmax_label", (BATCH, T))])
    mod.init_params(arg_params={k: mx.nd.array(v) for k, v in params.items()},
                    aux_params={})
    return mod


def _grads(sym, params, tokens, labels):
    mod = _module(sym, params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    return mod, {k: v.asnumpy()
                 for k, v in mod._exec_group.execs[0].grad_dict.items()
                 if k in params}


# -- the whole model, uncut and as a share -----------------------------------

@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_logits_loss_and_every_gradient_match_the_reference(cfg):
    sym = lfm2.from_config(cfg, seq_len=T)
    params = _params(sym, 1)
    assert "lm_head_weight" not in params       # the head is the embedding
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, cfg, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)

    mod, got = _grads(sym, params, tokens, labels)
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert len(outs) == 1 + EXPERT_LAYERS
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    for layer in range(EXPERT_LAYERS):
        # over all 16 of the router's experts, share or not
        np.testing.assert_array_equal(
            outs[1 + layer], np.asarray(want["expert_counts"][layer]))
        assert outs[1 + layer].sum() == BATCH * T * 3
    assert set(grads) == set(params) == set(got)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention)
        _close(got[name] / BATCH, want_g, name, ulps=32)
        if "select_bias" in name:
            assert not np.asarray(want_g).any()  # it moves the choice only
        else:
            assert np.abs(np.asarray(want_g)).max() > 1e-7, name

    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    _close(logits, want["logits"], "logits", ulps=16)


def test_the_tied_matrix_gradient_is_the_sum_of_both_uses():
    """The same weights under an untied symbol (``lm_blocks``' default
    head with a matrix of its own, set equal to the embedding): its two
    gradients add up to the tied symbol's one."""
    from mxnet_tpu.models import lm_blocks

    sym = lfm2.from_config(CFG, seq_len=T)
    params = _params(sym, 3)
    tokens, labels = _batch(4)
    _, tied = _grads(sym, params, tokens, labels)

    real = lm_blocks.head_and_loss
    lfm2.head_and_loss = lambda *a, **kw: real(*a, **dict(kw, tied_to=None))
    try:
        untied_sym = lfm2.from_config(CFG, seq_len=T)
    finally:
        lfm2.head_and_loss = real
    assert "lm_head_weight" in untied_sym.list_arguments()
    untied = dict(params, lm_head_weight=params["embed_weight"])
    _, got = _grads(untied_sym, untied, tokens, labels)
    assert np.abs(got["lm_head_weight"]).max() > 1e-7
    assert np.abs(got["embed_weight"]).max() > 1e-7
    _close(tied["embed_weight"],
           got["embed_weight"] + got["lm_head_weight"], "tied gradient")
    for name in params:
        if name != "embed_weight":
            _close(tied[name], got[name], name)


def test_fused_fit_trains_the_share_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep — on the share: the first steps follow the
    reference's own SGD with momentum (one momentum for the tied matrix),
    and the loss falls."""
    sym = lfm2.from_config(SHARE, seq_len=T)
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
    _close(seen[:2], losses, "loss of the first two steps")
    assert seen[-1] < seen[0] - 0.05, seen
    got, _ = mod.get_params()
    for name in params:  # no gradient and no rule moves the bias
        if "select_bias" in name:
            np.testing.assert_array_equal(got[name].asnumpy(), params[name])
    assert np.abs(got["layer0_conv_weight"].asnumpy()
                  - params["layer0_conv_weight"]).max() > 0


def test_the_model_states_its_own_initialisation_and_counts_its_call_sites():
    telemetry.reset()
    telemetry.enable()
    try:
        sym = lfm2.from_config(SHARE, seq_len=T)
        mod = mx.mod.Module(sym, context=mx.cpu(0))
        mod.bind(data_shapes=[("data", (BATCH, T))],
                 label_shapes=[("softmax_label", (BATCH, T))],
                 for_training=False)
        mx.random.seed(5)
        np.random.seed(5)
        mod.init_params(initializer=mx.init.Normal(sigma=0.02))
        tokens, labels = _batch(6)
        batch = mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)])
        mod.forward(batch, is_train=False)
        # one per layer's call site, nothing per step
        sconv = telemetry.REGISTRY.get("sconv.lowerings")
        assert sconv.value(channels=48, taps=3, impl="jnp") == CONV_LAYERS
        share = telemetry.REGISTRY.get("moe.share_lowerings")
        assert share.value(held=4, of=16, bound=BATCH * T * 3,
                           sum="segment_product", renorm_eps=1e-6) == EXPERT_LAYERS
        mod.forward(batch, is_train=False)
        assert telemetry.total("sconv.lowerings") == CONV_LAYERS
    finally:
        telemetry.disable()
        telemetry.reset()
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    # the family's initializer_range, not the untied symbols' unit
    # embedding: the head reads this matrix
    assert 0.015 < got["embed_weight"].std() < 0.025
    assert 0.015 < got["layer0_conv_in_proj_weight"].std() < 0.025
    assert got["layer0_conv_in_proj_weight"].shape == (3 * 48, 48)
    assert not got["layer1_moe_select_bias"].any()
    assert got["layer1_moe_gate_up_weight"].shape == (4, 48, 2 * 24)
    assert got["layer1_q_norm_gamma"].shape == (12,)
    assert (got["layer1_k_norm_gamma"] == 1).all()
    taps = got["layer0_conv_weight"]
    assert taps.shape == (3, 48)
    assert 0.5 < np.abs(taps).max() <= 3 ** -0.5 and abs(taps.mean()) < 0.1
    assert "layer0_conv_bias" not in got and "lm_head_weight" not in got


def test_from_config_refuses_what_it_does_not_implement():
    for key, value in [("conv_bias", True), ("use_expert_bias", False),
                       ("norm_topk_prob", False),
                       ("tie_word_embeddings", False),
                       ("attention_bias", True), ("num_hidden_layers", 3)]:
        with pytest.raises(ValueError, match=key):
            lfm2.from_config(dict(CFG, **{key: value}), seq_len=T)
    with pytest.raises(ValueError, match="sliding_attention"):
        lfm2.from_config(dict(CFG, layer_types=["conv", "sliding_attention",
                                                "conv", "conv"]), seq_len=T)
    with pytest.raises(ValueError, match="rope_type"):
        lfm2.from_config(dict(CFG, rope_parameters={
            "rope_theta": 1e6, "rope_type": "yarn"}), seq_len=T)


def test_the_symbol_is_built_from_the_shared_blocks():
    """Two norms a layer, the mixer an entry of ``layer_types``, the
    feed-forward dense then sparse, the head's nodes under the names
    every LM symbol has and no ``lm_head_weight`` among the arguments."""
    sym = lfm2.from_config(CFG, seq_len=T)
    internals = sym.get_internals().list_outputs()
    for name in ("layer0_operator_norm_output", "layer0_conv_in_proj_output",
                 "layer0_conv_output", "layer0_conv_out_proj_output",
                 "layer0_ffn_norm_output", "layer0_down_proj_output",
                 "layer1_q_norm_output", "layer1_k_rope_output",
                 "layer1_attn_output", "layer1_moe_output",
                 "layer3_conv_output", "final_norm_output",
                 "lm_head_f32_output", "loss_output"):
        assert name in internals, name
    assert "layer0_moe_output" not in internals
    assert "layer1_down_proj_output" not in internals
    args = sym.list_arguments()
    assert args.count("embed_weight") == 1 and "lm_head_weight" not in args
    assert mx.executor.op_class("_contrib_ShortConv") == "sconv"
    # the published depth and pattern are the defaults
    whole = lfm2.get_symbol(seq_len=8).get_internals().list_outputs()
    assert sum(n.endswith("_attn_output") for n in whole) == 10
    assert sum(n.endswith("_conv_output") for n in whole) == 30
    assert sum(n.endswith("_moe_output") for n in whole) == 38
    assert "layer2_attn_output" in whole and "layer38_attn_output" in whole


# -- the expert layer: the bias, the renormalisation, the shares -------------

def _moe_params(seed, held=16, d=48, width=24, experts=16):
    rng = np.random.RandomState(seed)
    return {"gate_w": jnp.asarray(0.5 * rng.randn(d, experts), jnp.float32),
            "w_gate_up": jnp.asarray(0.1 * rng.randn(held, d, 2 * width),
                                     jnp.float32),
            "w_down": jnp.asarray(0.1 * rng.randn(held, width, d),
                                  jnp.float32)}


def test_the_bias_changes_which_experts_are_chosen_and_never_their_weights():
    params = _moe_params(7)
    x = jnp.asarray(np.random.RandomState(8).randn(64, 48), jnp.float32)
    bias = np.zeros(16, np.float32)
    bias[[2, 5]] = 10.0                 # always chosen, whatever the score
    w0, e0 = _route(params, x, 3, True, "sigmoid", renorm_eps=1e-6)
    w1, e1 = _route(dict(params, select_bias=jnp.asarray(bias)), x, 3, True,
                    "sigmoid", renorm_eps=1e-6)
    e0, e1 = np.asarray(e0), np.asarray(e1)
    assert (np.sort(e1, axis=1)[:, :0:-1] != np.sort(e0, axis=1)[:, :0:-1]
            ).any()
    assert all({2, 5} <= set(row) for row in e1.tolist())
    # the weights are the chosen experts' SCORES over their sum + 1e-6:
    # the bias, 10 on two of three, is nowhere in them
    scores = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                               @ np.asarray(params["gate_w"], np.float64))))
    chosen = np.take_along_axis(scores, e1, axis=1)
    want = chosen / (chosen.sum(axis=1, keepdims=True) + 1e-6)
    _close(w1, want, "weights under the bias")
    assert np.asarray(w1).max() < 1.0


def test_the_renormalisation_adds_its_epsilon():
    """Where the chosen scores are tiny the ``+ 1e-6`` shows: weights sum
    to ``s / (s + 1e-6)``, and to exactly the plain quotient with
    ``renorm_eps=0`` (what every other model computes)."""
    # every expert's logit is -40: the router reads column 0 alone
    params = {"gate_w": jnp.zeros((48, 16), jnp.float32).at[0].set(1.0)}
    x = jnp.asarray(np.random.RandomState(10).randn(8, 48), jnp.float32)
    x = x.at[:, 0].set(-40.0)
    with_eps, _ = _route(params, x, 3, True, "sigmoid", renorm_eps=1e-6)
    without, _ = _route(params, x, 3, True, "sigmoid")
    s = 3 / (1 + np.exp(40.0))
    _close(np.asarray(with_eps).sum(axis=1), np.full(8, s / (s + 1e-6)),
           "sum with the epsilon")
    _close(np.asarray(without).sum(axis=1), np.ones(8), "sum without")
    assert np.asarray(with_eps).sum(axis=1).max() < 1e-10


def test_the_shares_add_up_to_the_uncut_layer():
    """One sparse layer: the held parts of all four shares of four
    experts each, every one routed over all sixteen, sum to the uncut
    reference's layer (no shared expert: nothing is counted twice)."""
    whole = _moe_params(11)
    x = jnp.asarray(np.random.RandomState(12).randn(BATCH * T, 48),
                    jnp.float32)
    bias = jnp.asarray(0.05 * np.random.RandomState(13).randn(16),
                       jnp.float32)
    want, want_counts, _ = ref.moe(x, whole["gate_w"], whole["w_gate_up"],
                                   whole["w_down"], bias, 3)
    total = np.zeros(want.shape, np.float64)
    for offset in range(0, 16, 4):
        part = dict(whole, select_bias=bias,
                    w_gate_up=whole["w_gate_up"][offset:offset + 4],
                    w_down=whole["w_down"][offset:offset + 4])
        y, counts = topk_moe(part, x, 3, norm_topk_prob=True,
                             scoring="sigmoid", expert_offset=offset,
                             share_rows_bound=BATCH * T * 3, renorm_eps=1e-6)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        ref_part, _, _ = ref.moe(x, whole["gate_w"], part["w_gate_up"],
                                 part["w_down"], bias, 3, offset=offset)
        _close(y, ref_part, "share from expert %d" % offset)
        total += np.asarray(y, np.float64)
    _close(total, want, "the four shares' sum", ulps=16)


@pytest.mark.parametrize("model", [mimo_v2, kanana2, nemotron_h],
                         ids=["mimo_v2", "kanana2", "nemotron_h"])
def test_the_other_share_symbols_state_no_epsilon(model):
    """``renorm_eps`` is 0 unless a model says otherwise: the three share
    symbols' expert nodes carry no such attribute, so their routing is
    the plain quotient it was; this model's carry it."""
    assert "_contrib_TopKMoE" in model.get_symbol(seq_len=8).tojson()
    assert "renorm_eps" not in model.get_symbol(seq_len=8).tojson()
    assert "renorm_eps" in lfm2.from_config(CFG, seq_len=T).tojson()
