"""Olmo-Hybrid-7B on the normal path against its plain reference.

``models/olmo_hybrid.py`` (an ``mx.sym`` graph of blocks that norm the
OUTPUT of their sub-layers: ``GatedDeltaNet`` between its seven
projections three times, then ``Attention`` over query/key-normed heads
without positions, a dense SwiGLU in every block) through
``Module.forward/backward`` and ``Module.fit``'s fused step, against
``models/olmo_hybrid_reference.py`` (plain float32 ``jax.numpy``: the
linear-attention layer as the token-by-token recurrence, attention by an
explicit mask) on seeded weights at a tiny size: hidden 48, one period
``L L L F``; linear attention of 3 heads with keys of 8 and values of
16, 4 taps, chunks of 8; 4 attention heads of 12; SwiGLU of 40; T 30
(not a multiple of the chunk).

Tolerances as in ``tests/test_nemotron_h.py``: both sides are float32
and only the order of summation differs, so rtol 1e-5 with an atol of a
few float32 ulps of the tensor's own scale (``_close``). Gradients get
1024 ulps (1.2e-4 of the tensor's largest entry): every block norms its
sub-layer's OUTPUT, which at these weights is small, so a gradient
passes eight ``1 / rms`` factors and the unit-length division of keys
and queries on its way down, and float32 rounding grows with them.
Measured against the same reference in float64 (seed 1): the float32
reference itself is 21-811 ulps off, the symbol 14-371, largest in the
first two layers and under 40 in the last. A wrong term is off by a
tenth of the tensor's scale or more.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import olmo_hybrid, olmo_hybrid_reference as ref
from mxnet_tpu.parallel import make_mesh

T, BATCH, CHUNK = 30, 2, 8
L, F = "linear_attention", "full_attention"
CFG = dict(
    model_type="olmo_hybrid", vocab_size=512, hidden_size=48,
    intermediate_size=40, num_hidden_layers=4, layer_types=[L, L, L, F],
    num_attention_heads=4, num_key_value_heads=4, hidden_act="silu",
    max_position_embeddings=T, attention_bias=False, rms_norm_eps=1e-6,
    tie_word_embeddings=False, linear_num_key_heads=3,
    linear_num_value_heads=3, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})
LINEAR_LAYERS = 3


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _symbol(cfg=CFG):
    """``from_config`` at the tests' chunk of 8 (the program's own
    choice, 64 by default, would make T one chunk)."""
    return olmo_hybrid.from_config(cfg, seq_len=T, chunk_size=CHUNK)


def _params(sym, seed, sigma=0.08):
    """Seeded weights under the symbol's argument names: Normal(sigma), a
    unit embedding as the model states it, gammas near 1, taps of the
    published spread, ``a_log`` and ``dt_bias`` by the published rule."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(BATCH, T), softmax_label=(BATCH, T))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("gdn_a_log"):
            out[name] = np.log(rng.uniform(1, 16, shape)).astype(np.float32)
        elif name.endswith("gdn_dt_bias"):
            dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), shape))
            out[name] = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        else:
            scale = (1.0 if name == "embed_weight" else
                     0.3 if name.endswith("conv_weight") else sigma)
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


@pytest.mark.parametrize("neg", [True, False],
                         ids=["neg_eigval", "no_neg_eigval"])
def test_logits_loss_and_every_gradient_match_the_reference(neg):
    """One period ``L L L F`` of post-norm blocks: per-sequence loss,
    the last positions' logits and the gradient of every parameter; with
    ``linear_allow_neg_eigval`` false the write strengths lose their
    factor 2 in the symbol and the reference alike, and the two
    settings' losses differ."""
    cfg = dict(CFG, linear_allow_neg_eigval=neg)
    sym = _symbol(cfg)
    params = _params(sym, 1)
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, cfg, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)
    other = ref.forward(params, tokens, dict(cfg, linear_allow_neg_eigval=(
        not neg)), labels=labels)["loss"]
    assert abs(float(other) - float(loss)) > 1e-4

    mod = _module(sym, params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert len(outs) == 1                       # the loss and nothing else
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention);
        # 1024 ulps: the float32 noise of either side (module docstring)
        _close(got[name].asnumpy() / BATCH, want_g, name, ulps=1024)
        assert np.abs(np.asarray(want_g)).max() > 1e-8, name

    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    # forward through eight output norms: 27 ulps measured, 64 allowed
    _close(logits, want["logits"], "logits", ulps=64)
    last = ref.forward(params, tokens, cfg, labels=labels, last=7)
    _close(last["logits"], want["logits"][:, -7:], "the last positions")
    assert np.isinf(np.asarray(last["router_gap"])).all()   # no experts


def test_fused_fit_trains_the_stage_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep: the first steps follow the reference's own SGD with
    momentum, and the loss falls."""
    sym = _symbol()
    params = _params(sym, 3)
    tokens, labels = _batch(4)
    lr, momentum, steps = 0.05, 0.9, 6

    want = {k: jnp.asarray(v) for k, v in params.items()}
    moms = {k: jnp.zeros_like(v) for k, v in want.items()}
    losses = []
    for _ in range(2):
        loss, grads = ref.loss_and_grads(want, tokens, labels, CFG)
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
    # the dynamics and the taps are trained like any weight
    for name in ("layer0_gdn_a_log", "layer1_gdn_dt_bias",
                 "layer2_gdn_conv_weight", "layer0_gdn_norm_gamma"):
        assert np.abs(got[name].asnumpy() - params[name]).max() > 0, name


def test_the_model_states_its_own_initialisation_and_counts_its_call_sites():
    telemetry.reset()
    telemetry.enable()
    try:
        sym = olmo_hybrid.from_config(dict(
            CFG, linear_num_key_heads=30, linear_num_value_heads=30),
            seq_len=T)
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
        # one per layer's call site, nothing per step; from_config's
        # chunk is the program's 64
        count = telemetry.REGISTRY.get("linear_attn.lowerings")
        assert count.value(heads=30, key_dim=8, value_dim=16, chunk=64,
                           conv=4, impl="chunked") == LINEAR_LAYERS
        # the gate and norm behind each rule: heads of 16 columns are no
        # lane rows, so the ``jax.numpy`` closure everywhere
        norm = telemetry.REGISTRY.get("gate_norm.lowerings")
        assert norm.value(site="gated_delta_net", groups=30, width=16,
                          impl="jnp") == LINEAR_LAYERS
        mod.forward(batch, is_train=False)
        assert telemetry.total("linear_attn.lowerings") == LINEAR_LAYERS
        assert telemetry.total("gate_norm.lowerings") == LINEAR_LAYERS
    finally:
        telemetry.disable()
        telemetry.reset()
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert 0.9 < got["embed_weight"].std() < 1.1
    assert 0.015 < got["layer0_gdn_v_proj_weight"].std() < 0.025
    assert 0.015 < got["layer3_q_proj_weight"].std() < 0.025
    for name in ("layer0_gdn_norm_gamma", "layer0_attn_norm_gamma",
                 "layer0_ffn_norm_gamma", "layer3_q_norm_gamma",
                 "layer3_k_norm_gamma", "final_norm_gamma"):
        assert (got[name] == 1).all(), name
    taps = got["layer0_gdn_conv_weight"]
    assert taps.shape == (4, 2 * 30 * 8 + 30 * 16)
    assert 0.45 < np.abs(taps).max() <= 0.5 and abs(taps.mean()) < 0.02
    # the published rule: rates in [1, 16], step sizes' bias the inverse
    # softplus of a step in [0.001, 0.1]: with the projection's part at 0
    # a head keeps between 0.94 (rate 1, step 0.001) and nothing of its
    # state over one chunk of 64 tokens, and some of 30 drawn heads keep a
    # tenth or more: what crosses a chunk is part of the result
    rate = np.exp(got["layer0_gdn_a_log"])
    step = np.log1p(np.exp(got["layer0_gdn_dt_bias"]))
    assert rate.min() >= 1 and rate.max() <= 16 and rate.std() > 2
    assert step.min() >= 0.00099 and step.max() <= 0.101
    kept = np.exp(-64 * rate * step)
    assert kept.max() > 0.1 and kept.min() < 1e-2, (kept.min(), kept.max())
    assert got["layer1_gdn_a_log"].tolist() != got["layer0_gdn_a_log"].tolist()


@pytest.mark.parametrize("key, value, match", [
    ("layer_types", [L, L, "sliding_attention", F], "layer_types"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("rope_parameters", {"rope_theta": 500000.0}, "rope_theta"),
    ("linear_num_value_heads", 6, "linear_num_value_heads"),
    ("hidden_act", "gelu", "hidden_act"),
    ("num_hidden_layers", 5, "num_hidden_layers"),
])
def test_from_config_refuses_what_it_does_not_implement(key, value, match):
    with pytest.raises(ValueError, match=match):
        olmo_hybrid.from_config(dict(CFG, **{key: value}), seq_len=T)


def test_from_config_takes_a_config_without_the_optional_keys():
    cfg = {k: v for k, v in CFG.items()
           if k not in ("rope_parameters", "attention_bias", "hidden_act",
                        "tie_word_embeddings")}
    assert olmo_hybrid.from_config(cfg).list_arguments() \
        == olmo_hybrid.from_config(CFG, seq_len=T).list_arguments()


def test_the_symbol_norms_outputs_and_adds_no_positions():
    """The block is ``h + RMSNorm(f(h))``: a sub-layer reads the
    residual stream itself, its norm reads the sub-layer; the head's
    nodes are under the names every LM symbol has; no positional op."""
    sym = _symbol()
    internals = sym.get_internals().list_outputs()
    for name in ("layer0_gdn_q_proj_output", "layer0_gdn_output",
                 "layer0_gdn_o_proj_output", "layer0_attn_norm_output",
                 "layer0_down_proj_output", "layer0_ffn_norm_output",
                 "layer3_q_norm_output", "layer3_k_norm_output",
                 "layer3_attn_output", "layer3_o_proj_output",
                 "final_norm_output", "lm_head_f32_output", "loss_output"):
        assert name in internals, name
    # two norms a block, two more in the full layer, the final one
    assert sum(n.endswith("norm_output") for n in internals) == 2 * 4 + 2 + 1
    assert not [n for n in internals if "rope" in n.lower()]
    assert not [n for n in internals if "expert_count" in n]
    # the norm's input is the projection's output, not the stream
    import json
    nodes = json.loads(sym.tojson())["nodes"]
    by_name = {n["name"]: n for n in nodes}

    def inputs(name):
        return [nodes[i[0]]["name"] for i in by_name[name]["inputs"]]

    assert inputs("layer0_attn_norm")[0] == "layer0_gdn_o_proj"
    assert inputs("layer0_ffn_norm")[0] == "layer0_down_proj"
    assert inputs("layer3_attn_norm")[0] == "layer3_o_proj"
    # the first block's projections read the embedding itself
    assert inputs("layer0_gdn_q_proj")[0] == "embed"


def test_the_benchmarks_copy_of_the_reference_is_the_programs():
    """``bench/reference/olmo_hybrid.py`` is this file byte for byte:
    the benchmark may not import the program's reference (it would then
    compare the program with itself across a refactor), and nothing else
    held the two equal."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "reference",
                           "olmo_hybrid.py"), "rb") as ours, \
            open(ref.__file__, "rb") as theirs:
        assert ours.read() == theirs.read()
