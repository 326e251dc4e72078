"""Kanana-2-30B-A3B on the normal path against its plain reference.

``models/kanana2.py`` (an ``mx.sym`` graph of ``RMSNorm``,
``LatentAttention`` — keys and values projected up from a normalised
latent, a rotary key shared by every head, interleaved RoPE on the last
dimensions of a head — shared experts beside ``TopKMoE`` with sigmoid
scores, a selection bias, ``routed_scale`` and a share of the experts)
through ``Module.forward/backward`` and ``Module.fit``'s fused step,
against ``models/kanana2_reference.py`` (plain float32 ``jax.numpy``:
the published de-interleave-then-``rotate_half`` order, attention by an
explicit mask, a loop over the experts held) on seeded weights at a tiny
size: hidden 64, 4 heads of 16 + 8 query/key and 16 value dimensions
from a latent of 32, 16 experts top-3 of width 32, 2 shared, T 32.

Tolerances as in ``tests/test_mimo_v2.py``: both sides are float32 and
only the order of summation differs, so rtol 1e-5 with an atol of a few
float32 ulps of the tensor's own scale (``_close``); a rotation of the
wrong slice, a key part not shared, a latent not normalised or a scale
left out is off by orders of magnitude more. The bf16 cases measure
their tolerances, see there.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import kanana2, kanana2_reference as ref
from mxnet_tpu.ops.transformer import latent_attention, rope
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import topk_moe

T, BATCH = 32, 2
HEADS, NOPE, ROPE, DV, LATENT = 4, 16, 8, 16, 32
CFG = dict(
    model_type="deepseek_v3", hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, moe_layer_freq=1, num_attention_heads=HEADS,
    num_key_value_heads=HEADS, qk_nope_head_dim=NOPE,
    qk_rope_head_dim=ROPE, qk_head_dim=NOPE + ROPE, head_dim=ROPE,
    v_head_dim=DV, kv_lora_rank=LATENT, q_lora_rank=None,
    rope_theta=1000000, rope_interleave=True, rope_scaling=None,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
    n_shared_experts=2, num_experts_per_tok=3, norm_topk_prob=True,
    scoring_func="sigmoid", n_group=1, topk_group=1,
    topk_method="noaux_tc", routed_scaling_factor=2.448,
    rms_norm_eps=1e-6, vocab_size=512, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False,
    max_position_embeddings=T)
# one chip's share of the same model: 4 of the 16 experts from the 8th
# on, a buffer that holds every row
SHARE = dict(CFG, n_routed_experts=4, share=dict(
    experts_of=16, expert_offset=8, share_rows_bound=BATCH * T * 3))
EXPERT_LAYERS = 2
MLA = {k: CFG[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                           "v_head_dim", "kv_lora_rank", "rope_theta",
                           "rope_interleave", "rms_norm_eps")}


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, sigma=0.08, t=T):
    """Seeded weights under the symbol's argument names: Normal(sigma),
    a unit embedding as the model states it, gammas near 1 and selection
    biases away from 0 (so that their part is tested)."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(BATCH, t), softmax_label=(BATCH, t))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = {"embed_weight": 1.0, "bias": 0.05}.get(
            name if name == "embed_weight" else name.rsplit("_", 1)[-1],
            sigma)
        out[name] = (scale * rng.randn(*shape)
                     + name.endswith("_gamma")).astype(np.float32)
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


# -- the whole model, uncut and as a share -----------------------------------

@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_logits_loss_and_every_gradient_match_the_reference(cfg):
    sym = kanana2.from_config(cfg, seq_len=T)
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
        _close(got[name].asnumpy() / BATCH, want_g, name, ulps=16)
        if "select_bias" in name:
            assert not np.asarray(want_g).any()  # it moves the choice only
        elif "latent_gamma" in name or "shared" in name:
            assert np.abs(np.asarray(want_g)).max() > 1e-6

    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    _close(logits, want["logits"], "logits")


def test_fused_fit_trains_the_share_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep — on the share: the first steps follow the
    reference's own SGD with momentum, and the loss falls."""
    sym = kanana2.from_config(SHARE, seq_len=T)
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


def test_the_model_states_its_own_initialisation_and_counts_its_call_sites():
    telemetry.reset()
    telemetry.enable()
    try:
        sym = kanana2.from_config(SHARE, seq_len=T)
        mod = mx.mod.Module(sym, context=mx.cpu(0))
        mod.bind(data_shapes=[("data", (BATCH, T))],
                 label_shapes=[("softmax_label", (BATCH, T))],
                 for_training=False)
        mx.random.seed(5)
        mod.init_params(initializer=mx.init.Normal(sigma=0.02))
        tokens, labels = _batch(6)
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                    label=[mx.nd.array(labels)]),
                    is_train=False)
        # one per layer's call site, nothing per step
        latent = telemetry.REGISTRY.get("attention.latent_lowerings")
        assert latent.value(heads=HEADS, latent=LATENT, rope=ROPE,
                            nope=NOPE, dv=DV, impl="composed") == 3
        assert telemetry.total("attention.latent_lowerings") == 3
        share = telemetry.REGISTRY.get("moe.share_lowerings")
        assert share.value(held=4, of=16, bound=BATCH * T * 3,
                           sum="segment_product", scale=2.448) == 2
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                    label=[mx.nd.array(labels)]),
                    is_train=False)
        assert telemetry.total("attention.latent_lowerings") == 3
    finally:
        telemetry.disable()
        telemetry.reset()
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert 0.9 < got["embed_weight"].std() < 1.1
    assert 0.015 < got["layer1_q_proj_weight"].std() < 0.025
    assert 0.015 < got["layer1_attn_up_weight"].std() < 0.025
    assert not got["layer1_moe_select_bias"].any()
    assert (got["layer0_attn_latent_gamma"] == 1).all()
    assert got["layer0_attn_up_weight"].shape == (HEADS * (NOPE + DV), LATENT)
    assert got["layer1_shared_gate_proj_weight"].shape == (64, 64)
    assert "layer0_shared_gate_proj_weight" not in got  # the dense layer


# heads whose own keys and values are whole lane rows, as the cell's are
# (128 + 64 / 128): the shapes ``kernels.latent_flash_takes`` admits
# the share at T 128, the shortest sequence either path sends to its
# kernels
SHARE_128 = dict(
    SHARE, max_position_embeddings=128,
    share=dict(SHARE["share"], share_rows_bound=BATCH * 128 * 3))
LANE_WHOLE = dict(
    SHARE_128, num_attention_heads=2, num_key_value_heads=2,
    qk_nope_head_dim=128, qk_rope_head_dim=64, qk_head_dim=192, head_dim=64,
    v_head_dim=128)


@pytest.mark.parametrize("path", ["flash_over_the_composition",
                                  "latent_pair"])
def test_every_layer_takes_the_one_pass_backward(monkeypatch, path):
    """Sent through the kernels (the Pallas interpreter here, by the
    kernel layer's one test seam; on the chip the op takes them by itself), every layer's attention traces the one-pass
    backward, and the gradients are the reference's through it. On the
    tiny widths the composition's ``attention`` call takes the flash
    kernel, a call site a layer; on lane-whole heads, the path the cell
    runs, the latent pair: three call sites, ONE trace of its forward and
    of its one-pass backward (there is no other), none of ``flash_*``."""
    from mxnet_tpu.ops import kernels as pk
    from mxnet_tpu.ops import transformer as tr

    pair = path == "latent_pair"
    cfg, t = (LANE_WHOLE if pair else SHARE_128), 128
    monkeypatch.setattr(pk.common, "INTERPRET", True)
    if pair:
        for jitted in (pk.latent.latent_fwd_call, pk.latent.latent_bwd_call,
                       pk.latent.latent_forward, pk.latent.latent_backward):
            jitted.clear_cache()
    sym = kanana2.from_config(cfg, seq_len=t)
    params = _params(sym, 7, t=t)
    tokens, labels = _batch(8, t)
    _, grads = ref.loss_and_grads(params, tokens, labels, cfg)
    telemetry.reset()
    telemetry.enable()
    try:
        mod = _module(sym, params, t)
        batch = mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)])
        for _ in range(2):  # a second step traces nothing
            mod.forward(batch, is_train=True)
            mod.backward()
        flash = telemetry.REGISTRY.get("attention.flash_lowerings")
        sites = telemetry.REGISTRY.get("attention.latent_lowerings")
        traces = telemetry.REGISTRY.get("attention.latent_kernel_traces")
        if pair:
            assert sites.value(heads=2, latent=LATENT, rope=64, nope=128,
                               dv=128, impl="kernel") == 3
            assert traces.value(**{"pass": "fwd"}) == 1
            assert traces.value(**{"pass": "bwd"}) == 1
            assert telemetry.total("attention.flash_lowerings") == 0
        else:
            tile = dict(operands="f32", block_q=t, block_k=t)
            assert flash.value(window=0, bwd="fused", **tile) == 3
            assert flash.value(window=0, bwd="split", **tile) == 0
            assert flash.value(window=0, kv_heads=HEADS, dv=DV, **tile) == 3
            assert sites.value(heads=HEADS, latent=LATENT, rope=ROPE,
                               nope=NOPE, dv=DV, impl="composed") == 3
            assert telemetry.total("attention.latent_kernel_traces") == 0
    finally:
        telemetry.disable()
        telemetry.reset()
    got = mod._exec_group.execs[0].grad_dict
    for name, want_g in grads.items():
        _close(got[name].asnumpy() / BATCH, want_g, name, rtol=1e-4,
               ulps=64)


def test_from_config_refuses_what_it_does_not_implement():
    for key, value in [("q_lora_rank", 1536), ("n_group", 2),
                       ("rope_scaling", {"type": "yarn", "factor": 4}),
                       ("attention_bias", True), ("topk_group", 2),
                       ("topk_method", "greedy"), ("hidden_act", "gelu"),
                       ("tie_word_embeddings", True), ("moe_layer_freq", 2),
                       ("scoring_func", "tanh"), ("qk_head_dim", 32),
                       ("num_key_value_heads", 2), ("head_dim", 24)]:
        with pytest.raises(ValueError, match=key):
            kanana2.from_config(dict(CFG, **{key: value}), seq_len=T)


def test_mimo_and_olmoe_keep_their_node_names_over_the_shared_blocks():
    """``models/lm_blocks.py`` names nothing itself: the MiMo and OLMoE
    symbols list the arguments and internals they listed before."""
    from mxnet_tpu.models import mimo_v2, olmoe
    from test_mimo_v2 import CFG as MIMO

    args = mimo_v2.from_config(MIMO, seq_len=32).list_arguments()
    for name in ("layer0_gate_proj_weight", "layer0_up_proj_weight",
                 "layer0_down_proj_weight", "layer1_moe_gate_weight",
                 "layer1_moe_select_bias", "final_norm_gamma",
                 "lm_head_weight"):
        assert name in args
    for sym in (mimo_v2.from_config(MIMO, seq_len=32),
                olmoe.get_symbol(vocab_size=64, hidden_size=32,
                                 num_layers=1, num_heads=2, num_experts=4,
                                 experts_per_token=2, expert_width=16,
                                 seq_len=8)):
        internals = sym.get_internals().list_outputs()
        for name in ("lm_head_f32_output", "lm_head_pick_output",
                     "lm_head_mean_output", "loss_output",
                     "final_norm_output"):
            assert name in internals


# -- LatentAttention ---------------------------------------------------------

def _mla_inputs(seed, dtype=jnp.float32):
    rng = np.random.RandomState(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(scale * rng.randn(*shape), dtype)

    return (draw(BATCH, T, HEADS * (NOPE + ROPE)),
            draw(BATCH, T, LATENT + ROPE),
            (1 + draw(LATENT, scale=0.1)).astype(dtype),
            draw(HEADS * (NOPE + DV), LATENT, scale=0.2),
            jnp.asarray(rng.randn(BATCH, T, HEADS * DV), jnp.float32))


def _op(q, latent, gamma, w_up, interleave=True):
    return latent_attention(q, latent, gamma, w_up, num_heads=HEADS,
                            rope_dim=ROPE, v_head_dim=DV, theta=1e6,
                            eps=1e-6, interleave=interleave)


def _plain(q, latent, gamma, w_up, interleave=True):
    return ref.latent_attention(q, latent, gamma, w_up,
                                dict(MLA, rope_interleave=interleave))


@pytest.mark.parametrize("interleave", [True, False],
                         ids=["interleaved", "rotate_half"])
def test_latent_attention_matches_the_reference(interleave):
    """Forward and the gradient with respect to every input (the query,
    the down-projection's output with its shared rotary key, the
    latent's gamma, the up-projection), float32 to summation order."""
    q, latent, gamma, w_up, cot = _mla_inputs(0)

    def loss(fn):
        return lambda *ins: jnp.sum(fn(*ins, interleave) * cot)

    _close(_op(q, latent, gamma, w_up, interleave),
           _plain(q, latent, gamma, w_up, interleave), "out")
    got = jax.grad(loss(_op), (0, 1, 2, 3))(q, latent, gamma, w_up)
    want = jax.grad(loss(_plain), (0, 1, 2, 3))(q, latent, gamma, w_up)
    for name, g, w in zip(("dq", "dlatent", "dgamma", "dw_up"), got, want):
        assert g.shape == w.shape
        _close(g, w, name, ulps=32)
    # the shared rotary key takes every head's gradient
    assert float(jnp.abs(want[1][..., LATENT:]).max()) > 1e-3


def test_latent_attention_in_bf16_is_one_rounding_from_the_reference():
    """bf16 inputs, the op's float32 statistics, rotation and softmax:
    against the float32 reference on the same bf16-rounded inputs the
    output's rms error is that of rounding the keys, values and the
    result to bf16, 0.00388-0.00422 of the output's standard deviation
    (measured here, seeds 0..5). The reference one precision below (the
    latent's norm, the rotation's tables and the softmax in bf16 too)
    reads 0.00546-0.00631. The limit 0.0048 lies between, an eighth
    from either."""
    for seed in range(3):
        q, latent, gamma, w_up, _ = _mla_inputs(seed, jnp.bfloat16)
        f32 = [a.astype(jnp.float32) for a in (q, latent, gamma, w_up)]
        want = _plain(*f32)

        def rms(out):
            return float(jnp.sqrt(jnp.mean(
                (out.astype(jnp.float32) - want) ** 2)) / want.std())

        got = _op(q, latent, gamma, w_up)
        assert got.dtype == jnp.bfloat16
        ours, theirs = rms(got), rms(_plain(q, latent, gamma, w_up))
        assert ours < 0.0048 < theirs, (seed, ours, theirs)


def test_a_bf16_latent_norm_is_caught_at_op_level():
    """The latent's statistics are float32 whatever the inputs: with a
    latent whose mean square sits where bf16 has 8 bits, the op's
    normalised latent is the float32 one rounded once; normalising in
    bf16 moves it by more than one bf16 ulp on some element."""
    from mxnet_tpu.ops.transformer import rms_norm

    rng = np.random.RandomState(7)
    c = jnp.asarray(3.0 + rng.randn(64, LATENT), jnp.bfloat16)
    gamma = jnp.ones((LATENT,), jnp.bfloat16)
    want = ref.rms_norm(c.astype(jnp.float32), 1.0, 1e-6)
    got = rms_norm(c, gamma, 1e-6).astype(jnp.float32)
    low = ref.rms_norm(c, gamma, 1e-6).astype(jnp.float32)
    once = want.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(once))
    assert float(jnp.abs(low - once).max()) > 0


def test_latent_attention_op_checks_its_inputs():
    q, c = mx.sym.Variable("q"), mx.sym.Variable("c")

    def infer(q_shape, c_shape, **attrs):
        attrs = dict(dict(num_heads=HEADS, rope_dim=ROPE, v_head_dim=DV),
                     **attrs)
        op = mx.contrib.sym.LatentAttention(q, c, name="attn", **attrs)
        return op.list_arguments(), op.infer_shape(q=q_shape, c=c_shape)

    names, (ins, outs, _) = infer((2, T, HEADS * 24), (2, T, LATENT + ROPE))
    assert names == ["q", "c", "attn_latent_gamma", "attn_up_weight"]
    assert ins[2:] == [(LATENT,), (HEADS * (NOPE + DV), LATENT)]
    assert outs == [(2, T, HEADS * DV)]
    for bad, what in [
            (dict(q_shape=(2, T, HEADS * 24 + 1), c_shape=(2, T, 40)),
             "query"),
            (dict(q_shape=(2, T, 96), c_shape=(2, T + 1, 40)), "latent"),
            (dict(q_shape=(2, T, 96), c_shape=(2, T, 40), rope_dim=7),
             "rotated"),
            (dict(q_shape=(2, T, 96), c_shape=(2, T, 40), v_head_dim=0),
             "v_head_dim")]:
        with pytest.raises(Exception, match=what):
            infer(**bad)


# -- RoPE: interleaved pairs, a slice that is not the head's first ----------

def test_interleaved_rope_gives_the_published_scores():
    """The op rotates pairs (2i, 2i + 1) in place; the published code
    de-interleaves and applies ``rotate_half``. Each is the other under
    one fixed permutation of a head's dimensions, so every query-key
    score is the same; a rotation of the wrong slice is not."""
    rng = np.random.RandomState(3)
    width = NOPE + ROPE
    q = jnp.asarray(rng.randn(BATCH, T, HEADS * width), jnp.float32)
    k = jnp.asarray(rng.randn(BATCH, T, HEADS * width), jnp.float32)

    def ours(x, offset=NOPE):
        return rope(x, HEADS, 1e6, ROPE, offset, True).reshape(
            BATCH, T, HEADS, width)

    def published(x):
        x = x.reshape(BATCH, T, HEADS, width)
        return jnp.concatenate(
            [x[..., :NOPE], ref.rope(x[..., NOPE:], 1e6, True)], axis=-1)

    def scores(a, b):
        return jnp.einsum("bqhd,bkhd->bhqk", a, b)

    got, want = ours(q), published(q)
    _close(scores(got, ours(k)), scores(want, published(k)), "scores")
    # the same numbers, de-interleaved; the first 16 pass through
    perm = np.concatenate([np.arange(0, ROPE, 2), np.arange(1, ROPE, 2)])
    _close(got[..., NOPE:][..., perm], want[..., NOPE:], "rotated part")
    np.testing.assert_array_equal(
        np.asarray(got[..., :NOPE]),
        np.asarray(q.reshape(BATCH, T, HEADS, width)[..., :NOPE]))
    # position 0 is not rotated at all; later ones are
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(
        q.reshape(BATCH, T, HEADS, width)[:, 0]))
    wrong = scores(ours(q, offset=0), ours(k, offset=0))
    assert float(jnp.abs(wrong - scores(want, published(k))).max()) > 0.1
    # and rotate_half on the same slice is another function
    halves = rope(q, HEADS, 1e6, ROPE, NOPE, False).reshape(got.shape)
    assert float(jnp.abs(halves - got).max()) > 0.1


def test_rope_op_checks_its_slice():
    data = mx.sym.Variable("data")

    def infer(**attrs):
        return mx.contrib.sym.RoPE(data, num_heads=HEADS, **attrs) \
            .infer_shape(data=(2, T, HEADS * 24))

    assert infer(rotary_dim=8, rotary_offset=16, interleave=True)[1] == [
        (2, T, HEADS * 24)]
    assert infer(rotary_offset=16)[1] == [(2, T, HEADS * 24)]  # the rest
    for bad in (dict(rotary_dim=8, rotary_offset=18), dict(rotary_dim=7),
                dict(rotary_dim=26), dict(rotary_offset=23)):
        with pytest.raises(Exception, match="rotated"):
            infer(**bad)


# -- TopKMoE: routed_scale, the shares and what every chip computes alike ---

def _moe_weights(seed, d=64, experts=16, hidden=32, tokens=BATCH * T):
    rng = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (f32(rng.randn(tokens, d)),
            {"gate_w": f32(rng.randn(d, experts) * 0.3),
             "w_gate_up": f32(rng.randn(experts, d, 2 * hidden) * 0.1),
             "w_down": f32(rng.randn(experts, hidden, d) * 0.1),
             "select_bias": jnp.zeros(experts, jnp.float32)})


def _held(w, offset, held):
    return dict(w, w_gate_up=w["w_gate_up"][offset:offset + held],
                w_down=w["w_down"][offset:offset + held])


@pytest.mark.parametrize("held", [16, 4], ids=["whole_layer", "share"])
def test_routed_scale_multiplies_the_weights_and_is_differentiated(held):
    x, w = _moe_weights(0)
    part = _held(w, 8 if held < 16 else 0, held)
    offset = 8 if held < 16 else 0

    def run(x, part, scale):
        return topk_moe(part, x, 3, norm_topk_prob=True, scoring="sigmoid",
                        expert_offset=offset,
                        share_rows_bound=x.shape[0] * 3 if held < 16 else 0,
                        routed_scale=scale)[0]

    def plain(x, part, scale):
        return ref.moe(x, part["gate_w"], part["w_gate_up"], part["w_down"],
                       part["select_bias"], 3, True, "sigmoid", offset,
                       scale)[0]

    _close(run(x, part, 2.448), plain(x, part, 2.448), "scaled")
    _close(run(x, part, 2.448), 2.448 * run(x, part, 1.0), "linear")
    cot = jnp.asarray(np.random.RandomState(1).randn(*x.shape), jnp.float32)
    got = jax.grad(lambda x, p: jnp.sum(run(x, p, 2.448) * cot), (0, 1))(
        x, part)
    want = jax.grad(lambda x, p: jnp.sum(plain(x, p, 2.448) * cot), (0, 1))(
        x, part)
    _close(got[0], want[0], "dx", ulps=32)
    for name in ("gate_w", "w_gate_up", "w_down"):
        _close(got[1][name], want[1][name], name, ulps=32)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """THE SHARE-SUM TEST. One expert layer of the model at the tiny
    size: the residual ``h``, its norm, the router over all 16 experts,
    the shared experts and the routed ones. Eight shares of two experts
    each route over all 16 and compute their own experts' part; the
    shared experts (and the residual) are what every chip computes
    alike and count once. The sum is the uncut reference's layer."""
    rng = np.random.RandomState(5)
    x, w = _moe_weights(5)
    shared = [jnp.asarray(rng.randn(*s) * 0.1, jnp.float32)
              for s in ((64, 64), (64, 64), (64, 64))]
    whole, counts, _ = ref.moe(
        x, w["gate_w"], w["w_gate_up"], w["w_down"], w["select_bias"], 3,
        True, "sigmoid", 0, 2.448)
    want = x + ref.swiglu(x, *shared) + whole

    total = x + ref.swiglu(x, *shared)          # counted once
    for offset in range(0, 16, 2):
        part, part_counts = topk_moe(
            _held(w, offset, 2), x, 3, norm_topk_prob=True,
            scoring="sigmoid", expert_offset=offset,
            share_rows_bound=x.shape[0] * 3, routed_scale=2.448)
        np.testing.assert_array_equal(np.asarray(part_counts),
                                      np.asarray(counts))
        mine, _, _ = ref.moe(
            x, w["gate_w"], w["w_gate_up"][offset:offset + 2],
            w["w_down"][offset:offset + 2], w["select_bias"], 3, True,
            "sigmoid", offset, 2.448)
        _close(part, mine, "share at %d" % offset)
        total = total + part
    _close(total, want, "sum of the eight shares", ulps=32)
    # adding the shared experts in every share would count them 8 times
    assert float(jnp.abs(ref.swiglu(x, *shared)).max()) > 1e-2


def test_bf16_share_keeps_its_scaled_sigmoid_router_in_float32():
    """bf16 activations and weights, router in float32: on the same
    bf16-rounded inputs the float32 reference takes the same routing
    decision for EVERY token (equal counts over all 16), and the share's
    output is off by bf16 matmul error only; the reference in bf16
    throughout misroutes rows (its counts differ) and its worst element
    is off by more than 0.3 standard deviations where ours stays
    under."""
    for seed in range(3):
        x, w = _moe_weights(seed, tokens=2048)
        w = {n: v.astype(jnp.bfloat16) for n, v in w.items()}
        x = x.astype(jnp.bfloat16)
        part = _held(w, 4, 4)

        def plain(cast):
            return ref.moe(cast(x), cast(part["gate_w"]),
                           cast(part["w_gate_up"]), cast(part["w_down"]),
                           cast(part["select_bias"]), 3, True, "sigmoid", 4,
                           2.448)

        want, want_counts, _ = plain(lambda a: a.astype(jnp.float32))
        y, counts = topk_moe(part, x, 3, norm_topk_prob=True,
                             scoring="sigmoid", expert_offset=4,
                             share_rows_bound=4096, routed_scale=2.448)
        assert y.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        error = float(jnp.abs(y.astype(jnp.float32) - want).max()
                      / want.std())
        assert error < 0.3, (seed, error)
        low, low_counts, _ = plain(lambda a: a)
        assert int(jnp.abs(low_counts - want_counts).sum()) > 0
        assert float(jnp.abs(low.astype(jnp.float32) - want).max()
                     / want.std()) > 0.3
