"""MiMo-V2-Flash on the normal path against its plain reference.

``models/mimo_v2.py`` (an ``mx.sym`` graph of ``RMSNorm``, ``RoPE`` with
a partial rotation, ``Attention`` with grouped heads, a window, a sink
and a value width of its own, ``TopKMoE`` with sigmoid scores, a
selection bias and a share of the experts) through
``Module.forward/backward`` and ``Module.fit``'s fused step, against
``models/mimo_v2_reference.py`` (plain float32 ``jax.numpy``: attention
by an explicit mask, a loop over the experts held) on seeded weights at
a tiny size: hidden 64, 4 query heads over 2 (window layers) and 1 (full
layers) key/value heads, query/key heads of 24 with 8 rotated, value
heads of 16, 16 experts top-4, window 8, T 32.

Tolerances. Both sides are float32 and compute the same mathematics;
only the order of summation differs (online softmax over tiles against
one softmax, grouped matmuls over sorted rows against dense masked
ones), so the float32 comparisons use rtol 1e-5 with an atol of a few
float32 ulps of the tensor's own scale (``_close``): a key one past the
window, a sink left out of the denominator, a head reading the wrong
key/value head or a bias leaking into the weights is off by orders of
magnitude more. The bf16 case measures its tolerance, see there.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import mimo_v2, mimo_v2_reference as ref
from mxnet_tpu.ops.kernels import (
    flash_attention, reference_attention)
from mxnet_tpu.ops.transformer import rope
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import topk_moe

T, WINDOW, BATCH = 32, 8, 2
CFG = dict(
    model_type="mimo_v2_flash", hidden_size=64, num_hidden_layers=4,
    hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
    num_attention_heads=4, num_key_value_heads=1,
    swa_num_attention_heads=4, swa_num_key_value_heads=2,
    head_dim=24, v_head_dim=16, swa_head_dim=24, swa_v_head_dim=16,
    partial_rotary_factor=0.334, rope_theta=5000000, swa_rope_theta=10000,
    sliding_window=WINDOW, sliding_window_size=WINDOW,
    attention_chunk_size=WINDOW, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False, attention_value_scale=0.707,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
    n_shared_experts=None, num_experts_per_tok=4, norm_topk_prob=True,
    scoring_func="sigmoid", n_group=1, topk_group=1,
    topk_method="noaux_tc", routed_scaling_factor=None,
    layernorm_epsilon=1e-5, vocab_size=512, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False,
    max_position_embeddings=T)
# one chip's share of the same model: 4 of the 16 experts from the 8th
# on, half the dense columns, a buffer of twice the expected rows
SHARE = dict(CFG, n_routed_experts=4, share=dict(
    experts_of=16, expert_offset=8, share_rows_bound=2 * BATCH * T,
    dense_columns_held=48))
EXPERT_LAYERS = sum(CFG["moe_layer_freq"])


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, batch=BATCH, sigma=0.08):
    """Seeded weights under the symbol's argument names: Normal(sigma),
    a unit embedding as the model states it, gammas near 1 and sinks and
    selection biases away from 0 (so that their part is tested)."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(batch, T), softmax_label=(batch, T))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = {"embed_weight": 1.0, "sink": 1.0, "bias": 0.05}.get(
            name if name == "embed_weight" else name.rsplit("_", 1)[-1],
            sigma)
        out[name] = (scale * rng.randn(*shape)
                     + name.endswith("_gamma")).astype(np.float32)
    return out


def _batch(seed, batch=BATCH):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, CFG["vocab_size"], (batch, T + 1))
    return tokens[:, :-1].astype(np.float32), tokens[:, 1:].astype(np.float32)


def _module(sym, params, batch=BATCH):
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (batch, T))],
             label_shapes=[("softmax_label", (batch, T))])
    mod.init_params(arg_params={k: mx.nd.array(v) for k, v in params.items()},
                    aux_params={})
    return mod


# -- the whole model, uncut and as a share -----------------------------------

@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_logits_loss_and_every_gradient_match_the_reference(cfg):
    sym = mimo_v2.from_config(cfg, seq_len=T)
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
        assert outs[1 + layer].shape == (16,)
        assert outs[1 + layer].sum() == BATCH * T * 4
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention: the
        # optimizer's rescale_grad = 1/batch makes it the mean)
        _close(got[name].asnumpy() / BATCH, want_g, name)
        if "select_bias" in name:
            assert not np.asarray(want_g).any()  # it moves the choice only
        elif "sink" in name:
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
    sym = mimo_v2.from_config(SHARE, seq_len=T)
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
    # no gradient and no rule moves the selection bias
    for name in params:
        if "select_bias" in name:
            np.testing.assert_array_equal(got[name].asnumpy(), params[name])


def test_the_model_states_its_own_initialisation():
    sym = mimo_v2.from_config(CFG, seq_len=T)
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH, T))],
             label_shapes=[("softmax_label", (BATCH, T))])
    mx.random.seed(5)
    mod.init_params(initializer=mx.init.Normal(sigma=0.02))
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert 0.9 < got["embed_weight"].std() < 1.1
    assert 0.015 < got["layer1_q_proj_weight"].std() < 0.025
    assert not got["layer1_attn_sink"].any()
    assert not got["layer1_moe_select_bias"].any()
    assert (got["final_norm_gamma"] == 1).all()
    assert "layer0_attn_sink" not in got  # a full layer has no sink


def test_from_config_refuses_what_it_does_not_implement():
    for key, value in [("n_shared_experts", 1), ("n_group", 2),
                       ("topk_method", "greedy"), ("attention_bias", True),
                       ("routed_scaling_factor", 2.5), ("hidden_act", "gelu"),
                       ("tie_word_embeddings", True),
                       ("attention_chunk_size", 64), ("swa_head_dim", 32),
                       ("scoring_func", "tanh"),
                       ("moe_layer_freq", [0, 1, 1])]:
        with pytest.raises(ValueError, match=key):
            mimo_v2.from_config(dict(CFG, **{key: value}), seq_len=T)


def test_olmoe_from_config_still_refuses_grouped_heads():
    """``Attention`` takes fewer key/value heads now; OLMoE's builder
    does not pass them on (its QK-norm is over the full hidden width),
    so it keeps refusing until it does."""
    from mxnet_tpu.models import olmoe

    cfg = dict(num_attention_heads=4, num_key_value_heads=2)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        olmoe.from_config(cfg, seq_len=T)


# -- Attention: window, sink, grouped heads, a value width of its own -------

def _qkv(seed, heads=4, kv_heads=2, d=24, dv=16, t=T):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(BATCH, t, heads, d), jnp.float32),
            jnp.asarray(rng.randn(BATCH, t, kv_heads, d), jnp.float32),
            jnp.asarray(rng.randn(BATCH, t, kv_heads, dv), jnp.float32),
            jnp.asarray(rng.randn(heads), jnp.float32),
            jnp.asarray(rng.randn(BATCH, t, heads, dv), jnp.float32))


def _tiled(q, k, v, window, sink):
    # 8 x 8 tiles: the band of a q tile crosses two k tiles, and most of
    # the grid's steps would be dead without the band's own inner extent
    return flash_attention(q, k, v, causal=True, window=window, sink=sink,
                           block_q=8, block_k=8, interpret=True)


def _materialised(q, k, v, window, sink):
    return reference_attention(q, k, v, causal=True, window=window,
                               sink=sink)


@pytest.mark.parametrize("path", [_tiled, _materialised],
                         ids=["flash_kernel", "materialised"])
@pytest.mark.parametrize("kv_heads,window,with_sink", [
    (2, WINDOW, True), (1, 0, False), (1, WINDOW, False), (2, 0, True)])
def test_attention_matches_the_reference(path, kv_heads, window, with_sink):
    """Forward, and dQ, dK, dV (a group's heads summed into their one
    key/value head) and the sink's gradient, for query/key heads of 24
    beside value heads of 16."""
    q, k, v, sink, cot = _qkv(0, kv_heads=kv_heads)
    sink = sink if with_sink else None

    def loss(fn):
        return lambda q, k, v, s: jnp.sum(fn(q, k, v, window, s) * cot)

    def plain(q, k, v, window, s):
        return ref.attention(q, k, v, window=window, sink=s)

    _close(path(q, k, v, window, sink), plain(q, k, v, window, sink), "out")
    argnums = (0, 1, 2, 3) if with_sink else (0, 1, 2)
    got = jax.grad(loss(path), argnums)(q, k, v, sink)
    want = jax.grad(loss(plain), argnums)(q, k, v, sink)
    for name, g, w in zip(("dq", "dk", "dv", "dsink"), got, want):
        assert g.shape == w.shape
        _close(g, w, name, ulps=32)


@pytest.mark.parametrize("path", [_tiled, _materialised],
                         ids=["flash_kernel", "materialised"])
def test_the_windows_edge_is_exact(path):
    """Equal scores and one-hot values: row i's output IS its
    probabilities, 1 / min(i + 1, window) on the keys i - window + 1 ..
    i and exactly 0 from i - j = window on (the published mask at 127 /
    128, scaled down to 7 / 8)."""
    q = jnp.zeros((1, T, 1, 8), jnp.float32)
    v = jnp.eye(T, dtype=jnp.float32).reshape(1, T, 1, T)
    out = np.asarray(path(q, q, v, WINDOW, None))[0, :, 0]   # [i, j]
    i, j = np.indices((T, T))
    inside = (j <= i) & (i - j < WINDOW)
    assert inside[20, 13] and not inside[20, 12]              # 7 in, 8 out
    assert (out[~inside] == 0).all()
    _close(out[inside], (1.0 / np.minimum(i + 1, WINDOW))[inside], "band")


def test_partial_rotation_matches_the_reference():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(BATCH, T, 4 * 24), jnp.float32)
    got = rope(x, 4, 10000.0, rotary_dim=8).reshape(BATCH, T, 4, 24)
    want = ref.rope(x.reshape(BATCH, T, 4, 24), 10000.0, 8)
    _close(got, want, "rope")
    # the 16 dimensions past the rotation pass through untouched
    np.testing.assert_array_equal(
        np.asarray(got[..., 8:]), np.asarray(x.reshape(BATCH, T, 4, 24)[..., 8:]))
    assert ref.rotary_dim({"partial_rotary_factor": 0.334}, 192) == 64


def test_attention_op_reports_the_offending_input_by_name():
    q = mx.sym.Variable("q")
    k = mx.sym.Variable("k")
    v = mx.sym.Variable("v")
    attn = mx.contrib.sym.Attention(q, k, v, num_heads=4, num_kv_heads=2)
    _, out, _ = attn.infer_shape(q=(2, T, 96), k=(2, T, 48), v=(2, T, 32))
    assert out == [(2, T, 64)]          # heads x the VALUE width
    with pytest.raises(Exception, match="key"):
        attn.infer_shape(q=(2, T, 96), k=(2, T, 40), v=(2, T, 32))
    with pytest.raises(Exception, match="value"):
        attn.infer_shape(q=(2, T, 96), k=(2, T, 48), v=(2, T + 1, 32))
    with pytest.raises(Exception, match="num_kv_heads"):
        mx.contrib.sym.Attention(q, k, v, num_heads=4, num_kv_heads=3) \
            .infer_shape(q=(2, T, 96), k=(2, T, 72), v=(2, T, 48))


# -- TopKMoE: sigmoid scores, the selection bias, a share of the experts ----

def _moe_weights(seed, d=64, experts=16, hidden=32, tokens=BATCH * T):
    rng = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (f32(rng.randn(tokens, d)),
            {"gate_w": f32(rng.randn(d, experts) * 0.3),
             "w_gate_up": f32(rng.randn(experts, d, 2 * hidden) * 0.1),
             "w_down": f32(rng.randn(experts, hidden, d) * 0.1),
             "select_bias": jnp.zeros(experts, jnp.float32)})


def _ref_moe(x, w, offset=0, top_k=4):
    return ref.moe(x, w["gate_w"], w["w_gate_up"], w["w_down"],
                   w["select_bias"], top_k, True, "sigmoid", offset)


def test_the_selection_bias_changes_the_choice_not_the_weights():
    x, w = _moe_weights(0)
    plain, plain_counts, _ = _ref_moe(x, w)
    # expert 5 gets every token; its weight is still its own sigmoid score
    biased = dict(w, select_bias=w["select_bias"].at[5].set(10.0))
    want, want_counts, _ = _ref_moe(x, biased)
    assert int(want_counts[5]) == x.shape[0] > int(plain_counts[5])

    def run(params):
        return topk_moe(params, x, 4, norm_topk_prob=True,
                        scoring="sigmoid")

    got, got_counts = run(biased)
    np.testing.assert_array_equal(np.asarray(got_counts),
                                  np.asarray(want_counts))
    _close(got, want, "biased choice")
    # were the bias part of the weights, expert 5 would carry nearly all
    # of every token; it carries its score's share
    score = jax.nn.sigmoid(x @ w["gate_w"])
    assert float(jnp.abs(got - plain).max()) > 1e-3
    assert float(score[:, 5].mean()) < 0.9
    # and it has no gradient
    grad = jax.grad(lambda p: jnp.sum(run(p)[0] ** 2))(biased)
    assert not np.asarray(grad["select_bias"]).any()
    assert np.asarray(grad["gate_w"]).any()


@pytest.mark.parametrize("held", [4, 8])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """What all 16 / held shares give, each routing over all 16 and
    computing its own experts' part, sums to the uncut reference's
    layer — forward, and the gradients of the router and of the input
    (which every share feeds); each share's expert gradients are the
    uncut layer's for those experts."""
    x, w = _moe_weights(1)
    cot = jnp.asarray(np.random.RandomState(2).randn(*x.shape), jnp.float32)
    want, want_counts, _ = _ref_moe(x, w)
    want_g = jax.grad(lambda x, w: jnp.sum(_ref_moe(x, w)[0] * cot),
                      (0, 1))(x, w)

    def share(x, w, offset):
        part = dict(w, w_gate_up=w["w_gate_up"][offset:offset + held],
                    w_down=w["w_down"][offset:offset + held])
        return topk_moe(part, x, 4, norm_topk_prob=True, scoring="sigmoid",
                        expert_offset=offset, share_rows_bound=x.shape[0] * 4)

    total = 0
    dx, dgate = 0, 0
    for offset in range(0, 16, held):
        y, counts = share(x, w, offset)
        ref_part, _, _ = ref.moe(
            x, w["gate_w"], w["w_gate_up"][offset:offset + held],
            w["w_down"][offset:offset + held], w["select_bias"], 4, True,
            "sigmoid", offset)
        _close(y, ref_part, "share at %d" % offset)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        total = total + y
        gx, gw = jax.grad(
            lambda x, w: jnp.sum(share(x, w, offset)[0] * cot), (0, 1))(x, w)
        dx, dgate = dx + gx, dgate + gw["gate_w"]
        for name in ("w_gate_up", "w_down"):
            _close(gw[name][offset:offset + held],
                   want_g[1][name][offset:offset + held], name, ulps=32)
            assert not np.asarray(gw[name][:offset]).any()
    _close(total, want, "sum of the shares", ulps=32)
    _close(dx, want_g[0], "dx", ulps=32)
    _close(dgate, want_g[1]["gate_w"], "d router", ulps=32)


def test_a_bound_too_small_is_reported_by_the_counts():
    x, w = _moe_weights(4)
    part = dict(w, w_gate_up=w["w_gate_up"][:4], w_down=w["w_down"][:4])
    full, counts = topk_moe(part, x, 4, norm_topk_prob=True,
                            scoring="sigmoid", share_rows_bound=x.shape[0] * 4)
    here = int(np.asarray(counts)[:4].sum())
    bound = here // 2
    cut, cut_counts = topk_moe(part, x, 4, norm_topk_prob=True,
                               scoring="sigmoid", share_rows_bound=bound)
    # the counts are the routing's, whatever was computed: a caller
    # compares the held experts' rows with its bound
    np.testing.assert_array_equal(np.asarray(cut_counts), np.asarray(counts))
    assert int(np.asarray(cut_counts).sum()) == x.shape[0] * 4
    assert here > bound
    # the rows of the first tokens fit and are computed; later ones are not
    assert np.isfinite(np.asarray(cut)).all()
    assert float(jnp.abs(cut - full).max()) > 1e-3
    first = int(np.argmax(np.cumsum(np.isin(
        np.asarray(jax.lax.top_k(jax.nn.sigmoid(x @ w["gate_w"]), 4)[1]),
        np.arange(4)).sum(axis=1)) > bound))
    _close(cut[:first], full[:first], "tokens inside the bound")


def test_topk_moe_symbol_op_checks_its_share():
    data = mx.sym.Variable("data")

    def infer(**attrs):
        op = mx.contrib.sym.TopKMoE(data, num_experts=16, num_hidden=32,
                                    top_k=4, name="moe", **attrs)
        return op.list_arguments(), op.infer_shape(data=(64, 64))

    names, (ins, outs, _) = infer(experts_held=4, expert_offset=8,
                                  share_rows_bound=128, scoring="sigmoid",
                                  with_select_bias=True)
    assert names[-1] == "moe_select_bias"
    assert ins[1:] == [(64, 16), (4, 64, 64), (4, 32, 64), (16,)]
    assert outs == [(64, 64), (16,)]
    for bad, what in [(dict(experts_held=4), "share_rows_bound"),
                      (dict(experts_held=4, expert_offset=13,
                            share_rows_bound=64), "expert_offset"),
                      (dict(scoring="tanh"), "scoring")]:
        with pytest.raises(Exception, match=what):
            infer(**bad)


# -- one precision below ------------------------------------------------------

def test_bf16_share_keeps_its_sigmoid_router_in_float32():
    """bf16 activations and weights, router in float32: on the same
    bf16-rounded inputs the float32 reference takes the same routing
    decision for EVERY token (equal counts over all 16), and the share's
    output is off by bf16 matmul error only. The reference computed in
    bf16 throughout (the nearest precision below: a bf16 router, bf16
    sigmoids) misroutes 58-68 of 8192 rows: its counts differ, and its
    worst element is off by 3.7-6.5 standard deviations of the output
    where ours is off by 0.060-0.099 (seeds 0..4). The limit 0.3 lies
    between, a factor of three from ours."""
    for seed in range(3):
        x, w = _moe_weights(seed, tokens=2048)
        w = {n: v.astype(jnp.bfloat16) for n, v in w.items()}
        x = x.astype(jnp.bfloat16)
        part = dict(w, w_gate_up=w["w_gate_up"][4:8], w_down=w["w_down"][4:8])

        def f32(a):
            return a.astype(jnp.float32)

        def plain(cast, w):
            return ref.moe(cast(x), cast(w["gate_w"]), cast(w["w_gate_up"]),
                           cast(w["w_down"]), cast(w["select_bias"]), 4,
                           True, "sigmoid", 4)

        want, want_counts, _ = plain(f32, part)
        y, counts = topk_moe(part, x, 4, norm_topk_prob=True,
                             scoring="sigmoid", expert_offset=4,
                             share_rows_bound=4096)
        assert y.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        error = float(jnp.abs(f32(y) - want).max() / want.std())
        assert error < 0.3, (seed, error)
        low, low_counts, _ = plain(lambda a: a, part)
        assert int(jnp.abs(low_counts - want_counts).sum()) > 0
        assert float(jnp.abs(f32(low) - want).max() / want.std()) > 0.3


def _bf16_logit_error(seed, drop_expert=False):
    """Per-token largest |logit difference| to the float32 reference, in
    standard deviations of the logits, as its 90th percentile over the
    tokens whose routing is not a near-tie. The bf16 symbol runs on the
    reference's weights rounded to bf16; ``drop_expert`` zeroes the
    busiest held expert of one layer."""
    sym = mimo_v2.from_config(SHARE, seq_len=T, dtype="bfloat16")
    params = _params(sym, seed)
    rounded = {k: np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32))
               for k, v in params.items()}
    tokens, _ = _batch(seed + 100)
    out = ref.forward(rounded, tokens, SHARE)
    want = np.asarray(out["logits"]).reshape(-1, CFG["vocab_size"])
    clear = np.asarray(out["router_gap"]).min(axis=0) > 1e-3
    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    args = {k: mx.nd.array(v).astype("bfloat16") for k, v in rounded.items()}
    if drop_expert:
        held = np.asarray(out["expert_counts"][1])[8:12]
        args["layer2_moe_down_weight"][int(np.argmax(held))] = 0
    mod.init_params(arg_params=args, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    got = mod.get_outputs()[0].asnumpy().reshape(want.shape)
    per_token = (np.abs(got - want).max(axis=1) / want.std())[clear]
    return float(np.percentile(per_token, 90))


def test_bf16_symbol_is_close_and_a_dropped_expert_is_not():
    """Measured here over seeds 0..5 (90th percentile): the bf16 symbol
    0.020-0.022; with the busiest held expert of one layer zeroed
    0.072-0.096. The limit 0.045 lies between the two, a factor of two
    from the one and 1.6 from the other."""
    limit = 0.045
    for seed in range(3):
        ours = _bf16_logit_error(seed)
        dropped = _bf16_logit_error(seed, drop_expert=True)
        assert ours < limit < dropped, (seed, ours, dropped)
