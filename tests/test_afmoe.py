"""Trinity-Mini (``model_type`` afmoe) on the normal path against its
plain reference.

``Attention(with_gate=True)`` against ``jax.numpy``; ``models/afmoe.py``
(an ``mx.sym`` graph of gated attention under per-head norms, sliding and
full layers three to one, four norms a block, a shared and sigmoid-routed
experts, a muP multiplier on the embedding) through
``Module.forward/backward`` and ``Module.fit``'s fused step against
``models/afmoe_reference.py`` (plain float32 ``jax.numpy``: attention by
an explicit causal / window mask, a loop over the experts held) on seeded
weights at a tiny size: hidden 48, 5 layers (sliding, sliding, sliding,
full, sliding; the first dense), 4 query heads on 2 key/value heads of
16, a window of 12, 16 experts top-3 of width 32, 1 shared, T 40.

Tolerances: both sides are float32 and only the order of summation
differs, so rtol 1e-5 with an atol of a few float32 ulps of the tensor's
own scale (``_close``); ``ulps`` is raised for gradients, which are long
sums of such terms through four norms a block.
"""
import functools
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import afmoe, afmoe_reference as ref
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import attention as attention_ops
from mxnet_tpu.ops.kernels import flash_tiles, reference_attention
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import topk_moe

T, BATCH = 40, 2
HEADS, KV, D, WINDOW = 4, 2, 16, 12
KINDS = [afmoe.SLIDING] * 3 + [afmoe.FULL, afmoe.SLIDING]
CFG = dict(
    model_type="afmoe", hidden_size=48, num_hidden_layers=5,
    num_dense_layers=1, layer_types=KINDS, global_attn_every_n_layers=4,
    num_attention_heads=HEADS, num_key_value_heads=KV, head_dim=D,
    rope_theta=10000, rope_scaling=None, sliding_window=WINDOW,
    intermediate_size=96, moe_intermediate_size=32, num_experts=16,
    num_shared_experts=1, num_experts_per_tok=3, route_norm=True,
    route_scale=2.826, score_func="sigmoid", n_group=1, topk_group=1,
    num_expert_groups=1, num_limited_groups=1, use_grouped_mm=True,
    load_balance_coeff=0.001, mup_enabled=True, rms_norm_eps=1e-5,
    vocab_size=512, hidden_act="silu", tie_word_embeddings=False,
    max_position_embeddings=T)
# one chip's share of the same model: 4 of the 16 experts from the 8th
# on, a buffer that holds every row
SHARE = dict(CFG, num_experts=4, share=dict(
    experts_of=16, expert_offset=8, share_rows_bound=BATCH * T * 3))
EXPERT_LAYERS = 4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join(ROOT, "bench", "configs", "trinity_mini.json")


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


# -- the gate on the attention op --------------------------------------------

def _attention_inputs(seed, dtype):
    rng = np.random.RandomState(seed)
    q, g = (jnp.asarray(rng.randn(BATCH, T, HEADS * D), dtype)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(BATCH, T, KV * D), dtype)
            for _ in range(2))
    return q, k, v, g


def _op(window=0, **more):
    attrs = dict(num_heads=HEADS, num_kv_heads=KV, causal=True,
                 window=window, **more)
    return lambda *ins: attention_ops._attention(attrs, list(ins), True)[0]


def _plain(window, dtype):
    """``jax.numpy`` alone: materialised scores under the mask, then the
    gate in float32 and one rounding."""
    def fn(q, k, v, g):
        def split(x, n):
            return x.reshape(BATCH, T, n, D)
        out = reference_attention(split(q, HEADS), split(k, KV),
                                  split(v, KV), causal=True, window=window)
        out = out.reshape(BATCH, T, HEADS * D)
        return (out.astype(jnp.float32)
                * jax.nn.sigmoid(g.astype(jnp.float32))).astype(dtype)
    return fn


def _out_and_grads(fn, ins, seed=9):
    out = fn(*ins)
    cot = jnp.asarray(np.random.RandomState(seed).randn(*out.shape),
                      jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot),
                     argnums=tuple(range(len(ins))))(*ins)
    return out, grads


@pytest.mark.parametrize("window", [0, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_gated_call_matches_jax_numpy(dtype, window):
    """Forward and every gradient, the gate's among them; the call site
    is counted once, with its heads and value width."""
    ins = _attention_inputs(3, dtype)
    telemetry.reset()
    telemetry.enable()
    try:
        got, got_grads = _out_and_grads(_op(window, with_gate=True), ins)
        sites = telemetry.REGISTRY.get("attention.gated_lowerings")
        assert sites.value(heads=HEADS, dv=D) >= 1
    finally:
        telemetry.disable()
        telemetry.reset()
    want, want_grads = _out_and_grads(_plain(window, dtype), ins)
    assert got.dtype == dtype and got.shape == (BATCH, T, HEADS * D)
    exact = dtype == jnp.float32
    for what, g, w in [("out", got, want)] + [
            ("gradient %d" % i, g, w)
            for i, (g, w) in enumerate(zip(got_grads, want_grads))]:
        if exact:
            _close(g, w, what, ulps=16)
        else:  # one bf16 rounding of values of the tensor's own scale
            scale = float(jnp.abs(w.astype(jnp.float32)).max())
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                atol=2 ** -7 * scale, rtol=0, err_msg=what)
    assert float(jnp.abs(got_grads[3].astype(jnp.float32)).max()) > 1e-3


def _parent_attention(attrs, ins):
    """``_attention`` as the parent commit had it, word for word."""
    from mxnet_tpu.ops.kernels import attention

    q, k, v = ins[:3]
    heads, kv_heads = int(attrs["num_heads"]), attention_ops._kv_heads(attrs)
    window = int(attrs.get("window", 0))
    b, t, _ = q.shape

    def split(x, n):
        return x.reshape(b, t, n, x.shape[2] // n)

    with jax.named_scope("window" if window else "full"):
        out = attention(split(q, heads), split(k, kv_heads),
                        split(v, kv_heads),
                        causal=bool(attrs.get("causal", True)),
                        window=window,
                        sink=ins[3] if bool(attrs.get("with_sink", False))
                        else None)
    return [out.reshape(b, t, -1)]


@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
def test_the_gateless_call_traces_what_the_parent_traced(with_sink):
    """Without a gate the op's program is the parent's: the same jaxpr,
    text for text, values and gradients bit for bit, and no gated call
    site is counted."""
    q, k, v, _ = _attention_inputs(5, jnp.bfloat16)
    ins = (q, k, v) + ((jnp.asarray(np.random.RandomState(6).randn(HEADS),
                                    jnp.float32),) if with_sink else ())
    attrs = dict(num_heads=HEADS, num_kv_heads=KV, causal=True,
                 window=WINDOW, with_sink=with_sink)

    def ours(*a):
        return attention_ops._attention(attrs, list(a), True)[0]

    def parents(*a):
        return _parent_attention(attrs, list(a))[0]

    assert str(jax.make_jaxpr(ours)(*ins)) == str(
        jax.make_jaxpr(parents)(*ins))
    telemetry.reset()
    telemetry.enable()
    try:
        got = _out_and_grads(ours, ins)
        assert telemetry.total("attention.gated_lowerings") == 0
    finally:
        telemetry.disable()
        telemetry.reset()
    want = _out_and_grads(parents, ins)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_the_gate_is_an_input_the_symbol_names_and_shapes():
    q, k, v, g = (mx.sym.Variable(n) for n in "qkvg")
    node = mx.contrib.sym.Attention(q, k, v, with_gate=True, gate=g,
                                    num_heads=HEADS, num_kv_heads=KV,
                                    name="a")
    assert node.list_arguments() == ["q", "k", "v", "g"]
    shapes, out, _ = node.infer_shape(q=(BATCH, T, HEADS * D),
                                      k=(BATCH, T, KV * D),
                                      v=(BATCH, T, KV * D))
    assert shapes[3] == (BATCH, T, HEADS * D) == out[0]
    both = mx.contrib.sym.Attention(
        q, k, v, with_sink=True, sink=mx.sym.Variable("s"), with_gate=True,
        gate=g, num_heads=HEADS, num_kv_heads=KV, name="b")
    assert both.list_arguments() == ["q", "k", "v", "s", "g"]
    plain = mx.contrib.sym.Attention(q, k, v, num_heads=HEADS,
                                     num_kv_heads=KV, name="c")
    assert plain.list_arguments() == ["q", "k", "v"]


# -- the whole model, uncut and as a share -----------------------------------

def _params(sym, seed, sigma=0.08, t=T):
    """Seeded weights under the symbol's argument names: Normal(sigma),
    the embedding at the rule the model states (8 / sqrt(hidden)), gammas
    near 1, selection biases away from 0 (so that their part is
    tested)."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(BATCH, t), softmax_label=(BATCH, t))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = {"embed_weight": afmoe.STREAM_RMS
                 * CFG["hidden_size"] ** -0.5,
                 "bias": 0.05}.get(
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


@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_logits_loss_and_every_gradient_match_the_reference(cfg):
    sym = afmoe.from_config(cfg, seq_len=T)
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
                "attn_gate_proj", "q_norm", "k_norm", "post_norm", "shared")):
            assert np.abs(np.asarray(want_g)).max() > 1e-7, name

    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    _close(logits, want["logits"], "logits", ulps=16)


def test_the_flash_path_matches_the_reference_over_a_window():
    """T 160 takes the attention dispatch's flash branch (its
    ``jax.numpy`` arithmetic off the TPU) under a window of 40: loss and
    logits against the reference's explicit mask."""
    t = 160
    cfg = dict(CFG, sliding_window=40)
    sym = afmoe.from_config(cfg, seq_len=t)
    params = _params(sym, 7, t=t)
    tokens, labels = _batch(8, t=t)
    want = ref.forward(params, tokens, cfg, labels=labels)
    telemetry.reset()
    telemetry.enable()
    try:
        mod = _module(sym, params, t=t)
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                    label=[mx.nd.array(labels)]),
                    is_train=False)
        _close(mod.get_outputs()[0].asnumpy(), want["per_sequence"],
               "per-sequence loss", ulps=16)
        flash = telemetry.REGISTRY.get("attention.flash_lowerings")
        for window, sites in ((40, 4), (0, 1)):
            bq, bk = flash_tiles(t, D, jnp.float32, window)
            assert flash.value(operands="f32", block_q=bq, block_k=bk,
                               window=window, kv_heads=KV, dv=D) == sites
        assert telemetry.REGISTRY.get("attention.gated_lowerings").value(
            heads=HEADS, dv=D) == 5
    finally:
        telemetry.disable()
        telemetry.reset()


def test_fused_fit_trains_the_share_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep — on the share: the first steps follow the
    reference's own SGD with momentum, and the loss falls."""
    sym = afmoe.from_config(SHARE, seq_len=T)
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
        sym = afmoe.from_config(SHARE, seq_len=T)
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
        # one per layer's call site, nothing per step
        gated = telemetry.REGISTRY.get("attention.gated_lowerings")
        assert gated.value(heads=HEADS, dv=D) == 5
        assert telemetry.total("attention.gated_lowerings") == 5
        share = telemetry.REGISTRY.get("moe.share_lowerings")
        assert share.value(held=4, of=16, bound=BATCH * T * 3,
                           sum="segment_product", scale=2.826,
                           renorm_eps=1e-20) == 4
    finally:
        telemetry.disable()
        telemetry.reset()
    got, _ = mod.get_params()
    got = {k: v.asnumpy() for k, v in got.items()}
    # the scaled stream starts at an rms of 8: sqrt(48) * Normal(8/sqrt(48))
    assert abs(got["embed_weight"].std() * 48 ** 0.5 / afmoe.STREAM_RMS
               - 1.0) < 0.05
    assert afmoe.STREAM_RMS == 8.0
    assert abs(got["layer1_q_proj_weight"].std() - 0.02) < 0.004
    assert abs(got["layer1_attn_gate_proj_weight"].std() - 0.02) < 0.004
    for name, value in got.items():
        if name.endswith("select_bias"):
            np.testing.assert_array_equal(value, 0.0)
        if name.endswith("_gamma"):
            np.testing.assert_array_equal(value, 1.0)
    assert not [n for n in got if n.endswith("_proj_bias")]
    assert got["layer0_q_norm_gamma"].shape == (D,)
    assert got["layer0_k_norm_gamma"].shape == (D,)
    assert got["layer0_attn_gate_proj_weight"].shape == (HEADS * D, 48)
    assert got["layer0_k_proj_weight"].shape == (KV * D, 48)
    for i in range(5):  # four norms a block over the stream
        for norm in ("attn_norm", "attn_post_norm", "ffn_norm",
                     "ffn_post_norm"):
            assert got["layer%d_%s_gamma" % (i, norm)].shape == (48,)


def _nodes(sym):
    return {n["name"]: n for n in json.loads(sym.tojson())["nodes"]}


def test_a_full_layer_rotates_nothing():
    nodes = _nodes(afmoe.from_config(CFG, seq_len=T))
    for i, kind in enumerate(KINDS):
        for name in ("q", "k"):
            assert (("layer%d_%s_rope" % (i, name)) in nodes) is (
                kind == afmoe.SLIDING)
        window = str(nodes["layer%d_attn" % i]["attr"].get("window", "0"))
        assert window == (str(WINDOW) if kind == afmoe.SLIDING else "0")
        assert str(nodes["layer%d_attn" % i]["attr"]["with_gate"]) in (
            "True", "1")
    # the reference alike: a full layer's result does not change with
    # rope_theta, a sliding layer's does
    rng = np.random.RandomState(11)
    q, g = (jnp.asarray(rng.randn(1, T, HEADS * D), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, T, KV * D), jnp.float32)
            for _ in range(2))
    ones = jnp.ones((D,), jnp.float32)

    def run(kind, theta):
        return ref.gated_attention(q, k, v, g, ones, ones,
                                   dict(CFG, rope_theta=theta), kind)

    np.testing.assert_array_equal(np.asarray(run(afmoe.FULL, 1e4)),
                                  np.asarray(run(afmoe.FULL, 5e5)))
    assert float(jnp.abs(run(afmoe.SLIDING, 1e4)
                         - run(afmoe.SLIDING, 5e5)).max()) > 1e-3


def test_the_mup_node_scales_the_embedding_by_sqrt_hidden():
    sym = afmoe.from_config(CFG, seq_len=T)
    node = _nodes(sym)["embed_scale"]
    assert node["op"] == "_contrib_ScaledSum"
    assert float(node["attr"]["scales"].strip("()[], ")) == pytest.approx(
        48 ** 0.5)
    assert "embed_scale" not in _nodes(
        afmoe.from_config(dict(CFG, mup_enabled=False), seq_len=T))
    params = _params(sym, 13)
    tokens, _ = _batch(14)
    stream = sym.get_internals()["embed_scale_output"]
    mod = mx.mod.Module(stream, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={"embed_weight": mx.nd.array(
        params["embed_weight"])}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    want = 48 ** 0.5 * params["embed_weight"][tokens.astype(int).ravel()]
    _close(mod.get_outputs()[0].asnumpy(), want, "scaled embedding")


# -- the share adds up -------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """THE SHARE-SUM TEST. One expert layer of the model at a 32-wide
    router: the residual, the post-norm of the shared expert and the
    routed ones. Eight shares of four experts each route over all 32 and
    compute their own experts' part; the shared expert is what every
    chip computes alike and counts once. The sum, under the sub-layer's
    output norm, is the uncut reference's layer."""
    rng = np.random.RandomState(5)
    d, hidden, experts, top_k, n = 48, 32, 32, 3, BATCH * T
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(rng.randn(n, d))
    gamma = f32(1 + 0.1 * rng.randn(d))
    w = {"gate_w": f32(rng.randn(d, experts)),
         "w_gate_up": f32(0.2 * rng.randn(experts, d, 2 * hidden)),
         "w_down": f32(0.2 * rng.randn(experts, hidden, d)),
         "select_bias": f32(0.05 * rng.randn(experts))}
    shared = [f32(0.1 * rng.randn(*s))
              for s in ((hidden, d), (hidden, d), (d, hidden))]
    whole, counts, _ = ref.moe(
        x, w["gate_w"], w["w_gate_up"], w["w_down"], w["select_bias"],
        top_k, 0, 2.826)
    want = x + ref.rms_norm(ref.swiglu(x, *shared) + whole, gamma, 1e-5)

    total = ref.swiglu(x, *shared)          # counted once
    for offset in range(0, experts, 4):
        held = dict(w, w_gate_up=w["w_gate_up"][offset:offset + 4],
                    w_down=w["w_down"][offset:offset + 4])
        part, part_counts = topk_moe(
            held, x, top_k, norm_topk_prob=True, scoring="sigmoid",
            expert_offset=offset, share_rows_bound=n * top_k,
            routed_scale=2.826, renorm_eps=1e-20)
        np.testing.assert_array_equal(np.asarray(part_counts),
                                      np.asarray(counts))
        mine, _, _ = ref.moe(
            x, w["gate_w"], held["w_gate_up"], held["w_down"],
            w["select_bias"], top_k, offset, 2.826)
        _close(part, mine, "share at %d" % offset)
        total = total + part
    _close(x + ref.rms_norm(total, gamma, 1e-5), want,
           "sum of the eight shares", ulps=32)
    # adding the shared expert in every share would count it 8 times
    assert float(jnp.abs(ref.swiglu(x, *shared)).max()) > 1e-2


# -- from_config on the published keys ---------------------------------------

def _published():
    with open(FILE) as f:
        held = json.load(f)
    return dict(held, **{k: held["published"][k]
                         for k in ("num_hidden_layers", "num_dense_layers",
                                   "layer_types", "num_experts",
                                   "vocab_size")})


def test_from_config_reads_the_published_keys():
    cfg = _published()
    assert len(cfg["layer_types"]) == 32
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == afmoe.FULL] == list(range(3, 32, 4))
    sym = afmoe.from_config(dict(cfg, share={}), seq_len=64)
    names = sym.list_arguments()
    assert "layer1_gate_proj_weight" in names       # the second dense layer
    assert "layer2_moe_gate_weight" in names
    assert "layer1_moe_gate_weight" not in names
    shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    shape = dict(zip(names, shapes))
    assert shape["layer2_moe_gate_weight"] == (2048, 128)
    assert shape["layer2_moe_gate_up_weight"] == (128, 2048, 2048)
    assert shape["layer2_shared_gate_proj_weight"] == (1024, 2048)
    assert shape["layer0_gate_proj_weight"] == (6144, 2048)
    assert shape["layer0_q_proj_weight"] == (4096, 2048)
    assert shape["layer0_attn_gate_proj_weight"] == (4096, 2048)
    assert shape["layer0_k_proj_weight"] == (512, 2048)
    assert shape["layer0_q_norm_gamma"] == (128,)
    assert shape["lm_head_weight"] == (200192, 2048)
    assert shape["embed_weight"] == (200192, 2048)
    nodes = _nodes(sym)
    assert "layer3_q_rope" not in nodes and "layer2_q_rope" in nodes
    assert str(nodes["layer2_attn"]["attr"]["window"]) == "2048"
    assert float(nodes["layer2_moe"]["attr"]["routed_scale"]) == 2.826
    # the defaults of get_symbol are the published model
    assert afmoe.get_symbol(seq_len=64).list_arguments() == names


def test_the_file_is_the_share_the_cell_trains():
    with open(FILE) as f:
        cfg = json.load(f)
    sym = afmoe.from_config(cfg, **cfg["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 8192), softmax_label=(1, 8192))
    shape = dict(zip(sym.list_arguments(), shapes))
    assert shape["layer1_moe_gate_weight"] == (2048, 128)   # all 128
    assert shape["layer1_moe_gate_up_weight"] == (16, 2048, 2048)
    assert shape["lm_head_weight"] == (25024, 2048)
    assert "layer0_gate_proj_weight" in shape
    assert "layer1_gate_proj_weight" not in shape
    total = sum(int(np.prod(s)) for n, s in shape.items()
                if n not in ("data", "softmax_label"))
    assert total == 705474304


@pytest.mark.parametrize("change,match", [
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(n_group=2), "n_group"),
    (dict(topk_group=2), "topk_group"),
    (dict(num_expert_groups=4), "num_expert_groups"),
    (dict(num_limited_groups=2), "num_limited_groups"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(score_func="tanh"), "score_func"),
    (dict(layer_types=KINDS[:4]), "num_hidden_layers"),
    (dict(layer_types=KINDS[:4] + ["chunked_attention"],
          global_attn_every_n_layers=None), "chunked_attention"),
    (dict(global_attn_every_n_layers=3), "global_attn_every_n_layers"),
], ids=["scaled_rope", "n_group", "topk_group", "expert_groups",
        "limited_groups", "activation", "tied", "attention_bias",
        "router_act", "layer_count", "layer_kind", "full_every"])
def test_from_config_refuses_what_it_cannot_honour(change, match):
    with pytest.raises(ValueError, match=match):
        afmoe.from_config(dict(CFG, **change), seq_len=T)


def test_the_keys_listed_as_unread_are_read_by_nothing():
    def graph(cfg):  # auto-named nodes count up from one symbol to the next
        return re.sub(r'"([a-z_]*[a-z_])\d+"', r'"\1"',
                      afmoe.from_config(cfg, seq_len=T).tojson())

    base = graph(CFG)
    moved = dict(CFG, use_grouped_mm=False, load_balance_coeff=0.5,
                 max_position_embeddings=1 << 20)
    assert set(afmoe.ASSUMED_UNREAD) == {
        "use_grouped_mm", "load_balance_coeff", "max_position_embeddings"}
    assert graph(moved) == base
    assert graph(dict(CFG, rms_norm_eps=1e-6)) != base
    assert graph(dict(CFG, route_scale=1.0)) != base


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(ROOT, "bench", "reference",
                           "trinity_mini.py")) as ours, \
            open(ref.__file__) as program:
        assert ours.read() == program.read()


def test_the_reference_one_precision_below_is_another_result():
    """``dtype=bfloat16`` is the same mathematics in bf16 throughout: it
    must differ from float32 by far more than the system does."""
    sym = afmoe.from_config(SHARE, seq_len=T)
    params = _params(sym, 15)
    tokens, labels = _batch(16)
    want = ref.forward(params, tokens, SHARE, labels=labels)
    below = ref.forward(params, tokens, SHARE, labels=labels,
                        dtype="bfloat16")
    assert below["logits"].dtype == jnp.bfloat16
    error = float(jnp.abs(below["logits"].astype(jnp.float32)
                          - want["logits"]).max() / want["logits"].std())
    assert error > 1e-2


# -- one test an ``assumed`` entry of the configuration's file ---------------

@functools.lru_cache(maxsize=None)
def _published_symbol():
    return afmoe.get_symbol(seq_len=64)


@functools.lru_cache(maxsize=None)
def _published_nodes():
    return _nodes(_published_symbol())


def _attn_node(attr, layer=0):
    return str(_published_nodes()["layer%d_attn" % layer]["attr"].get(attr))


def _source(fn):
    import inspect
    return inspect.getsource(fn)


ASSUMED = {
    "factory": lambda text: "from_config(this file, **kwargs)" in text,
    "gate": lambda text: (
        "sigmoid" in text and "float32" in text and "BEFORE o_proj" in text
        and _attn_node("with_gate") in ("True", "1")
        and "jax.nn.sigmoid(gate.astype(jnp.float32))" in _source(
            tr.gate_output)),
    "head_norms": lambda text: (
        "BEFORE the rotation" in text and "one gamma of 128" in text
        and _published_symbol().infer_shape(
            data=(1, 64), softmax_label=(1, 64))[0][
            _published_symbol().list_arguments().index(
                "layer0_q_norm_gamma")] == (128,)),
    "rotation": lambda text: (
        "NOTHING is rotated" in text and "all 128 dimensions" in text
        and "layer3_q_rope" not in _published_nodes()
        and "layer0_q_rope" in _published_nodes()),
    "window": lambda text: (
        "i - j < 2048" in text and _attn_node("window") == "2048"
        and _attn_node("window", 3) in ("0", "None")),
    "block": lambda text: (
        "h += RMSNorm(attention(RMSNorm(h))); h += RMSNorm(ffn(RMSNorm(h)))"
        in text and "layer0_attn_post_norm" in _published_nodes()),
    "mup": lambda text: (
        "sqrt(2048)" in text and "embed_scale" in _published_nodes()),
    "embedding": lambda text: (
        "Normal(8/sqrt(2048))" in text and "10.537" in text
        and afmoe.STREAM_RMS == 8.0),
    "router": lambda text: (
        "sum + 1e-20" in text and "2.826" in text and "zeros" in text
        and float(_published_nodes()["layer2_moe"][
            "attr"]["renorm_eps"]) == 1e-20),
    "shared_experts": lambda text: (
        "moe_intermediate_size * num_shared_experts" in text
        and "layer2_shared_gate_proj" in _published_nodes()),
    "unread": lambda text: all(k in text for k in afmoe.ASSUMED_UNREAD + (
        "n_group", "topk_group", "num_expert_groups", "num_limited_groups",
        "global_attn_every_n_layers")),
    "weights": lambda text: "Normal(0.02)" in text and "gammas 1" in text,
    "dtype": lambda text: "float32" in text and text.startswith("bfloat16"),
    "optimizer": lambda text: "SGD momentum 0.9" in text,
    "objective": lambda text: "no auxiliary loss" in text
    and "load_balance_coeff" in text,
    "share_rows_bound": lambda text: "16384" in text or "16,384" in text
    or "24576" in text or "24,576" in text,
    "input_shape": lambda text: "[1, 1, 8192]" in text,
}


@pytest.mark.parametrize("entry", sorted(ASSUMED))
def test_an_assumed_entry_says_what_the_program_does(entry):
    """Each assumption of ``bench/configs/trinity_mini.json`` is one
    entry, and it fails here if the file or the program moves."""
    with open(FILE) as f:
        assumed = json.load(f)["assumed"]
    assert ASSUMED[entry](assumed[entry]), assumed[entry]


def test_every_assumed_entry_has_its_test():
    with open(FILE) as f:
        assert set(json.load(f)["assumed"]) == set(ASSUMED)
