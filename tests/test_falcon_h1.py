"""Falcon-H1 on the normal path against its plain reference.

``models/falcon_h1.py`` (an ``mx.sym`` graph whose every block runs
``Mamba2`` between its two projections AND ``Attention`` over rotated
grouped heads on ONE normed input, scales and sums them in one
``ScaledSum`` node, then a dense SwiGLU, all under fourteen fixed
multipliers) through ``Module.forward/backward`` and ``Module.fit``'s
fused step, against ``models/falcon_h1_reference.py`` (plain float32
``jax.numpy``: the state-space layer as the token-by-token recurrence
with the multipliers on the projection as published, the convolution a
loop over taps, attention by an explicit mask) on seeded weights at a
tiny size that keeps the cell's ratios: the state twice the head (16 on
8), heads that share one group's B and C, five query heads a key/value
head, and fourteen multipliers that all differ from each other and from
1 (the published ``attention_in_multiplier`` is 1 and would hide
itself).

Tolerances as in ``tests/test_nemotron_h.py``: both sides are float32
and only the order of summation differs, so rtol 1e-5 with an atol of a
few float32 ulps of the tensor's own scale (``_close``): 8 for losses,
64 for logits (two norms and nine products a layer), 256 for gradients
(long sums over tokens through the scan, the softmax and three norms a
layer; measured 2-40 at these shapes). A moved or dropped multiplier
changes a value by tenths of its scale: the comparison that passes at
these tolerances fails for each of the fourteen
(``test_a_multiplier_moved_or_dropped_fails_the_comparison``).
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import falcon_h1, falcon_h1_reference as ref
from mxnet_tpu.models import lm_blocks
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import ssm, sums
from mxnet_tpu.parallel import make_mesh

T, BATCH, CHUNK, TAPS = 30, 2, 8, 4
# the uncut tiny model: 4 Mamba-2 heads of 8 in 2 groups, state 16; 10
# query heads on 2 key/value heads of 8; SwiGLU of 80
CFG = dict(
    model_type="falcon_h1", vocab_size=512, hidden_size=48,
    intermediate_size=80, num_hidden_layers=2, num_attention_heads=10,
    num_key_value_heads=2, head_dim=8, mamba_n_heads=4, mamba_d_head=8,
    mamba_d_ssm=32, mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=TAPS,
    mamba_chunk_size=CHUNK, mamba_expand=2, rms_norm_eps=1e-5,
    rope_theta=1e4, rope_scaling=None, max_position_embeddings=T,
    hidden_act="silu", attention_bias=False, mlp_bias=False,
    projectors_bias=False, mamba_proj_bias=False, mamba_conv_bias=True,
    mamba_rms_norm=True, mamba_norm_before_gate=False, mamba_use_mlp=True,
    tie_word_embeddings=False, attn_layer_indices=None,
    embedding_multiplier=1.7, lm_head_multiplier=0.3,
    attention_in_multiplier=0.9, attention_out_multiplier=0.6,
    key_multiplier=0.45, ssm_in_multiplier=0.8, ssm_out_multiplier=0.7,
    ssm_multipliers=[0.55, 1.3, 0.65, 1.2, 0.85],
    mlp_multipliers=[0.75, 0.35])
# one of the two chips that share each layer: 2 heads with 1 group, 5
# query heads with 1 key/value head, 40 of the 80 columns
SHARE = dict(
    CFG, mamba_n_heads=2, mamba_n_groups=1, num_attention_heads=5,
    num_key_value_heads=1, vocab_size=256,
    share=dict(chips=2, mamba_heads_of=4, mamba_groups_of=2,
               attention_heads_of=10, kv_heads_of=2, ssm_columns_held=16,
               dense_columns_held=40))
# (key, index in a list or None) of the fourteen
MULTIPLIERS = (
    [(k, None) for k in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")]
    + [("ssm_multipliers", i) for i in range(5)]
    + [("mlp_multipliers", i) for i in range(2)])


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _dynamics(rng, shape):
    """``a_log`` and ``dt_bias`` by the published Mamba-2 rule."""
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), shape))
    return (np.log(rng.uniform(1, 16, shape)).astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


def _params(sym, seed, data=(BATCH, T), sigma=0.2):
    """Seeded weights under the symbol's argument names: Normal(sigma), a
    unit embedding, gammas and the skip near 1, taps of the published
    spread, ``a_log`` and ``dt_bias`` by the published rule."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=data, softmax_label=data)
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("ssm_a_log"):
            out[name] = _dynamics(rng, shape)[0]
        elif name.endswith("ssm_dt_bias"):
            out[name] = _dynamics(rng, shape)[1]
        else:
            scale = (1.0 if name == "embed_weight" else
                     0.3 if name.endswith("conv_weight") else sigma)
            out[name] = (scale * rng.randn(*shape) + name.endswith(
                ("_gamma", "ssm_d"))).astype(np.float32)
    return out


def _batch(seed, vocab, shape=(BATCH, T)):
    tokens = np.random.RandomState(seed).randint(
        0, vocab, (shape[0], shape[1] + 1))
    return tokens[:, :-1].astype(np.float32), tokens[:, 1:].astype(np.float32)


def _module(sym, params, data=(BATCH, T)):
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", data)],
             label_shapes=[("softmax_label", data)])
    mod.init_params(arg_params={k: mx.nd.array(v) for k, v in params.items()},
                    aux_params={})
    return mod


def _program(cfg, params, tokens, labels):
    """(per-sequence loss, {name: gradient of the mean token loss}) of
    the symbol ``cfg`` builds, through ``Module.forward`` / ``backward``."""
    mod = _module(falcon_h1.from_config(cfg, seq_len=T), params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert len(outs) == 1                       # the loss and nothing else
    grads = mod._exec_group.execs[0].grad_dict
    # the head sums the sequences' losses (MXNet's convention)
    return outs[0], {k: grads[k].asnumpy() / BATCH for k in params}


def _compare(cfg, program_cfg, seed=1):
    """THE COMPARISON of (b): the program built from ``program_cfg``
    against the reference given ``cfg``, on the same seeded weights:
    per-sequence loss and the gradient of every parameter at the module's
    stated tolerances."""
    sym = falcon_h1.from_config(cfg, seq_len=T)
    params = _params(sym, seed)
    tokens, labels = _batch(seed + 1, cfg["vocab_size"])
    want = ref.forward(params, tokens, cfg, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)
    per_sequence, got = _program(program_cfg, params, tokens, labels)
    _close(per_sequence, want["per_sequence"], "per-sequence loss")
    _close(per_sequence.mean(), loss, "loss")
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        _close(got[name], want_g, name, ulps=256)
        assert np.abs(np.asarray(want_g)).max() > 1e-8, name
    return sym, params, tokens, labels, want


@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_logits_loss_and_every_gradient_match_the_reference(cfg):
    """(b) Two parallel blocks: per-sequence loss, the last positions'
    logits and the gradient of every parameter, whole and as one chip's
    share (its heads, its group, its columns, its rows of the
    vocabulary)."""
    sym, params, tokens, labels, want = _compare(cfg, cfg)
    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    _close(logits, want["logits"], "logits", ulps=64)
    last = ref.forward(params, tokens, cfg, labels=labels, last=7)
    _close(last["logits"], want["logits"][:, -7:], "the last positions")
    assert np.isinf(np.asarray(last["router_gap"])).all()   # no experts


def _changed(cfg, key, index, value):
    if index is None:
        return dict(cfg, **{key: value})
    values = list(cfg[key])
    values[index] = value
    return dict(cfg, **{key: values})


def _value(cfg, key, index):
    return cfg[key] if index is None else cfg[key][index]


@pytest.mark.parametrize("key,index", MULTIPLIERS,
                         ids=["%s%s" % (k, "" if i is None else "_%d" % i)
                              for k, i in MULTIPLIERS])
def test_a_multiplier_moved_or_dropped_fails_the_comparison(key, index):
    """(d) Each of the fourteen, in the program alone: dropped (1 in its
    place) and moved (it trades places with the next of the fourteen),
    the reference keeping the published placement. The comparison of
    (b), which passes at its stated tolerances, fails either way."""
    at = MULTIPLIERS.index((key, index))
    other = MULTIPLIERS[(at + 1) % len(MULTIPLIERS)]
    values = [_value(CFG, *m) for m in MULTIPLIERS]
    assert len(set(values)) == 14 and 1 not in values
    dropped = _changed(CFG, key, index, 1.0)
    moved = _changed(_changed(CFG, key, index, _value(CFG, *other)),
                     *other, _value(CFG, key, index))
    for what, program_cfg in (("dropped", dropped), ("moved", moved)):
        with pytest.raises(AssertionError):
            _compare(CFG, program_cfg)
            pytest.fail("%s %s went unseen" % (key, what))


@pytest.mark.parametrize("key,index", [
    ("ssm_out_multiplier", None), ("attention_out_multiplier", None),
    ("mlp_multipliers", 1)], ids=["mamba2", "attention", "mlp"])
def test_a_zeroed_sub_layer_fails_the_comparison(key, index):
    """(d) Either mixer or the MLP adding nothing in the program alone
    (its output under 1e-9: a billionth of the stream, nothing at
    float32): at these weights every sub-layer is a visible share of the
    stream, and the comparison fails."""
    with pytest.raises(AssertionError):
        _compare(CFG, _changed(CFG, key, index, 1e-9))
        pytest.fail("a zeroed %s went unseen" % key)


def _share_of(params, s):
    """Share ``s`` of 2 of the uncut tiny model's parameters: its heads
    and its group of ``in_proj``'s five segments and of what ``Mamba2``
    owns, its key/value head with the five query heads that read it, its
    columns of the SwiGLU; ``out_proj``, ``o_proj`` and ``down_proj`` by
    rows of their inputs; norms and embedding whole, the head's rows."""
    d_in, gn, h = 32, 32, 4                    # uncut: H P, G N, H
    hd, gd, hh = d_in // 2, gn // 2, h // 2    # a share's
    segments = np.split(np.arange(2 * d_in + 2 * gn + h),
                        np.cumsum([d_in, d_in, gn, gn]))
    proj_rows = np.concatenate([
        seg[s * w:(s + 1) * w] for seg, w in zip(segments,
                                                 (hd, hd, gd, gd, hh))])
    conv = proj_rows[hd:hd + hd + 2 * gd] - d_in
    x_cols = np.arange(s * hd, (s + 1) * hd)
    q_cols = np.arange(s * 40, (s + 1) * 40)   # 5 heads of 8
    kv_cols = np.arange(s * 8, (s + 1) * 8)
    mlp_cols = np.arange(s * 40, (s + 1) * 40)
    out = {}
    for name, v in params.items():
        leaf = name.split("_", 1)[1] if name.startswith("layer") else name
        if leaf == "in_proj_weight":
            v = v[proj_rows]
        elif leaf in ("ssm_conv_weight", "ssm_conv_bias"):
            v = v[..., conv]
        elif leaf in ("ssm_dt_bias", "ssm_a_log", "ssm_d"):
            v = v[s * hh:(s + 1) * hh]
        elif leaf == "ssm_norm_gamma":
            v = v[x_cols]
        elif leaf == "out_proj_weight":
            v = v[:, x_cols]
        elif leaf == "q_proj_weight":
            v = v[q_cols]
        elif leaf in ("k_proj_weight", "v_proj_weight"):
            v = v[kv_cols]
        elif leaf == "o_proj_weight":
            v = v[:, q_cols]
        elif leaf in ("gate_proj_weight", "up_proj_weight"):
            v = v[mlp_cols]
        elif leaf == "down_proj_weight":
            v = v[:, mlp_cols]
        elif leaf == "lm_head_weight":
            v = v[s * 256:(s + 1) * 256]
        out[name] = np.ascontiguousarray(v)
    return out


def test_the_two_shares_add_up_to_the_uncut_layer():
    """(c) THE SHARE-SUM TEST. One layer of the uncut tiny model on one
    normed input: the two shares' scaled mixer outputs, summed, are the
    uncut reference's scaled mixer outputs, and their MLP outputs on one
    input the uncut MLP's; the residual is what every chip holds alike
    and counts once. The program's share computes the reference's share
    (the node ``layer0_mixer_sum`` of the share's symbol). A four-way
    division would split a group of the gated norm and is refused."""
    whole = _params(falcon_h1.from_config(CFG, seq_len=T), 5)
    rng = np.random.RandomState(6)
    n = jnp.asarray(rng.randn(BATCH, T, 48), jnp.float32)
    v = jnp.asarray(rng.randn(BATCH, T, 48), jnp.float32)

    def fetch(params):
        return lambda name: jnp.asarray(params[name])

    with jax.default_matmul_precision("highest"):
        ssm, attn = ref.mixers(n, fetch(whole), "layer0_", CFG)
        mlp = ref.mlp(v, fetch(whole), "layer0_", CFG)
        parts = []
        for s in range(2):
            mine = fetch(_share_of(whole, s))
            parts.append(ref.mixers(n, mine, "layer0_", SHARE)
                         + (ref.mlp(v, mine, "layer0_", SHARE),))
    for name, want, got in zip(("ssm", "attn", "mlp"), (ssm, attn, mlp),
                               zip(*parts)):
        _close(got[0] + got[1], want, "the two shares' %s" % name, ulps=32)
        # a share is a part, not the whole: neither alone is the sum
        assert float(jnp.abs(got[0] - want).max()) > 1e-2, name
    # the residual counted once: h + both shares' parts, not 2 h + ...
    h = jnp.asarray(rng.randn(BATCH, T, 48), jnp.float32)
    _close(h + sum(p[0] + p[1] for p in parts), h + ssm + attn,
           "the stream after the mixers", ulps=32)

    # the program's share is the reference's share: the scaled sum of its
    # two mixers, from the embedding on
    tokens, _ = _batch(7, SHARE["vocab_size"])
    for s in range(2):
        mine = _share_of(whole, s)
        sym = falcon_h1.from_config(SHARE, seq_len=T)
        node = sym.get_internals()["layer0_mixer_sum_output"]
        mod = mx.mod.Module(node, context=mx.cpu(0), label_names=None)
        mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
        held = set(node.list_arguments())
        mod.init_params(arg_params={k: mx.nd.array(v) for k, v in
                                    mine.items() if k in held},
                        aux_params={})
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]),
                    is_train=False)
        got = mod.get_outputs()[0].asnumpy().reshape(BATCH, T, 48)
        record = []
        ref.forward(mine, tokens, SHARE, parts=record)
        _close(got, record[0]["ssm"] + record[0]["attn"],
               "share %d's mixer sum" % s, ulps=32)


def test_a_share_that_divides_the_gated_norms_group_is_refused():
    """(c) Four chips would hold 1 of the 4 heads each, half a group:
    the factory says why it builds no such share."""
    four_way = dict(SHARE, mamba_n_heads=1, mamba_n_groups=1,
                    share=dict(SHARE["share"], chips=4, ssm_columns_held=8))
    with pytest.raises(ValueError, match="gated RMSNorm's statistic is "
                       "over one group's 16 columns"):
        falcon_h1.from_config(four_way, seq_len=T)
    # and the cell's: 8 of 32 heads is half of a group of 16
    with pytest.raises(ValueError, match="hold whole groups"):
        falcon_h1.from_config(dict(
            CFG, mamba_n_heads=8, mamba_n_groups=1, mamba_d_head=128,
            mamba_d_ssm=4096, share=dict(mamba_heads_of=32,
                                         mamba_groups_of=2)), seq_len=T)
    with pytest.raises(ValueError, match="all the query heads that read"):
        falcon_h1.from_config(dict(
            SHARE, num_attention_heads=4, share=dict(
                SHARE["share"], attention_heads_of=10)), seq_len=T)


@pytest.mark.parametrize("key, value", [
    ("attention_bias", True), ("mlp_bias", True), ("projectors_bias", True),
    ("mamba_proj_bias", True), ("mamba_conv_bias", False),
    ("mamba_rms_norm", False), ("mamba_norm_before_gate", True),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "linear"}), ("attn_layer_indices", [0]),
    ("mamba_d_ssm", 64)])
def test_from_config_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match=key):
        falcon_h1.from_config(dict(CFG, **{key: value}), seq_len=T)


# -- (a) the op with its five multipliers -------------------------------------

HEADS, P, N, OP_CHUNK = 2, 64, 128, 128        # the state twice the head
SSM = dict(mamba_n_heads=HEADS, mamba_d_head=P, mamba_n_groups=1,
           mamba_d_state=N, rms_norm_eps=1e-5,
           ssm_multipliers=[0.55, 1.3, 0.65, 1.2, 0.85])
OP_GRADS = ("dproj", "dconv_weight", "dconv_bias", "ddt_bias", "da_log",
            "dd", "dnorm_gamma")


@pytest.fixture(params=["jnp_branch", "kernels_interpreted"])
def scan_path(request, monkeypatch):
    """``mamba2`` both ways a CPU test can run it at a shape the scan's
    and the taps' kernels take: as a step lowered off the TPU runs it
    (``jax.numpy`` inside the ``custom_vjp``s), and with both kernel
    pairs put through the Pallas interpreter (what the TPU's branch
    computes)."""
    ssm._mamba2_block.clear_cache()
    if request.param == "kernels_interpreted":
        monkeypatch.setattr(pk.common, "INTERPRET", True)
    yield request.param
    ssm._mamba2_block.clear_cache()


def _op_inputs(seed, t):
    rng = np.random.RandomState(seed)
    conv_dim = HEADS * P + 2 * N
    a_log, dt_bias = _dynamics(rng, HEADS)

    def draw(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(shift + scale * rng.randn(*shape), jnp.float32)

    return (draw(BATCH, t, HEADS * P + conv_dim + HEADS),
            draw(TAPS, conv_dim, scale=0.3), draw(conv_dim, scale=0.1),
            jnp.asarray(dt_bias), jnp.asarray(a_log),
            draw(HEADS, scale=0.2, shift=1.0),
            draw(HEADS * P, scale=0.1, shift=1.0))


def _op(*ins, remat=False, multipliers=tuple(SSM["ssm_multipliers"])):
    return tr.mamba2(*ins, num_heads=HEADS, head_dim=P, state_size=N,
                     num_groups=1, chunk_size=OP_CHUNK, eps=1e-5,
                     remat=remat, multipliers=multipliers)


def _published(proj, *rest):
    """The reference as published: the vector on the projection."""
    return ref.mamba2(proj * ref.ssm_vector(SSM, proj.dtype), *rest, SSM)


def test_mamba2_under_its_five_multipliers_matches_the_recurrence(scan_path):
    """(a) Values and the gradient of every input, float32 to summation
    order: the op never scales ``proj`` (the taps' weights, the gate and
    the step sizes carry the five), the reference scales it as
    published. T 300 is not whole chunks."""
    assert pk.ssd_takes(HEADS, P, N, 1, OP_CHUNK, jnp.float32)
    ins = _op_inputs(0, 300)
    _close(_op(*ins), _published(*ins), "out", ulps=16)
    # without them the result is another one
    assert float(jnp.abs(_op(*ins, multipliers=None)
                         - _published(*ins)).max()) > 0.1
    ins = _op_inputs(2, 256)
    assert pk.taps_takes(HEADS * P + 2 * N, 256, TAPS, jnp.float32,
                         "bias_silu", HEADS * P, ins[0].shape[2])
    got = jax.grad(lambda *a: jnp.sum(_op(*a, remat=True) ** 2),
                   tuple(range(7)))(*ins)
    want = jax.grad(lambda *a: jnp.sum(_published(*a) ** 2),
                    tuple(range(7)))(*ins)
    for name, g, w in zip(OP_GRADS, got, want):
        assert float(jnp.abs(w).max()) > 1e-4, name
        _close(g, w, name, ulps=64)


def test_mamba2_refuses_multipliers_that_are_not_five():
    ins = _op_inputs(0, 16)
    with pytest.raises(ValueError, match="five scalars"):
        _op(*ins, multipliers=(0.5, 0.5))


def test_scaled_sum_multiplies_in_float32_and_rounds_once():
    """A scalar rounded to bf16 first is another scalar (0.0375 is
    0.03759765625 there): the product is taken in float32."""
    x = jnp.asarray(np.random.RandomState(0).randn(64, 48), jnp.bfloat16)
    y = jnp.asarray(np.random.RandomState(1).randn(64, 48), jnp.bfloat16)
    got = tr.scaled_sum([x, y], (0.0375, 0.0884))
    want = (x.astype(jnp.float32) * 0.0375
            + y.astype(jnp.float32) * 0.0884).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    rounded = x * jnp.asarray(0.0375, jnp.bfloat16)
    assert float(jnp.abs(rounded.astype(jnp.float32) - tr.scaled_sum(
        [x], (0.0375,)).astype(jnp.float32)).max()) > 0
    # a scalar of 1 adds no node
    data = mx.sym.Variable("data")
    assert lm_blocks.scaled(data, "same", 1.0) is data
    with pytest.raises(ValueError, match="2 inputs under scales"):
        sums._scaled_sum({"scales": (0.5,)}, [x, y], False)


# -- the model through the fused step ------------------------------------------

def test_fused_fit_trains_the_share_and_the_loss_falls():
    """(b) Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep on the share: the first steps follow the reference's
    own SGD with momentum, and the loss falls."""
    sym = falcon_h1.from_config(SHARE, seq_len=T)
    params = _params(sym, 3)
    tokens, labels = _batch(4, SHARE["vocab_size"])
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
    # the dynamics, the taps and the skip are trained like any weight
    for name in ("layer0_ssm_a_log", "layer1_ssm_dt_bias",
                 "layer0_ssm_conv_weight", "layer1_ssm_d",
                 "layer0_ssm_norm_gamma"):
        assert np.abs(got[name].asnumpy() - params[name]).max() > 0, name


def test_the_blocks_count_themselves_where_they_are_traced():
    """(f) Two parallel blocks at a shape the scan's and the taps'
    kernels take (2 heads of 64, state 128, chunks of 128, T 256; 5
    query heads on 1 of 16) through the fused step: each ``Mamba2`` node
    counts its call site with the label that says its projection is
    scaled, the convolution under its site, the flash kernel's call site
    with its key/value heads, the ``ScaledSum`` node its parallel block;
    the block behind the two nodes is one ``jax.jit`` and each scan
    kernel's ``pallas_call`` is traced once. Off the TPU the step runs
    the ``jax.numpy`` branch inside each ``custom_vjp`` and follows the
    reference."""
    t = 256
    cfg = dict(SHARE, mamba_n_heads=HEADS, mamba_d_head=P,
               mamba_d_state=N, mamba_d_ssm=2 * HEADS * P,
               mamba_chunk_size=OP_CHUNK, head_dim=16,
               max_position_embeddings=t,
               share=dict(SHARE["share"], mamba_heads_of=2 * HEADS,
                          ssm_columns_held=HEADS * P))
    sym = falcon_h1.from_config(cfg, seq_len=t)
    params = _params(sym, 7, data=(1, t))
    tokens, labels = _batch(8, cfg["vocab_size"], (1, t))
    lr, momentum, steps = 0.02, 0.9, 3
    want = {k: jnp.asarray(v) for k, v in params.items()}
    moms = {k: jnp.zeros_like(v) for k, v in want.items()}
    losses = []
    for _ in range(2):
        loss, grads = ref.loss_and_grads(want, tokens, labels, cfg)
        losses.append(float(loss))
        want, moms = ref.sgd_momentum_step(want, moms, grads, lr, momentum)

    for jitted in (ssm._mamba2_block, pk.ssd.ssd_fwd_call,
                   pk.ssd.ssd_bwd_call):
        jitted.clear_cache()    # another test's trace is not this one's
    telemetry.reset()
    telemetry.enable()
    try:
        seen = []
        mod = mx.mod.Module(sym, context=mx.cpu(0), mesh=make_mesh(dp=1))
        mod.fit(mx.io.NDArrayIter(np.tile(tokens, (steps, 1)),
                                  np.tile(labels, (steps, 1)), batch_size=1),
                num_epoch=1, eval_metric="loss", optimizer="sgd",
                optimizer_params={"learning_rate": lr, "momentum": momentum},
                kvstore="device",
                arg_params={k: mx.nd.array(v) for k, v in params.items()},
                aux_params={}, initializer=None,
                batch_end_callback=lambda b: (
                    seen.append(b.eval_metric.get()[1]),
                    b.eval_metric.reset()))
        assert mod._fused_trainer is not None
        scan = telemetry.REGISTRY.get("ssm.scan_lowerings")
        assert scan.value(heads=HEADS, head_dim=P, state=N, groups=1,
                          chunk=OP_CHUNK, conv=TAPS, impl="kernel",
                          scaled=1) == 2
        assert telemetry.total("ssm.scan_lowerings") == 2
        traces = telemetry.REGISTRY.get("ssm.scan_kernel_traces")
        assert (traces.value(mode="fwd"), traces.value(mode="bwd")) == (1, 1)
        taps = telemetry.REGISTRY.get("causal_taps.lowerings")
        assert taps.value(site="mamba2", channels=HEADS * P + 2 * N,
                          taps=TAPS, impl="kernel") == 2
        norm = telemetry.REGISTRY.get("gate_norm.lowerings")
        assert norm.value(site="mamba2", groups=1, width=HEADS * P,
                          impl="kernel") == 2
        assert telemetry.total("gate_norm.lowerings") == 2
        blocks = telemetry.REGISTRY.get("lm.parallel_blocks")
        assert blocks.value(mixers="mamba2+attention", count=2) == 2
        assert telemetry.total("lm.parallel_blocks") == 2
        flash = telemetry.REGISTRY.get("attention.flash_lowerings")
        sites = [dict(k) for k in flash._values if dict(k).get("kv_heads")]
        assert sites and all(s["kv_heads"] == 1 for s in sites), sites
        assert sum(flash._values[k] for k in flash._values
                   if dict(k).get("kv_heads") == 1) == 2
    finally:
        telemetry.disable()
        telemetry.reset()
    _close(seen[:2], losses, "loss of the first two steps")
    assert seen[-1] < seen[0], seen


def test_the_model_states_its_own_initialisation():
    """Every matrix ``Normal(gain / (sqrt(fan_in) * m))`` with the UNCUT
    model's fan-in, so that the multipliers cancel at step 0; what
    ``Mamba2`` owns by the published rules; gammas one."""
    sym = falcon_h1.from_config(dict(SHARE, hidden_size=64, vocab_size=2048),
                                seq_len=T)
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH, T))],
             label_shapes=[("softmax_label", (BATCH, T))],
             for_training=False)
    mx.random.seed(5)
    np.random.seed(5)
    mod.init_params(initializer=mx.init.Normal(sigma=0.02))
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    gains = falcon_h1.INIT_GAINS
    m = SHARE
    want = {
        "embed_weight": 1 / m["embedding_multiplier"],
        "layer0_q_proj_weight": 1 / (8 * m["attention_in_multiplier"]),
        "layer0_k_proj_weight": 1 / (8 * m["attention_in_multiplier"]
                                     * m["key_multiplier"]),
        "layer0_v_proj_weight": 1 / (8 * m["attention_in_multiplier"]),
        # the uncut fan-in: 10 heads of 8, 32 columns, 80 columns
        "layer0_o_proj_weight": gains["o_proj"] / (
            80 ** 0.5 * m["attention_out_multiplier"]),
        "layer0_in_proj_weight": 1 / (8 * m["ssm_in_multiplier"]
                                      * m["ssm_multipliers"][1]),
        "layer0_out_proj_weight": gains["out_proj"] / (
            32 ** 0.5 * m["ssm_out_multiplier"]),
        "layer1_gate_proj_weight": 1 / (8 * m["mlp_multipliers"][0]),
        "layer1_up_proj_weight": 1 / 8,
        "layer1_down_proj_weight": gains["down_proj"] / (
            80 ** 0.5 * m["mlp_multipliers"][1]),
        "lm_head_weight": gains["head"] / (8 * m["lm_head_multiplier"]),
    }
    for name, sigma in want.items():
        assert got[name].std() == pytest.approx(sigma, rel=0.1), name
    for name in ("layer0_norm_gamma", "layer0_ffn_norm_gamma",
                 "layer1_ssm_norm_gamma", "final_norm_gamma",
                 "layer0_ssm_d"):
        assert (got[name] == 1).all(), name
    assert (got["layer0_ssm_conv_bias"] == 0).all()
    taps = got["layer0_ssm_conv_weight"]
    assert taps.shape == (TAPS, 2 * 8 + 2 * 16)
    assert 0.4 < np.abs(taps).max() <= 0.5
    rate = np.exp(got["layer0_ssm_a_log"])
    step = np.log1p(np.exp(got["layer0_ssm_dt_bias"]))
    assert rate.min() >= 1 and rate.max() <= 16
    assert step.min() >= 0.00099 and step.max() <= 0.101


def test_the_symbol_is_one_norm_two_mixers_and_names_every_scaling():
    """Both mixers read the ONE norm; the scalings are nodes named for
    what they scale (a trace files each under its neighbour); a
    multiplier of 1 adds none."""
    sym = falcon_h1.from_config(CFG, seq_len=T)
    nodes = json.loads(sym.tojson())["nodes"]
    by_name = {n["name"]: n for n in nodes}

    def inputs(name):
        return [nodes[i[0]]["name"] for i in by_name[name]["inputs"]]

    assert inputs("layer0_in_proj")[0] == "layer0_norm"
    assert inputs("layer0_attn_in_scale") == ["layer0_norm"]
    for name in ("q", "k", "v"):
        assert inputs("layer0_%s_proj" % name)[0] == "layer0_attn_in_scale"
    assert inputs("layer0_k_proj_scale") == ["layer0_k_proj"]
    assert inputs("layer0_mixer_sum") == ["layer0_out_proj", "layer0_o_proj"]
    assert by_name["layer0_mixer_sum"]["op"] == "_contrib_ScaledSum"
    assert inputs("layer0_gate_proj_scale") == ["layer0_gate_proj"]
    assert inputs("layer0_down_proj_scale") == ["layer0_down_proj"]
    assert inputs("embed_scale") == ["embed"]
    assert inputs("lm_head_f32") == ["lm_head_cast"]
    # two norms a layer and the final one; no node for the ssm's six
    norms = [n["name"] for n in nodes if n["op"] == "_contrib_RMSNorm"]
    assert norms == ["layer0_norm", "layer0_ffn_norm", "layer1_norm",
                     "layer1_ffn_norm", "final_norm"]
    assert eval(by_name["layer0_ssm"]["attr"]["multipliers"]) == \
        pytest.approx([0.8 * v for v in CFG["ssm_multipliers"]])
    assert not [n for n in by_name if "ssm_in" in n or "ssm_out" in n]
    # the published attention_in_multiplier is 1: no node
    published = falcon_h1.from_config(dict(CFG, attention_in_multiplier=1),
                                      seq_len=T)
    names = [n["name"] for n in json.loads(published.tojson())["nodes"]]
    assert "layer0_attn_in_scale" not in names
    assert "layer0_k_proj_scale" in names


def test_the_benchmarks_copy_of_the_reference_is_the_programs():
    """(e) ``bench/reference/falcon_h1.py`` is this file byte for byte:
    the benchmark may not import the program's reference (it would then
    compare the program with itself across a refactor), and nothing else
    held the two equal."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "reference",
                           "falcon_h1.py"), "rb") as ours, \
            open(ref.__file__, "rb") as theirs:
        assert ours.read() == theirs.read()
