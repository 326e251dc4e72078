"""Keye-VL-2.0-30B-A3B's language model on the normal path against its
plain reference.

``models/keye_vl2.py`` (an ``mx.sym`` graph of ``RMSNorm``, per-head
norms, ``RoPE``, ``KeyIndexer`` reading the block's normed input,
``Attention(with_keep=True)`` on grouped heads and ``TopKMoE``) through
``Module.forward/backward`` and ``Module.fit``'s fused step, against
``models/keye_vl2_reference.py`` (plain float32 ``jax.numpy``:
materialised scores under explicit masks, ``jax.lax.top_k`` on the masked
index scores, a loop over the experts held) on seeded weights at a tiny
size: hidden 64, 3 layers, 8 query heads on 2 key/value heads of 16
choosing 12 keys by a 4-head indexer of 8, 16 experts top-3 of width 32,
T 32.

Tolerances as in ``tests/test_dots3.py``: both sides are float32 and only
the order of summation differs (``_close``). The index scores of the two
sides differ in their last bits too, and a selection is a discontinuous
function of them: the seeds below have no two scores of a row within 1e-5
at the rank that decides.
"""
import functools
import inspect
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import keye_vl2, keye_vl2_reference as ref
from mxnet_tpu.ops import kernels
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import attention as attention_ops
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import topk_moe

T, BATCH = 32, 2
CFG = dict(
    model_type="KeyeVL2", hidden_size=64, num_hidden_layers=3,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    rope_theta=10000000, rope_scaling={
        "mrope_section": [2, 3, 3], "rope_type": "default",
        "type": "default"},
    sa_config=dict(indexer_head_dim=8, indexer_num_heads=4,
                   indexer_num_kv_heads=1, kv_chunk_size=512,
                   q_chunk_size=512, topk=12),
    intermediate_size=96, moe_intermediate_size=32, num_experts=16,
    num_local_experts=16, num_experts_per_tok=3, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6,
    vocab_size=512, hidden_act="silu", attention_bias=False,
    tie_word_embeddings=False, use_sliding_window=False,
    sliding_window=None, max_window_layers=3, max_position_embeddings=T)
# one chip's share of the same model: 4 of the 16 experts from the 8th on
# in a buffer that holds every row
SHARE = dict(CFG, num_experts=4, num_local_experts=4, share=dict(
    experts_of=16, expert_offset=8, share_rows_bound=BATCH * T * 3))
LAYERS = 3
SELECTED = sum(min(t + 1, CFG["sa_config"]["topk"]) for t in range(T))
ROOT = os.path.join(os.path.dirname(__file__), "..")
FILE = os.path.join(ROOT, "bench", "configs", "keye_vl2_30b_a3b.json")


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, sigma=0.08, t=T):
    """Seeded weights under the symbol's argument names: Normal(sigma), a
    unit embedding, gammas near 1, the indexer's beta away from 0."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(BATCH, t), softmax_label=(BATCH, t))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = {"embed_weight": 1.0, "beta": 0.05}.get(
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
    sym = keye_vl2.from_config(cfg, seq_len=T)
    params = _params(sym, 1)
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, cfg, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)

    mod = _module(sym, params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert len(outs) == 1 + 2 * LAYERS
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    for layer in range(LAYERS):
        # over all 16 of the router's experts, share or not
        np.testing.assert_array_equal(
            outs[1 + layer], np.asarray(want["expert_counts"][layer]))
        assert outs[1 + layer].sum() == BATCH * T * 3
    for kept in outs[1 + LAYERS:]:   # pairs kept a sequence
        np.testing.assert_array_equal(kept, [SELECTED] * BATCH)
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention)
        _close(got[name].asnumpy() / BATCH, want_g, name, ulps=16)
        if "_index_" in name:
            # they move a choice and nothing else: no gradient
            assert not np.asarray(want_g).any()
            assert not got[name].asnumpy().any()
        elif any(part in name for part in ("q_norm", "k_norm", "v_proj")):
            assert np.abs(np.asarray(want_g)).max() > 1e-6, name

    logits_sym = sym.get_internals()["lm_head_f32_output"]
    mod = mx.mod.Module(logits_sym, context=mx.cpu(0), label_names=None)
    mod.bind(data_shapes=[("data", tokens.shape)], for_training=False)
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in params.items()}, aux_params={})
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]), is_train=False)
    logits = mod.get_outputs()[0].asnumpy().reshape(want["logits"].shape)
    _close(logits, want["logits"], "logits")


def test_the_bf16_symbol_stays_within_bf16s_distance_of_the_reference():
    """``dtype=bfloat16`` (the cell's): bf16 weights and operands,
    float32 accumulation and statistics. A bf16 value carries 8 bits
    (2^-9 = 2e-3 of its magnitude a rounding) and a position's logits
    pass some twenty roundings a layer: the mean loss of 64 positions
    stays within 2e-2 of the float32 reference on weights bf16 holds
    exactly, and every row still keeps exactly its keys."""
    sym = keye_vl2.from_config(SHARE, seq_len=T, dtype="bfloat16")
    params = {k: np.asarray(jnp.asarray(v, jnp.bfloat16), np.float32)
              for k, v in _params(sym, 31).items()}
    tokens, labels = _batch(32)
    want = ref.forward(params, tokens, SHARE, labels=labels)
    mod = _module(sym, params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=False)
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert abs(float(outs[0].mean()) - float(want["loss"])) < 2e-2
    for kept in outs[1 + LAYERS:]:
        np.testing.assert_array_equal(kept, [SELECTED] * BATCH)


def test_fused_fit_trains_the_share_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1) — the fused
    ShardedTrainStep — on the share: the first steps follow the
    reference's own SGD with momentum, the loss falls, and what has no
    gradient (the indexer) stands still."""
    sym = keye_vl2.from_config(SHARE, seq_len=T)
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
    _close(seen[:2], losses, "loss of the first two steps", rtol=1e-4)
    assert seen[-1] < seen[0] - 0.05, seen
    got, _ = mod.get_params()
    for name in params:
        if "_index_" in name:
            np.testing.assert_array_equal(got[name].asnumpy(), params[name])


def test_the_model_states_its_own_initialisation_and_counts_its_call_sites():
    telemetry.reset()
    telemetry.enable()
    try:
        sym = keye_vl2.from_config(SHARE, seq_len=T)
        mod = mx.mod.Module(sym, context=mx.cpu(0))
        mod.bind(data_shapes=[("data", (BATCH, T))],
                 label_shapes=[("softmax_label", (BATCH, T))],
                 for_training=False)
        mx.random.seed(5)
        mod.init_params(initializer=mx.init.Normal(sigma=0.02))
        tokens, labels = _batch(6)
        batch = mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)])
        mod.forward(batch, is_train=False)
        # one per layer's call site, nothing per step
        select = telemetry.REGISTRY.get("attention.select_lowerings")
        assert select.value(select=1, heads=8, group=4,
                            impl="composed") == LAYERS
        index = telemetry.REGISTRY.get("attention.index_lowerings")
        assert index.value(heads=4, width=8, topk=12, rows=T,
                           impl="jnp") == LAYERS
        mod.forward(batch, is_train=False)
        assert telemetry.total("attention.select_lowerings") == LAYERS
        assert telemetry.total("attention.index_lowerings") == LAYERS
    finally:
        telemetry.disable()
        telemetry.reset()
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert 3.6 < got["embed_weight"].std() < 4.4     # STREAM_RMS
    assert 0.015 < got["layer1_q_proj_weight"].std() < 0.025
    assert 0.015 < got["layer0_index_q_weight"].std() < 0.025
    assert not got["layer0_index_k_beta"].any()
    for name in ("layer0_q_norm_gamma", "layer0_k_norm_gamma",
                 "layer0_index_k_gamma", "layer2_ffn_norm_gamma"):
        assert (got[name] == 1).all()
    # a share holds its experts; attention, the indexer and the router
    # are whole
    assert got["layer0_q_proj_weight"].shape == (8 * 16, 64)
    assert got["layer0_k_proj_weight"].shape == (2 * 16, 64)
    assert got["layer0_q_norm_gamma"].shape == (16,)
    assert got["layer0_index_q_weight"].shape == (4 * 8, 64)
    assert got["layer0_index_k_weight"].shape == (8, 64)
    assert got["layer0_index_head_weight"].shape == (4, 64)
    assert got["layer0_moe_gate_weight"].shape == (64, 16)
    assert got["layer0_moe_down_weight"].shape == (4, 32, 64)
    assert not any("select_bias" in name or "shared" in name for name in got)


# -- the shares add up --------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """THE SHARE-SUM TEST, one layer of 128 experts at hidden 64: what
    every member computes alike (the norms, the indexer's selection, the
    attention of all heads, the router) counts once; eight expert shares
    of 16 run the PROGRAM's ``topk_moe`` over all 128 of the router's
    outputs and compute their own experts' part; the sum is the uncut
    reference's layer."""
    cfg = dict(CFG, num_hidden_layers=1, num_experts=128,
               num_local_experts=128, num_experts_per_tok=8)
    sym = keye_vl2.from_config(cfg, seq_len=T)
    p = {k: jnp.asarray(v) for k, v in _params(sym, 11).items()}
    rng = np.random.RandomState(12)
    h = jnp.asarray(rng.randn(BATCH, T, 64), jnp.float32)
    n, eps = "layer0_", cfg["rms_norm_eps"]

    # the uncut reference's layer
    x = ref.rms_norm(h, p[n + "attn_norm_gamma"], eps)
    attn, _ = ref.sparse_attention(x, p.__getitem__, n, cfg)
    mid = h + attn
    x2 = ref.rms_norm(mid, p[n + "ffn_norm_gamma"], eps)
    routed, counts, _ = ref.moe(
        x2.reshape(BATCH * T, -1), p[n + "moe_gate_weight"],
        p[n + "moe_gate_up_weight"], p[n + "moe_down_weight"], 8, True)
    want = mid + routed.reshape(BATCH, T, -1)

    # what every member computes alike, once: the program's ops
    keep, kept = tr.key_indexer(
        x, x, *(p[n + "index_" + w] for w in (
            "q_weight", "k_weight", "k_gamma", "k_beta", "head_weight")),
        num_heads=4, rope_dim=8, topk=12, theta=1e7)
    np.testing.assert_array_equal(np.asarray(kept), [SELECTED] * BATCH)

    def rotated(name, heads):
        y = tr.rms_norm((x @ p[n + name + "_proj_weight"].T).reshape(
            -1, 16), p[n + name + "_norm_gamma"], eps)
        return tr.rope(y.reshape(BATCH, T, heads * 16), heads, 1e7)

    ours = attention_ops._attention(
        dict(num_heads=8, num_kv_heads=2, causal=True, with_keep=True),
        [rotated("q", 8), rotated("k", 2), x @ p[n + "v_proj_weight"].T,
         keep], True)[0] @ p[n + "o_proj_weight"].T
    _close(ours, attn, "the attention every member computes", ulps=32)

    ffn = 0.0
    for offset in range(0, 128, 16):        # the eight shares
        held = {"gate_w": p[n + "moe_gate_weight"],
                "w_gate_up": p[n + "moe_gate_up_weight"][offset:offset + 16],
                "w_down": p[n + "moe_down_weight"][offset:offset + 16]}
        part, part_counts = topk_moe(
            held, x2.reshape(BATCH * T, -1), 8, norm_topk_prob=True,
            expert_offset=offset, share_rows_bound=BATCH * T * 8)
        np.testing.assert_array_equal(np.asarray(part_counts),
                                      np.asarray(counts))
        ffn = ffn + part.reshape(BATCH, T, -1)
    _close(mid + ffn, want, "sum of the eight shares", ulps=32)
    assert float(jnp.abs(routed).max()) > 1e-3


# -- the selection ------------------------------------------------------------

def _indexer_inputs(seed, t, d=64, heads=4, width=8):
    rng = np.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    return (draw(BATCH, t, d), 0.2 * draw(heads * width, d),
            0.2 * draw(width, d), 1 + 0.1 * draw(width), 0.1 * draw(width),
            0.2 * draw(heads, d))


@pytest.mark.parametrize("t,topk", [(48, 12), (300, 64), (16, 32)])
def test_the_selection_keeps_exactly_min_t_plus_1_k_keys_a_row(t, topk):
    """``KeyIndexer`` fed the block's normed input as its query (no
    latent), heads of 8 rotated whole: row t keeps ``min(t + 1, topk)``
    keys, none past the diagonal, and the same keys as the reference's
    ``jax.lax.top_k``; a sequence no longer than ``topk`` keeps the causal
    triangle."""
    x, *weights = _indexer_inputs(t, t)
    keep, count = tr.key_indexer(x, x, *weights, num_heads=4, rope_dim=8,
                                 topk=topk, theta=1e7)
    keep = np.asarray(keep)
    assert keep.dtype == np.int8 and keep.shape == (BATCH, t, t)
    want = np.minimum(np.arange(t) + 1, topk)
    np.testing.assert_array_equal(keep.sum(axis=-1), np.tile(want, (BATCH, 1)))
    assert not np.triu(keep, 1).any()
    np.testing.assert_array_equal(np.asarray(count), [want.sum()] * BATCH)
    chosen, share = ref.select(x, *weights, 1e7, topk, near_tie_eps=1e-4,
                               block=64)
    np.testing.assert_array_equal(keep != 0, np.asarray(chosen))
    assert 0 <= float(share) < 0.05
    if t <= topk:
        np.testing.assert_array_equal(keep[0], np.tril(np.ones((t, t))))


# -- Attention's keep-mask -----------------------------------------------------

HEADS, KV, D = 8, 2, 16


def _attention_inputs(seed, dtype, t=40):
    rng = np.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rng.randn(*s), dtype)
    scores = rng.randn(BATCH, t, t)
    scores[:, np.triu_indices(t, 1)[0], np.triu_indices(t, 1)[1]] = -np.inf
    kth = np.sort(scores, axis=-1)[..., -min(9, t)][..., None]
    keep = (scores >= kth) & np.tril(np.ones((t, t), bool))
    return (draw(BATCH, t, HEADS * D), draw(BATCH, t, KV * D),
            draw(BATCH, t, KV * D), jnp.asarray(keep, jnp.int8))


def _parent_attention(attrs, ins):
    """``_attention`` as the tree before the keep-mask had it (commit
    53b256f, ``ops/transformer/attention.py``)."""
    q, k, v = ins[:3]
    optional = dict(zip(attention_ops.optional_inputs(
        attrs, ("sink", "gate")), ins[3:]))
    heads = int(attrs["num_heads"])
    kv_heads = int(attrs.get("num_kv_heads", 0)) or heads
    window = int(attrs.get("window", 0))
    b, t, _ = q.shape

    def split(x, n):
        return x.reshape(b, t, n, x.shape[2] // n)

    with jax.named_scope("window" if window else "full"):
        out = kernels.attention(
            split(q, heads), split(k, kv_heads), split(v, kv_heads),
            causal=bool(attrs.get("causal", True)), window=window,
            sink=optional.get("sink"))
    out = out.reshape(b, t, -1)
    if "gate" in optional:
        with jax.named_scope("gate"):
            out = attention_ops.gate_output(out, optional["gate"])
    return [out]


@pytest.mark.parametrize("t,window,with_gate", [
    (40, 0, False), (160, 0, False), (160, 40, False), (160, 0, True)],
    ids=["short", "flash", "window", "gate"])
def test_the_maskless_call_traces_what_the_parent_traced(t, window,
                                                         with_gate):
    """Without a keep-mask the op's program is the parent's: the same
    jaxpr, text for text, and no selected call site is counted."""
    q, k, v, _ = _attention_inputs(5, jnp.bfloat16, t)
    ins = (q, k, v) + ((q,) if with_gate else ())
    attrs = dict(num_heads=HEADS, num_kv_heads=KV, causal=True,
                 window=window, with_gate=with_gate)

    def ours(*a):
        return attention_ops._attention(attrs, list(a), True)[0]

    def parents(*a):
        return _parent_attention(attrs, list(a))[0]

    telemetry.reset()
    telemetry.enable()
    try:
        assert str(jax.make_jaxpr(ours)(*ins)) == str(
            jax.make_jaxpr(parents)(*ins))
        assert telemetry.total("attention.select_lowerings") == 0
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_kept_call_matches_jax_numpy(dtype):
    """``Attention(keep=)`` on grouped heads against the plain masked
    softmax written out here: the weighted sum of the values and the
    three gradients; the mask has none. float32 differs by summation
    order; bf16 rounds each output once (2^-9 of its magnitude, and the
    sum of 10,240 of them drifts by some dozens of those) and the
    cotangents once more: 2^-5 of the largest magnitude."""
    q, k, v, keep = _attention_inputs(7, dtype)
    attrs = dict(num_heads=HEADS, num_kv_heads=KV, causal=True,
                 with_keep=True)
    t = q.shape[1]

    def ours(q, k, v):
        return attention_ops._attention(attrs, [q, k, v, keep], True)[0]

    def plain(q, k, v):
        f32 = jnp.float32
        q4 = q.astype(f32).reshape(BATCH, t, HEADS, D)
        k4, v4 = (jnp.repeat(a.astype(f32).reshape(BATCH, t, KV, D),
                             HEADS // KV, axis=2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q4, k4) / np.sqrt(D)
        s = jnp.where(keep[:, None] != 0, s, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v4)
        return out.reshape(BATCH, t, -1)

    w = jnp.asarray(np.random.RandomState(8).randn(BATCH, t, HEADS * D),
                    jnp.float32)

    def run(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
            argnums=(0, 1, 2))(q, k, v)

    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -5
    for g, want in zip(jax.tree_util.tree_leaves(run(ours)),
                       jax.tree_util.tree_leaves(run(plain))):
        g, want = np.asarray(g, np.float32), np.asarray(want, np.float32)
        assert np.abs(g - want).max() <= tol * max(np.abs(want).max(), 1), \
            np.abs(g - want).max()


def test_a_selection_of_every_key_is_plain_causal_attention():
    q, k, v, _ = _attention_inputs(9, jnp.float32)
    t = q.shape[1]
    everything = jnp.ones((BATCH, t, t), jnp.int8)
    attrs = dict(num_heads=HEADS, num_kv_heads=KV, causal=True)
    kept = attention_ops._attention(dict(attrs, with_keep=True),
                                    [q, k, v, everything], True)[0]
    plain = attention_ops._attention(attrs, [q, k, v], True)[0]
    _close(kept, plain, "a mask of ones")


def test_the_keep_mask_is_an_input_the_symbol_names_and_shapes():
    q, k, v = (mx.sym.Variable(n) for n in ("q", "k", "v"))
    attn = mx.contrib.sym.Attention(
        q, k, v, with_keep=True, keep=mx.sym.Variable("keep"), num_heads=8,
        num_kv_heads=2, name="attn")
    assert attn.list_arguments() == ["q", "k", "v", "keep"]
    shapes, out, _ = attn.infer_shape(q=(3, 20, 128), k=(3, 20, 32),
                                      v=(3, 20, 32))
    assert shapes == [(3, 20, 128), (3, 20, 32), (3, 20, 32), (3, 20, 20)]
    assert out == [(3, 20, 128)]
    types, out_types, _ = attn.infer_type(q=np.float32)
    assert types == [np.float32] * 3 + [np.int8]
    assert out_types == [np.float32]
    for extra, match in ((dict(window=8), "keep-mask"),
                         (dict(with_sink=True, sink=mx.sym.Variable("s")),
                          "keep-mask"), (dict(causal=False), "keep-mask")):
        bad = mx.contrib.sym.Attention(
            q, k, v, with_keep=True, keep=mx.sym.Variable("keep"),
            num_heads=8, num_kv_heads=2, name="bad", **extra)
        with pytest.raises(Exception, match=match):
            bad.infer_shape(q=(3, 20, 128), k=(3, 20, 32), v=(3, 20, 32))


def test_the_flash_path_runs_the_selected_pair_through_the_model(
        monkeypatch):
    """At T 256 with heads of 128 the model's ``Attention`` nodes take
    the selected pair (through the Pallas interpreter by the one seam):
    the loss and the counts are the reference's."""
    cfg = dict(CFG, num_hidden_layers=1, head_dim=128,
               num_attention_heads=8, num_key_value_heads=1, vocab_size=64,
               rope_scaling=dict(CFG["rope_scaling"],
                                 mrope_section=[16, 24, 24]),
               sa_config=dict(CFG["sa_config"], topk=48))
    t = 256
    sym = keye_vl2.from_config(cfg, seq_len=t)
    params = _params(sym, 41, t=t)
    rng = np.random.RandomState(42)
    tokens = rng.randint(0, 64, (BATCH, t + 1))
    tokens, labels = (tokens[:, :-1].astype(np.float32),
                      tokens[:, 1:].astype(np.float32))
    want = ref.forward(params, tokens, cfg, labels=labels)
    monkeypatch.setattr(kernels.common, "INTERPRET", True)
    telemetry.reset()
    telemetry.enable()
    try:
        mod = _module(sym, params, t=t)
        mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                    label=[mx.nd.array(labels)]),
                    is_train=False)
        outs = [o.asnumpy() for o in mod.get_outputs()]
        assert telemetry.REGISTRY.get("attention.select_lowerings").value(
            select=1, heads=8, group=8, impl="kernel") == 1
        # 256 keys are whole lane rows: the indexer's choice is the
        # kernel's (``ops/kernels/topk.py``), through the interpreter
        assert telemetry.REGISTRY.get("attention.index_lowerings").value(
            heads=4, width=8, topk=48, rows=256, impl="pallas") == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    _close(outs[0], want["per_sequence"], "per-sequence loss", rtol=1e-4)
    np.testing.assert_array_equal(
        outs[2], [sum(min(i + 1, 48) for i in range(t))] * BATCH)


# -- from_config --------------------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("use_sliding_window", True),
    ("mlp_only_layers", [1]), ("decoder_sparse_step", 2),
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("model_type", "qwen3_moe"), ("num_local_experts", 8),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
    ("rope_scaling", {"mrope_section": [2, 2, 2], "rope_type": "default",
                      "type": "default"}),
    ("sa_config", dict(CFG["sa_config"], indexer_num_kv_heads=2))])
def test_from_config_refuses_what_it_does_not_implement(key, value):
    match = "indexer_num_kv_heads" if key == "sa_config" else key
    with pytest.raises(ValueError, match=match):
        keye_vl2.from_config(dict(CFG, **{key: value}), seq_len=T)


def test_the_keys_listed_as_unread_are_read_by_nothing():
    def graph(cfg):  # auto-named nodes count up from one symbol to the next
        return re.sub(r'"([a-z_]*[a-z_])\d+"', r'"\1"',
                      keye_vl2.from_config(cfg, seq_len=T).tojson())

    base = graph(CFG)
    moved = dict(CFG, intermediate_size=1, max_window_layers=0,
                 max_position_embeddings=1 << 20, sliding_window=4096,
                 sa_config=dict(CFG["sa_config"], q_chunk_size=64,
                                kv_chunk_size=64))
    assert set(keye_vl2.ASSUMED_UNREAD) == {
        "intermediate_size", "max_window_layers", "max_position_embeddings",
        "sliding_window"}
    assert graph(moved) == base
    assert graph(dict(CFG, rms_norm_eps=1e-5)) != base
    assert graph(dict(CFG, sa_config=dict(CFG["sa_config"],
                                          topk=11))) != base


def test_the_file_is_the_share_the_cell_trains():
    """``bench/configs/keye_vl2_30b_a3b.json`` through ``from_config``:
    the published widths, the experts and rows the deployment's member
    holds, and the parameters the cut counted."""
    with open(FILE) as f:
        cfg = json.load(f)
    sym = keye_vl2.from_config(cfg, **cfg["kwargs"])
    shapes, outs, _ = sym.infer_shape(data=(1, 8192),
                                      softmax_label=(1, 8192))
    by_name = dict(zip(sym.list_arguments(), shapes))
    assert by_name["layer0_q_proj_weight"] == (32 * 128, 2048)
    assert by_name["layer0_k_proj_weight"] == (4 * 128, 2048)
    assert by_name["layer0_v_proj_weight"] == (4 * 128, 2048)
    assert by_name["layer0_o_proj_weight"] == (2048, 32 * 128)
    assert by_name["layer0_q_norm_gamma"] == (128,)
    assert by_name["layer4_index_q_weight"] == (16 * 64, 2048)
    assert by_name["layer4_index_k_weight"] == (64, 2048)
    assert by_name["layer4_index_head_weight"] == (16, 2048)
    assert by_name["layer1_moe_gate_weight"] == (2048, 128)
    assert by_name["layer1_moe_gate_up_weight"] == (16, 2048, 2 * 768)
    assert by_name["layer1_moe_down_weight"] == (16, 768, 2048)
    assert by_name["embed_weight"] == (18992, 2048)
    assert by_name["lm_head_weight"] == (18992, 2048)
    count = sum(int(np.prod(s)) for n, s in by_name.items()
                if n not in ("data", "softmax_label"))
    assert 561.5e6 < count < 563e6, count   # the issue's 562 M
    # loss, five layers' expert counts, five layers' selection counts
    assert outs == [(1,)] + [(128,)] * 5 + [(1,)] * 5
    nodes = {n["name"]: n for n in json.loads(sym.tojson())["nodes"]}
    attn = nodes["layer0_attn"]["attr"]
    assert attn["with_keep"] in ("True", "1")
    assert (attn["num_heads"], attn["num_kv_heads"]) == ("32", "4")
    index = nodes["layer0_index"]["attr"]
    assert (index["num_heads"], index["head_dim"], index["rope_dim"],
            index["topk"]) == ("16", "64", "64", "2048")
    moe = nodes["layer0_moe"]["attr"]
    assert (moe["num_experts"], moe["experts_held"], moe["top_k"],
            moe["share_rows_bound"]) == ("128", "16", "8", "16384")


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(ROOT, "bench", "reference",
                           "keye_vl2.py")) as ours, \
            open(ref.__file__) as program:
        assert ours.read() == program.read()


def test_the_reference_one_precision_below_is_another_result():
    """The reference in bf16 throughout (its index scores and its compare
    too) is off by what float32 is not: the reading a cell's tolerance
    has to fail."""
    sym = keye_vl2.from_config(CFG, seq_len=T)
    params = _params(sym, 21)
    tokens, labels = _batch(22)
    exact = ref.forward(params, tokens, CFG, labels=labels)
    below = ref.forward(params, tokens, CFG, labels=labels,
                        dtype="bfloat16")
    assert below["logits"].dtype == jnp.bfloat16
    err = np.abs(np.asarray(below["logits"], np.float32)
                 - np.asarray(exact["logits"])).max() \
        / np.asarray(exact["logits"]).std()
    assert 1e-3 < err < 0.5, err
    report = {"eps": 1e-3}
    ref.forward(params, tokens, dict(CFG, select_report=report),
                labels=labels)
    assert report["float32"]["keys_selected"] == [[SELECTED] * BATCH] * LAYERS
    assert all(0 <= s < 0.2 for s in report["float32"]["near_tie_share"])


# -- one test an ``assumed`` entry of the configuration's file ---------------

@functools.lru_cache(maxsize=None)
def _published_nodes():
    sym = keye_vl2.get_symbol(seq_len=64)
    return {n["name"]: n for n in json.loads(sym.tojson())["nodes"]}


def _attr(node, attr):
    return str(_published_nodes()[node]["attr"].get(attr))


ASSUMED = {
    "factory": lambda text: "from_config(this file, **kwargs)" in text,
    "indexer": lambda text: (
        "class Indexer" in text and "normed input" in text
        and (_attr("layer0_index", "num_heads"),
             _attr("layer0_index", "head_dim"),
             _attr("layer0_index", "topk")) == ("16", "64", "2048")
        and "ties to the lower index" in text),
    "indexer_rope": lambda text: (
        "WHOLE 64" in text and _attr("layer0_index", "rope_dim") == "64"
        and float(_attr("layer0_index", "theta")) == 1e7),
    "indexer_training": lambda text: (
        "detached" in text and "stop_gradient" in inspect.getsource(
            tr.key_indexer)),
    "chunks": lambda text: (
        "change no arithmetic" in text and "1,048,576" in text
        and "chunk" not in inspect.getsource(keye_vl2.get_symbol)),
    "head_norms": lambda text: (
        "BEFORE the rotation" in text and "one gamma of 128" in text
        and "layer0_q_norm" in _published_nodes()
        and "layer0_k_norm" in _published_nodes()
        and "layer0_v_norm" not in _published_nodes()),
    "rotation": lambda text: (
        "all 128 dimensions" in text and "[16, 24, 24]" in text
        and float(_attr("layer0_q_rope", "theta")) == 1e7
        and _attr("layer0_q_rope", "rotary_dim") in ("0", "None")
        and _attr("layer0_q_rope", "interleave") in ("False", "0", "None")),
    "attention": lambda text: (
        "1/sqrt(128)" in text
        and _attr("layer0_attn", "with_keep") in ("True", "1")
        and _attr("layer0_attn", "window") in ("0", "None")
        and (_attr("layer0_attn", "num_heads"),
             _attr("layer0_attn", "num_kv_heads")) == ("32", "4")),
    "block": lambda text: (
        "h += W_o attention(RMSNorm(h)); h += experts(RMSNorm(h))" in text
        and "layer0_attn_norm" in _published_nodes()
        and "layer0_ffn_norm" in _published_nodes()
        and float(_attr("layer0_attn_norm", "eps")) == 1e-6),
    "router": lambda text: (
        "THEN the 8 largest" in text and "no shared expert" in text
        and _attr("layer0_moe", "scoring") in ("softmax", "None")
        and _attr("layer0_moe", "norm_topk_prob") in ("True", "1")
        and _attr("layer0_moe", "with_select_bias") in ("False", "None",
                                                        "0")),
    "experts": lambda text: (
        "every layer is an expert layer" in text
        and all("layer%d_moe" % i in _published_nodes() for i in range(48))
        and _attr("layer0_moe", "num_hidden") == "768"),
    "embedding": lambda text: (
        "Normal(4)" in text and "10.261" in text
        and keye_vl2.STREAM_RMS == 4.0
        and "embed_sigma=STREAM_RMS" in inspect.getsource(
            keye_vl2.get_symbol)),
    "weights": lambda text: "Normal(0.02)" in text and "gammas 1" in text,
    "dtype": lambda text: "float32" in text and text.startswith("bfloat16"),
    "optimizer": lambda text: "SGD momentum 0.9" in text,
    "objective": lambda text: ("no auxiliary loss" in text
                               and "no KL loss" in text),
    "left_out": lambda text: (
        "vision tower" in text
        and not any("vision" in n or "patch" in n
                    for n in _published_nodes())),
    "share_rows_bound": lambda text: "16384" in text,
    "max_position_embeddings": lambda text: (
        "262144" in text
        and "max_position_embeddings" in keye_vl2.ASSUMED_UNREAD),
    "input_shape": lambda text: "[1, 1, 8192]" in text,
}


@pytest.mark.parametrize("entry", sorted(ASSUMED))
def test_an_assumed_entry_says_what_the_program_does(entry):
    """Each assumption of ``bench/configs/keye_vl2_30b_a3b.json`` is one
    entry, and it fails here if the file or the program moves."""
    with open(FILE) as f:
        assumed = json.load(f)["assumed"]
    assert ASSUMED[entry](assumed[entry]), assumed[entry]


def test_every_assumed_entry_has_its_test():
    with open(FILE) as f:
        assert set(json.load(f)["assumed"]) == set(ASSUMED)
