"""Xing4.0-29B-A4B on the normal path against its plain reference.

``models/xing4.py`` (an ``mx.sym`` graph: four residual streams a token
under ``HyperCoeff`` / ``HyperMix`` round every sub-layer, ``LatentAttention``
behind a query latent with YaRN's frequencies and score scale, the dense
SwiGLU or shared + ``TopKMoE`` experts, a multi-token-prediction module
that reads the ONE embedding and the ONE head a second time, two losses
behind one ``MakeLoss``) through ``Module.forward/backward`` and
``Module.fit``'s fused step, against ``models/xing4_reference.py`` (plain
float32 ``jax.numpy``: the stream [B, T, n, C], the recurrences as the
papers write them, attention by an explicit mask, a loop over the experts
held) on seeded weights at a tiny size: hidden 64, ``hc_mult`` 4, 4 heads
of 16 + 8 query/key and 16 value dimensions from latents of 24 and 32, 8
experts top-2 of width 32 and one shared, 2 layers and one module, T 32.

Tolerances. Float32 against float32 differs by the order of summation
only: ``_close`` is rtol 1e-5 with an atol of a few float32 ulps of the
tensor's own scale (``tests/test_kanana2.py``); the gradients take 64 ulps
because a gradient here passes 6 x 20 Sinkhorn iterations, each a
division by a sum, twice. The bf16 cases state their measured bands.
"""
import hashlib
import json
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import xing4, xing4_reference as ref
from mxnet_tpu.ops.kernels.common import rope_inv_freq
from mxnet_tpu.ops.transformer import (
    hyper_coeff, hyper_mix, latent_attention, sinkhorn)
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import topk_moe

T, BATCH, VOCAB, STREAMS = 32, 2, 128, 4
HEADS, NOPE, ROPE, DV, LATENT, QLATENT = 4, 16, 8, 16, 32, 24
YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16}
CFG = dict(
    model_type="xing4_0", hidden_size=64, num_hidden_layers=2,
    first_k_dense_replace=1, moe_layer_freq=1, num_attention_heads=HEADS,
    num_key_value_heads=HEADS, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
    v_head_dim=DV, kv_lora_rank=LATENT, q_lora_rank=QLATENT,
    rope_theta=10000, rope_scaling=YARN, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, norm_topk_prob=True, scoring_func="sigmoid",
    n_group=1, topk_group=1, topk_method="noaux_tc",
    routed_scaling_factor=2, rms_norm_eps=1e-6, vocab_size=VOCAB,
    hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
    max_position_embeddings=T, ep_size=1, num_nextn_predict_layers=1,
    hc_mult=STREAMS, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
# one chip's share: 2 of the 8 experts from the 4th on, a buffer that
# holds every row
SHARE = dict(CFG, n_routed_experts=2, share=dict(
    experts_of=8, expert_offset=4, share_rows_bound=BATCH * T * 2))
EXPERT_LAYERS = 2  # layer1 and the module's block


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, sigma=0.08):
    """Seeded weights under the symbol's argument names: Normal(sigma), a
    unit embedding, gammas near 1, selection biases away from 0, and a
    mixing that is far from its initial value (``alpha`` near 1, so the
    coefficients depend on the token; a carry bias that is no identity)."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(BATCH, T), softmax_label=(BATCH, T))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        tail = name.rsplit("_", 1)[-1]
        scale = {"alpha": 0.2, "bias": 0.5 if "_hc_" in name else 0.05}.get(
            tail, 1.0 if name == "embed_weight" else sigma)
        out[name] = (scale * rng.randn(*shape)
                     + (name.endswith("_gamma") or tail == "alpha")
                     ).astype(np.float32)
    return out


def _batch(seed):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, VOCAB, (BATCH, T + 1))
    return tokens[:, :-1].astype(np.float32), tokens[:, 1:].astype(np.float32)


def _data_batch(tokens, labels):
    return mx.io.DataBatch(data=[mx.nd.array(tokens)],
                           label=[mx.nd.array(labels)])


def _module(sym, params, for_training=True):
    """Bound with the label where the symbol reads it (the main head's
    logits alone do not)."""
    labelled = "softmax_label" in sym.list_arguments()
    mod = mx.mod.Module(sym, context=mx.cpu(0),
                        label_names=["softmax_label"] if labelled else None)
    mod.bind(data_shapes=[("data", (BATCH, T))],
             label_shapes=([("softmax_label", (BATCH, T))] if labelled
                           else None), for_training=for_training)
    names = set(sym.list_arguments())
    mod.init_params(arg_params={k: mx.nd.array(v) for k, v in params.items()
                                if k in names}, aux_params={})
    return mod


def _internals(sym, params, names, tokens, labels):
    internals = sym.get_internals()
    mod = _module(mx.sym.Group([internals[n] for n in names]), params,
                  for_training=False)
    mod.forward(_data_batch(tokens, labels), is_train=False)
    return [o.asnumpy() for o in mod.get_outputs()]


# -- the whole model, uncut and as a share -----------------------------------

@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_losses_both_logits_and_every_gradient_match_the_reference(cfg):
    sym = xing4.from_config(cfg, seq_len=T)
    params = _params(sym, 1)
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, cfg, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)

    mod = _module(sym, params)
    mod.forward(_data_batch(tokens, labels), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert sym.list_outputs() == [
        "loss_output", "layer1_expert_count_output",
        "mtp0_expert_count_output", "loss_part_output",
        "mtp0_loss_part_output", "hc_res_sum_err_output"]
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    _close(outs[3].mean(), want["loss_main"], "main loss")
    _close(outs[4].mean(), want["loss_mtp"], "module's loss")
    # ONE loss: main + 0.3 x the module's
    _close(outs[0], outs[3] + 0.3 * outs[4], "the sum")
    assert abs(float(want["loss_mtp"]) - float(want["loss_main"])) > 1e-3
    for layer in range(EXPERT_LAYERS):
        np.testing.assert_array_equal(
            outs[1 + layer], np.asarray(want["expert_counts"][layer]))
        assert outs[1 + layer].sum() == BATCH * T * 2
    # what 20 iterations left, the same number on both sides
    _close(outs[5], [float(want["hc_res_sum_err"])], "hc_res_sum_err",
           rtol=0.05, ulps=4)
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention)
        _close(got[name].asnumpy() / BATCH, want_g, name, rtol=1e-4, ulps=64)
        if "select_bias" in name:
            assert not np.asarray(want_g).any()  # it moves the choice only
        elif "_hc_" in name or "q_latent" in name or name.startswith("mtp0"):
            assert np.abs(np.asarray(want_g)).max() > 1e-7, name

    main, module = _internals(
        sym, params, ["lm_head_f32_output", "mtp0_lm_head_f32_output"],
        tokens, labels)
    _close(main.reshape(want["logits"].shape), want["logits"], "logits")
    _close(module.reshape(want["mtp_logits"].shape), want["mtp_logits"],
           "the module's logits")
    assert np.abs(main - module).max() > 0.1


@pytest.mark.parametrize("seed", [0, 1])
def test_the_bf16_symbol_stays_in_a_band_round_the_reference(seed):
    """bf16 weights and activations (mixing coefficients, router, norm
    statistics, RoPE, softmaxes and losses float32) against the float32
    reference on the same bf16-rounded weights. Measured over seeds 0-7 at
    this size: loss off by up to 0.011 (band 0.04), the logits' largest
    error up to 0.055 standard deviations of the reference's logits (band
    0.15) for either head: a float32 -> bf16 slip in the coefficients or a
    stream summed in bf16 is several times that, a wrong wiring orders of
    magnitude."""
    sym = xing4.from_config(SHARE, seq_len=T, dtype="bfloat16")
    params = {k: np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32))
              for k, v in _params(sym, 10 + seed).items()}
    tokens, labels = _batch(20 + seed)
    want = ref.forward(params, tokens, SHARE, labels=labels)
    mod = _module(sym, params, for_training=False)
    mod.forward(_data_batch(tokens, labels), is_train=False)
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert abs(outs[3].mean() - float(want["loss_main"])) < 0.04
    assert abs(outs[4].mean() - float(want["loss_mtp"])) < 0.04
    for name, key in (("lm_head_f32_output", "logits"),
                      ("mtp0_lm_head_f32_output", "mtp_logits")):
        got, = _internals(sym, params, [name], tokens, labels)
        ref_logits = np.asarray(want[key]).reshape(got.shape)
        assert np.abs(got - ref_logits).max() / ref_logits.std() < 0.15, key
    # float32 inside: the coefficients' outputs and the losses
    types = dict(zip(sym.get_internals().list_outputs(),
                     sym.get_internals().infer_type(
                         data=np.float32, softmax_label=np.float32)[1]))
    for name in ("layer0_attn_hc_pre", "layer0_attn_hc_res",
                 "lm_head_f32_output", "mtp0_lm_head_f32_output"):
        assert types[name] == np.float32, name
    assert types["layer0_attn_hc_write_output"] == jnp.bfloat16


def test_fused_fit_follows_the_reference_and_holds_each_weight_once():
    """``Module.fit(kvstore='device', mesh dp=1)`` — the fused step — on
    the share: two steps follow the reference's own SGD with momentum on
    ONE dict of weights, the optimizer's state has one entry an argument
    (the embedding and the head one each, though two nodes read them),
    and the loss falls."""
    sym = xing4.from_config(SHARE, seq_len=T)
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
    state = mod._fused_opt
    assert sorted(state) == sorted(params)
    got, _ = mod.get_params()
    assert sorted(got) == sorted(params)
    for name in ("embed_weight", "lm_head_weight", "mtp0_attn_hc_phi"):
        leaf = state[name]
        leaf = leaf[0] if isinstance(leaf, (tuple, list)) else leaf
        assert np.asarray(leaf).shape == params[name].shape
    for name in params:  # no gradient and no rule moves the bias
        if "select_bias" in name:
            np.testing.assert_array_equal(got[name].asnumpy(), params[name])


# -- ONE embedding and ONE head, read twice -----------------------------------

def _twin(sym):
    """The symbol with the prediction module reading an embedding and a
    head of its OWN (``mtp0_embed_weight``, ``mtp0_lm_head_weight``): the
    same graph but for the sharing."""
    graph = json.loads(sym.tojson())
    nodes = graph["nodes"]
    for reader, shared in (("mtp0_embed", "embed_weight"),
                           ("mtp0_lm_head", "lm_head_weight")):
        node, = [n for n in nodes if n["name"] == reader]
        entry, = [e for e in node["inputs"] if nodes[e[0]]["name"] == shared]
        nodes.append({"op": "null", "name": "mtp0_" + shared,
                      "attr": dict(nodes[entry[0]].get("attr", {})),
                      "inputs": []})
        entry[0] = len(nodes) - 1
    graph["arg_nodes"] = [i for i, n in enumerate(nodes) if n["op"] == "null"]
    graph["node_row_ptr"] = list(range(len(nodes) + 1))
    return mx.sym.load_json(json.dumps(graph))


def _loss_and_grads(sym, params, tokens, labels):
    mod = _module(sym, params)
    mod.forward(_data_batch(tokens, labels), is_train=True)
    mod.backward()
    grads = mod._exec_group.execs[0].grad_dict
    return (mod.get_outputs()[0].asnumpy(),
            {k: grads[k].asnumpy() for k in params})


@pytest.mark.parametrize("shared", ["embed_weight", "lm_head_weight"])
def test_a_shared_arguments_gradient_is_the_sum_of_its_two_uses(shared):
    sym = xing4.from_config(SHARE, seq_len=T)
    names = sym.list_arguments()
    assert names.count(shared) == 1 and len(names) == len(set(names))
    nodes = json.loads(sym.tojson())["nodes"]
    readers = [n["name"] for n in nodes if any(
        nodes[i[0]]["name"] == shared for i in n["inputs"])]
    assert readers == {"embed_weight": ["embed", "mtp0_embed"],
                       "lm_head_weight": ["lm_head", "mtp0_lm_head"]}[shared]
    params = _params(sym, 5)
    tokens, labels = _batch(6)
    twin = _twin(sym)
    assert "mtp0_" + shared in twin.list_arguments()
    twin_params = dict(params, mtp0_embed_weight=params["embed_weight"],
                       mtp0_lm_head_weight=params["lm_head_weight"])
    loss, got = _loss_and_grads(sym, params, tokens, labels)
    twin_loss, twin_got = _loss_and_grads(twin, twin_params, tokens, labels)
    np.testing.assert_array_equal(loss, twin_loss)
    parts = [twin_got[shared], twin_got["mtp0_" + shared]]
    _close(got[shared], parts[0] + parts[1], shared, ulps=64)
    for part in parts:  # neither use is the whole
        assert (np.abs(got[shared] - part).max()
                > 0.01 * np.abs(got[shared]).max())


def test_params_and_a_checkpoint_hold_a_weight_once_and_give_the_loss_back(
        tmp_path):
    sym = xing4.from_config(SHARE, seq_len=T)
    params = _params(sym, 9)
    tokens, labels = _batch(10)
    mod = _module(sym, params, for_training=False)
    mod.forward(_data_batch(tokens, labels), is_train=False)
    first = [o.asnumpy() for o in mod.get_outputs()]
    arg_params, aux_params = mod.get_params()
    assert sorted(arg_params) == sorted(params) and not aux_params
    prefix = str(tmp_path / "xing4")
    mx.model.save_checkpoint(prefix, 3, sym, arg_params, aux_params)
    loaded_sym, loaded_args, loaded_aux = mx.model.load_checkpoint(prefix, 3)
    assert sorted(loaded_args) == sorted(params) and not loaded_aux
    assert loaded_sym.list_arguments() == sym.list_arguments()
    loaded = mx.mod.Module(loaded_sym, context=mx.cpu(0))
    loaded.bind(data_shapes=[("data", (BATCH, T))],
                label_shapes=[("softmax_label", (BATCH, T))],
                for_training=False)
    loaded.set_params(loaded_args, loaded_aux)
    loaded.forward(_data_batch(tokens, labels), is_train=False)
    for a, b in zip(first, loaded.get_outputs()):
        np.testing.assert_array_equal(a, b.asnumpy())


# -- the mixing's two ops -----------------------------------------------------

def _stream(seed, tokens=48, c=16, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    n = STREAMS
    x = jnp.asarray(rng.randn(tokens, n * c), dtype)
    phi = jnp.asarray(rng.randn(n * (n + 2), n * c) * 0.3, dtype)
    bias = jnp.asarray(rng.randn(n * (n + 2)) * 0.5, jnp.float32)
    alpha = jnp.asarray([0.7, 1.1, 0.9], jnp.float32)
    return x, phi, bias, alpha


def _ref_coefficients(x, phi, bias, alpha, **over):
    cfg = dict(CFG, **over)
    n = STREAMS
    with jax.default_matmul_precision("highest"):
        return ref.hyper_coefficients(
            x.astype(jnp.float32).reshape(1, x.shape[0], n, -1),
            phi.astype(jnp.float32), bias, alpha, cfg)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_coefficients_are_the_recurrence_and_the_carry_doubly_stochastic(
        seed):
    """``HyperCoeff`` (tokens on the last axis, one matrix product, sums
    written stream by stream) against the recurrence as the reference
    writes it a token; and what 20 iterations leave: rows end exact to
    ``hc_eps`` and float32 rounding (under 5e-6), columns within 0.05 at
    these deliberately far-from-uniform pre-exponentials (1.0e-2 to 1.6e-2
    on the three seeds; the model's stated initial, diagonal-heavy ones
    leave 4.6e-5, which the model tests read)."""
    x, phi, bias, alpha = _stream(seed)
    pre, post, res, err = hyper_coeff(x, phi, bias, alpha, STREAMS, 20,
                                      1e-6, (-30.0, 30.0))
    assert pre.shape == (1, STREAMS, 48) and post.shape == (STREAMS, 48)
    assert res.shape == (STREAMS, STREAMS, 48) and err.shape == (1,)
    want_pre, want_post, want_res = _ref_coefficients(x, phi, bias, alpha)
    _close(pre[0].T, want_pre[0], "pre", ulps=16)
    _close(post.T, want_post[0], "post", ulps=16)
    _close(jnp.transpose(res, (2, 0, 1)), want_res[0], "res", rtol=1e-4,
           ulps=64)
    rows = np.abs(np.asarray(res).sum(axis=1) - 1).max()
    cols = np.abs(np.asarray(res).sum(axis=0) - 1).max()
    assert rows < 5e-6 and cols < 0.05, (rows, cols)
    assert float(err[0]) == pytest.approx(max(rows, cols), rel=1e-3)
    assert (np.asarray(res) > 0).all()
    # one iteration leaves far more: the 20 do the work
    once = hyper_coeff(x, phi, bias, alpha, STREAMS, 1, 1e-6,
                       (-30.0, 30.0))[3]
    assert float(once[0]) > 5 * float(err[0])
    # the mean over the streams is carried unchanged by an exact carry
    exact = sinkhorn(jnp.exp(jnp.asarray(
        np.random.RandomState(seed).randn(STREAMS, STREAMS, 8),
        jnp.float32)), 200, 0.0)
    v = np.random.RandomState(seed + 1).randn(STREAMS, 8)
    carried = np.einsum("ijt,jt->it", np.asarray(exact), v)
    _close(carried.mean(axis=0), v.mean(axis=0), "mean carried", rtol=1e-4,
           ulps=64)


def test_the_clamp_binds_where_the_pre_exponential_passes_it():
    """A carry whose pre-exponentials reach +-100 at ``alpha_res`` 40: the
    op and the reference clamp them to +-30 before ``exp`` (unclamped,
    ``exp(100)`` is inf in float32 and the carry NaN), and agree; a
    narrower clamp gives another carry, so the attribute is read."""
    x, phi, bias, _ = _stream(3)
    alpha = jnp.asarray([0.7, 1.1, 40.0], jnp.float32)
    raw = np.asarray(_ref_pre_exponential(x, phi, bias, alpha))
    assert raw.max() > 60 and raw.min() < -60
    res = hyper_coeff(x, phi, bias, alpha, STREAMS, 20, 1e-6,
                      (-30.0, 30.0))[2]
    assert np.isfinite(np.asarray(res)).all()
    want = _ref_coefficients(x, phi, bias, alpha)[2]
    _close(jnp.transpose(res, (2, 0, 1)), want[0], "clamped carry",
           rtol=1e-4, ulps=64)
    loose = hyper_coeff(x, phi, bias, alpha, STREAMS, 20, 1e-6,
                        (-1e4, 1e4))[2]
    assert not np.isfinite(np.asarray(loose)).all()
    tight = hyper_coeff(x, phi, bias, alpha, STREAMS, 20, 1e-6,
                        (-5.0, 5.0))[2]
    assert np.abs(np.asarray(tight) - np.asarray(res)).max() > 1e-3
    _close(jnp.transpose(tight, (2, 0, 1)), _ref_coefficients(
        x, phi, bias, alpha, mhc_h_res_clamp_min=-5,
        mhc_h_res_clamp_max=5)[2][0], "tight clamp", rtol=1e-4, ulps=64)


def _ref_pre_exponential(x, phi, bias, alpha):
    n = STREAMS
    flat = x.astype(jnp.float32)
    xbar = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + 1e-6)
    with jax.default_matmul_precision("highest"):
        return alpha[2] * (xbar @ phi.T)[:, 2 * n:] + bias[2 * n:]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_mixing_reads_and_writes_the_streams(dtype):
    """``HyperMix`` against the einsums of the reference, forward and
    gradient. bf16: products and sums float32, ONE rounding of the result
    (half a bf16 ulp, 2^-9 relative)."""
    dtype = jnp.dtype(dtype)
    n, c = STREAMS, 16
    x, phi, bias, alpha = _stream(4, c=c, dtype=dtype)
    y = jnp.asarray(np.random.RandomState(5).randn(48, c), dtype)
    pre, post, res, _ = hyper_coeff(x, phi, bias, alpha, n, 20, 1e-6,
                                    (-30.0, 30.0))
    x4 = x.astype(jnp.float32).reshape(48, n, c)
    want_read = jnp.einsum("jt,tjc->tc", pre[0], x4)
    want_write = (jnp.einsum("ijt,tjc->tic", res, x4)
                  + post.T[:, :, None] * y.astype(jnp.float32)[:, None, :])
    read = hyper_mix(x, pre)
    write = hyper_mix(x, res, y, post)
    assert read.dtype == write.dtype == dtype
    assert read.shape == (48, c) and write.shape == (48, n * c)
    tol = dict(rtol=2.0 ** -8, ulps=0) if dtype == jnp.bfloat16 else {}
    _close(read.astype(jnp.float32), want_read, "read", **tol)
    _close(write.astype(jnp.float32), want_write.reshape(48, n * c),
           "write", **tol)
    if dtype == jnp.bfloat16:
        return
    cot = jnp.asarray(np.random.RandomState(6).randn(48, n * c), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(hyper_mix(*a) * cot), (0, 1, 2, 3))(
        x, res, y, post)
    want = jax.grad(lambda x, res, y, post: jnp.sum((
        jnp.einsum("ijt,tjc->tic", res, x.reshape(48, n, c))
        + post.T[:, :, None] * y[:, None, :]).reshape(48, n * c) * cot),
        (0, 1, 2, 3))(x, res, y, post)
    for g, w, name in zip(got, want, ("dx", "dres", "dy", "dpost")):
        _close(g, w, name, ulps=32)


def test_the_ops_check_their_inputs_and_say_their_types():
    data = mx.sym.Variable("data")
    coeff = mx.contrib.sym.HyperCoeff(data, streams=4, name="hc")
    args, outs, _ = coeff.infer_shape(data=(48, 64))
    assert args == [(48, 64), (24, 64), (24,), (3,)]
    assert outs == [(1, 4, 48), (4, 48), (4, 4, 48), (1,), (48, 16),
                    (48, 64)]
    assert coeff.list_arguments() == ["data", "hc_phi", "hc_bias", "hc_alpha"]
    arg_types, out_types, _ = coeff.infer_type(data=jnp.bfloat16)
    assert arg_types == [jnp.bfloat16, jnp.bfloat16, np.float32, np.float32]
    assert out_types == [np.float32] * 4 + [jnp.bfloat16] * 2
    with pytest.raises(Exception, match="streams"):
        coeff.infer_shape(data=(48, 66))
    read = mx.contrib.sym.HyperMix(data, coeff[0], name="read")
    assert read.infer_shape(data=(48, 64))[1] == [(48, 16)]
    write = mx.contrib.sym.HyperMix(data, coeff[2], read, coeff[1],
                                    with_add=True, name="write")
    assert write.infer_shape(data=(48, 64))[1] == [(48, 64)]
    assert write.infer_type(data=jnp.bfloat16)[1] == [jnp.bfloat16]
    from mxnet_tpu.executor import op_class
    assert op_class("_contrib_HyperCoeff") == op_class(
        "_contrib_HyperMix") == "hc"


# -- YaRN's table and the score scale ----------------------------------------

def test_yarn_blends_the_frequencies_as_written_by_hand():
    """The published widths: 64 rotary lanes, theta 1e4, factor 64 over
    4096 positions, ramp between 32 and 1 rotations. Pair i turns 4096 /
    (2 pi 1e4^(i/32)) times over the original context: more than 32 up to
    pair 10 (floor(10.48)), fewer than 1 from pair 23 on (ceil(22.53)).
    So pairs 0..10 keep ``theta^(-i/32)``, pairs 23..31 take it over 64,
    pair 10 + k blends by k / 13."""
    got = rope_inv_freq((1e4, 64.0, 32.0, 1.0, 4096.0), 64)
    plain = 1e4 ** (-np.arange(32) / 32.0)
    np.testing.assert_array_equal(rope_inv_freq(1e4, 64), 1.0 / (
        1e4 ** (np.arange(0, 64, 2, dtype=np.float64) / 64)))
    by_hand = plain.copy()
    for i in range(32):
        k = min(max((i - 10) / 13.0, 0.0), 1.0)
        by_hand[i] = plain[i] * (1 - k) + plain[i] / 64 * k
    np.testing.assert_allclose(got, by_hand, rtol=1e-12)
    np.testing.assert_array_equal(got[:11], rope_inv_freq(1e4, 64)[:11])
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-12)
    np.testing.assert_allclose(
        got, ref.yarn_inv_freq(64, 1e4, dict(YARN, **{
            "original_max_position_embeddings": 4096})), rtol=1e-12)
    # the score scale: 192^-0.5 x (0.1 ln 64 + 1)^2
    m = 0.1 * math.log(64) + 1
    assert m == pytest.approx(1.4159, abs=1e-4)
    assert m * m == pytest.approx(2.0048, abs=1e-4)
    assert xing4.score_scale(192, 64.0, 1.0) == pytest.approx(
        192 ** -0.5 * m * m, rel=1e-12)
    assert xing4.score_scale(192) == 192 ** -0.5
    assert ref.score_scale(dict(CFG, qk_nope_head_dim=128,
                                qk_rope_head_dim=64)) == pytest.approx(
        192 ** -0.5 * m * m, rel=1e-12)


def _latent_inputs(seed, heads, nope, rope_dim, dv, latent, t, dtype):
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.5, dtype)
    return (mk(BATCH, t, heads * (nope + rope_dim)),
            mk(BATCH, t, latent + rope_dim),
            jnp.asarray(1 + 0.1 * rng.randn(latent), dtype),
            mk(heads * (nope + dv), latent) * 0.3)


@pytest.mark.parametrize("path,interleave", [
    ("composed", True), ("composed", False), ("kernel", True)])
def test_scaled_latent_attention_matches_the_reference(monkeypatch, path,
                                                       interleave):
    """``LatentAttention(rope_scaling=, score_scale=)`` against the
    reference's attention (the published YaRN code's order: blended
    table, ``rotate_half`` after the de-interleave, scores times ``m^2 /
    sqrt(d)``), forward and gradients; on the composed form at the tiny
    widths and, through the Pallas interpreter, on the latent pair and
    the query pass at lane-whole heads (128 + 64 / 128), which is what
    the cell runs."""
    from mxnet_tpu.ops import kernels as pk

    kernel = path == "kernel"
    heads, nope, rope_dim, dv, latent, t = (
        (2, 128, 64, 128, 32, 128) if kernel
        else (HEADS, NOPE, ROPE, DV, LATENT, T))
    if kernel:
        monkeypatch.setattr(pk.common, "INTERPRET", True)
    cfg = dict(CFG, qk_nope_head_dim=nope, qk_rope_head_dim=rope_dim,
               v_head_dim=dv, kv_lora_rank=latent,
               rope_interleave=interleave)
    scaling = (64.0, 32.0, 1.0, 16.0)
    args = _latent_inputs(7, heads, nope, rope_dim, dv, latent, t,
                          jnp.float32)
    telemetry.reset()
    telemetry.enable()
    try:
        def run(*a):
            return latent_attention(
                *a, num_heads=heads, rope_dim=rope_dim, v_head_dim=dv,
                theta=1e4, eps=1e-6, interleave=interleave,
                rope_scaling=scaling, score_scale=ref.score_scale(cfg))

        def plain(*a):
            with jax.default_matmul_precision("highest"):
                return ref.latent_attention(*a, cfg)

        cot = jnp.asarray(np.random.RandomState(8).randn(
            BATCH, t, heads * dv), jnp.float32)
        got, got_g = jax.value_and_grad(
            lambda *a: jnp.sum(run(*a) * cot), (0, 1, 2, 3))(*args)
        sites = telemetry.REGISTRY.get("attention.latent_lowerings")
        assert sites.value(
            heads=heads, latent=latent, rope=rope_dim, nope=nope, dv=dv,
            impl=path, rope_factor=64.0,
            score_scale="%.6g" % ref.score_scale(cfg)) == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    want, want_g = jax.value_and_grad(
        lambda *a: jnp.sum(plain(*a) * cot), (0, 1, 2, 3))(*args)
    tol = dict(rtol=2e-4, ulps=256) if kernel else dict(ulps=32)
    _close(run(*args), plain(*args), "output", **tol)
    for g, w, name in zip(got_g, want_g, ("dq", "dlatent", "dgamma", "dup")):
        _close(g, w, name, **tol)
    # both attributes are read: without either the result is another
    bare = latent_attention(*args, num_heads=heads, rope_dim=rope_dim,
                            v_head_dim=dv, theta=1e4, eps=1e-6,
                            interleave=interleave, rope_scaling=scaling)
    unscaled = latent_attention(*args, num_heads=heads, rope_dim=rope_dim,
                                v_head_dim=dv, theta=1e4, eps=1e-6,
                                interleave=interleave,
                                score_scale=ref.score_scale(cfg))
    for other in (bare, unscaled):
        assert np.abs(np.asarray(other - run(*args))).max() > 1e-3


# the traced program (forward and gradient, jaxpr text with addresses
# struck out) of ``latent_attention`` at its DEFAULT new attributes, taken
# on the parent commit 418218d with this very function: the three older
# users' lowering did not move. (name, heads, nope, rope, dv, latent,
# theta, interleave, extra attributes), then {(T, dtype): digest}
OLDER_USERS = {
    "kanana2": ((32, 128, 64, 128, 512, 1e6, True, {}),
                {(64, "float32"): "3528974055e2827d",
                 (256, "bfloat16"): "08647dc8f9d5ec18"}),
    "kimi_linear": ((32, 128, 64, 128, 512, 1e4, True, {"rotary": False}),
                    {(64, "float32"): "918a863dfb045b14",
                     (256, "bfloat16"): "700847db8c4b5ef5"}),
    "dots3": ((16, 128, 64, 128, 512, 1e4, False, {"latent_scale": 0.5}),
              {(64, "float32"): "9b9e5f76058b1b13",
               (256, "bfloat16"): "f25ba7dd66a1f68a"}),
}


@pytest.mark.parametrize("t,dtype", [(64, "float32"), (256, "bfloat16")])
@pytest.mark.parametrize("user", sorted(OLDER_USERS))
def test_latent_attention_at_default_attributes_is_the_parents_program(
        user, t, dtype):
    (h, n, r, dv, width, theta, interleave, extra), digests = OLDER_USERS[
        user]
    shapes = [jax.ShapeDtypeStruct(s, jnp.dtype(dtype)) for s in (
        (1, t, h * (n + r)), (1, t, width + r), (width,),
        (h * (n + dv), width))]

    def fn(*args):
        def f(*a):
            return latent_attention(
                *a, num_heads=h, rope_dim=r, v_head_dim=dv, theta=theta,
                eps=1e-6, interleave=interleave, **extra).astype(
                    jnp.float32).sum()
        return jax.value_and_grad(f, (0, 1, 2, 3))(*args)

    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*shapes)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digests[
        (t, dtype)]


# -- the shares add up --------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_expert_layer():
    """THE SHARE-SUM TEST, at the cell's own cut (8 ways, the shared
    expert whole): eight shares of one expert each route over all 8 and
    compute their own expert's part; the shared expert is what every chip
    computes alike and counts once. The sum is the uncut reference's
    layer."""
    rng = np.random.RandomState(5)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    d, experts, hidden, tokens = 64, 8, 32, BATCH * T
    x = f32(rng.randn(tokens, d))
    w = {"gate_w": f32(rng.randn(d, experts) * 0.3),
         "w_gate_up": f32(rng.randn(experts, d, 2 * hidden) * 0.1),
         "w_down": f32(rng.randn(experts, hidden, d) * 0.1),
         "select_bias": f32(rng.randn(experts) * 0.05)}
    shared = [f32(rng.randn(*s) * 0.1) for s in ((32, d), (32, d), (d, 32))]
    whole, counts, _ = ref.moe(
        x, w["gate_w"], w["w_gate_up"], w["w_down"], w["select_bias"], 2,
        True, 0, 2.0)
    want = ref.swiglu(x, *shared) + whole
    total = ref.swiglu(x, *shared)              # counted once
    for offset in range(experts):
        held = dict(w, w_gate_up=w["w_gate_up"][offset:offset + 1],
                    w_down=w["w_down"][offset:offset + 1])
        part, part_counts = topk_moe(
            held, x, 2, norm_topk_prob=True, scoring="sigmoid",
            expert_offset=offset, share_rows_bound=tokens * 2,
            routed_scale=2.0)
        np.testing.assert_array_equal(np.asarray(part_counts),
                                      np.asarray(counts))
        mine, _, _ = ref.moe(
            x, w["gate_w"], held["w_gate_up"], held["w_down"],
            w["select_bias"], 2, True, offset, 2.0)
        _close(part, mine, "share at %d" % offset)
        total = total + part
    _close(total, want, "sum of the eight shares", ulps=32)
    assert float(jnp.abs(ref.swiglu(x, *shared)).max()) > 1e-2


# -- what the model states, counts and refuses --------------------------------

def test_the_model_states_its_initialisation_and_counts_what_it_traces():
    telemetry.reset()
    telemetry.enable()
    try:
        sym = xing4.from_config(SHARE, seq_len=T)
        mod = mx.mod.Module(sym, context=mx.cpu(0))
        mod.bind(data_shapes=[("data", (BATCH, T))],
                 label_shapes=[("softmax_label", (BATCH, T))],
                 for_training=False)
        mx.random.seed(5)
        mod.init_params(initializer=mx.init.Normal(sigma=0.02))
        tokens, labels = _batch(6)
        for _ in range(2):  # a second step traces nothing
            mod.forward(_data_batch(tokens, labels), is_train=False)
        # 3 blocks x 2 sub-layers, one per node and lowering
        assert telemetry.REGISTRY.get("lm.hc_sublayers").value(
            streams=4, iters=20) == 6
        assert telemetry.REGISTRY.get("lm.mtp_modules").value(ahead=2) == 1
        # the embedding and the head have two readers each; the label has
        # four (both heads' picks, the module's embedding, the shift)
        assert telemetry.REGISTRY.get("lm.shared_argument_uses").value() == 4
        assert telemetry.REGISTRY.get("attention.latent_lowerings").value(
            heads=HEADS, latent=LATENT, rope=ROPE, nope=NOPE, dv=DV,
            impl="composed", query_latent=QLATENT, rope_factor=64.0,
            score_scale="%.6g" % ref.score_scale(CFG)) == 3
        first = [o.asnumpy() for o in mod.get_outputs()]
    finally:
        telemetry.disable()
        telemetry.reset()
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert 0.9 < got["embed_weight"].std() < 1.1
    assert 0.015 < got["layer1_attn_hc_phi"].std() < 0.025
    assert got["layer1_attn_hc_phi"].shape == (24, 4 * 64)
    assert (got["mtp0_ffn_hc_alpha"] == np.float32(0.01)).all()
    bias = got["layer0_ffn_hc_bias"]
    assert not bias[:8].any()
    np.testing.assert_array_equal(bias[8:].reshape(4, 4), 4 * np.eye(4))
    assert not got["mtp0_moe_select_bias"].any()
    assert (got["layer0_q_latent_norm_gamma"] == 1).all()
    assert got["mtp0_proj_weight"].shape == (64, 128)
    assert "layer0_moe_gate_weight" not in got  # the dense layer
    # at these weights the streams start as copies, the carry is
    # diagonal-heavy and nearly exact, the two losses near each other
    assert first[5][0] < 1e-4
    assert abs(first[3].mean() - first[4].mean()) < 0.2
    assert abs(first[3].mean() - (math.log(VOCAB) + 0.05)) < 0.3


def test_the_scopes_name_the_mixing_the_query_latent_and_the_module():
    """What a trace files the new device ops under. A node's ops are traced
    under ``<op class>/<node name>``: ``hc/layer<i>_attn_hc`` and
    ``_ffn_hc`` (the coefficients and, since PR 70, the read, one pass over
    the stream; ``_write`` the mixing's other node, which reads the stream
    off the first), the query latent's three nodes ``*_q_latent_*``, every
    node of the module ``mtp0_*``; inside the ``hc`` nodes the ops' own scopes
    ``hc_coeff``, ``hc_sinkhorn`` and ``hc_mix``."""
    from mxnet_tpu.executor import op_class

    nodes = json.loads(xing4.from_config(SHARE, seq_len=T).tojson())["nodes"]
    scopes = {"%s/%s" % (op_class(n["op"]), n["name"])
              for n in nodes if n["op"] != "null"}
    assert {"hc/layer0_attn_hc", "hc/layer1_ffn_hc", "hc/layer1_attn_hc_write",
            "hc/mtp0_ffn_hc_write", "fc/layer0_q_latent_a_proj",
            "norm/layer1_q_latent_norm", "fc/mtp0_q_latent_b_proj",
            "fc/mtp0_proj", "norm/mtp0_embed_norm", "norm/mtp0_hidden_norm",
            "embed/mtp0_embed", "fc/mtp0_lm_head", "norm/mtp0_final_norm",
            "moe/mtp0_moe", "attn/mtp0_attn", "other/mtp0_lm_head_pick",
            "act/mtp0_stream_sum", "act/stream_sum", "loss/loss"} <= scopes
    assert len([s for s in scopes if s.startswith("hc/")]) == 3 * 2 * 2
    # the norm in front of the sub-layer reads the coefficient node's
    # fifth result (the read), the write its sixth (the stream)
    by_name = {n["name"]: n for n in nodes}
    coeff = nodes.index(by_name["layer1_attn_hc"])
    assert by_name["layer1_attn_norm"]["inputs"][0][:2] == [coeff, 4]
    assert by_name["layer1_attn_hc_write"]["inputs"][0][:2] == [coeff, 5]
    # every node of the module says so but those ``lm_blocks`` leaves
    # unnamed in every LM symbol: the shape-only ones of the head's loss,
    # and the shared SwiGLU's activation and product and its sum with the
    # routed part (elementwise, fused into the products round them)
    ops = [(n["name"], n["op"]) for n in nodes if n["op"] != "null"]
    names = [name for name, _ in ops]
    module = ops[names.index("mtp0_embed_ids"):
                 names.index("mtp0_lm_head_mean") + 1]
    assert len(module) > 40
    assert {op for name, op in module if not name.startswith("mtp0_")} <= {
        "Reshape", "_rminus_scalar", "slice_axis", "Activation",
        "elemwise_mul", "elemwise_add"}
    x, phi, bias, alpha = _stream(0)
    text = jax.jit(lambda *a: hyper_coeff(
        *a, STREAMS, 20, 1e-6, (-30.0, 30.0))).lower(
            x, phi, bias, alpha).as_text(debug_info=True)
    assert "hc_coeff" in text and "hc_sinkhorn" in text
    pre = jnp.ones((1, STREAMS, 48), jnp.float32)
    assert "hc_mix" in jax.jit(hyper_mix).lower(x, pre).as_text(
        debug_info=True)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", None), ("n_group", 2), ("topk_group", 2),
    ("rope_scaling", {"type": "linear", "factor": 4}),
    ("rope_scaling", dict(YARN, mscale=0.7)), ("attention_bias", True),
    ("topk_method", "greedy"), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("moe_layer_freq", 2),
    ("num_nextn_predict_layers", 2), ("scoring_func", "softmax"),
    ("num_key_value_heads", 2), ("ep_size", 8)])
def test_from_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        xing4.from_config(dict(CFG, **{key: value}), seq_len=T)


def test_from_config_reads_the_published_keys():
    """No scaling and no module are forms it builds too; ``rope_scaling``
    null is the plain rotation at the plain scale (the attributes stay at
    their defaults: ``models/kanana2.py``'s attention behind a query
    latent)."""
    plain = xing4.from_config(dict(CFG, rope_scaling=None,
                                   num_nextn_predict_layers=0), seq_len=T)
    assert plain.list_outputs() == [
        "loss_output", "layer1_expert_count_output", "loss_part_output",
        "hc_res_sum_err_output"]
    attn, = [n for n in json.loads(plain.tojson())["nodes"]
             if n["name"] == "layer0_attn"]
    assert "rope_scaling" not in attn["attr"] or attn["attr"][
        "rope_scaling"] == "()"
    assert float(attn["attr"].get("score_scale", 0)) == 0
    scaled, = [n for n in json.loads(xing4.from_config(
        CFG, seq_len=T).tojson())["nodes"] if n["name"] == "layer0_attn"]
    assert float(scaled["attr"]["score_scale"]) == pytest.approx(
        ref.score_scale(CFG))
    assert "64.0" in scaled["attr"]["rope_scaling"]


def test_the_two_copies_of_the_reference_are_one_text():
    import os

    import mxnet_tpu.models.xing4_reference as theirs
    ours = os.path.join(os.path.dirname(__file__), "..", "bench",
                        "reference", "xing4.py")
    with open(ours) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()
