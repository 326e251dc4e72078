"""dots3-note-prev on the normal path against its plain reference.

``models/dots3.py`` (an ``mx.sym`` graph of ``RMSNorm``, a query latent,
``LatentAttention`` in two geometries a ``layer_types`` entry picks — full
layers whose ``KeyIndexer`` chooses the keys, window layers over a latent
of their own — a sigmoid gate a head, shared experts beside ``TopKMoE``)
through ``Module.forward/backward`` and ``Module.fit``'s fused step,
against ``models/dots3_reference.py`` (plain float32 ``jax.numpy``:
materialised scores under explicit masks, ``jax.lax.top_k`` on the masked
index scores, a loop over the experts held) on seeded weights at a tiny
size: hidden 64, full layers of 4 heads of 16 + 8 / 16 from latents of 32
and 16 choosing 12 keys by a 4-head indexer of 16, window layers of 2
heads of 24 + 8 / 16 from latents of 32 under a window of 9, 16 experts
top-3 of width 32, 1 shared, T 32.

Tolerances as in ``tests/test_kanana2.py``: both sides are float32 and
only the order of summation differs (``_close``). The index scores of the
two sides differ in their last bits too, and a selection is a
discontinuous function of them: the seeds below have no two scores of a
row within 1e-5 at the rank that decides.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import dots3, dots3_reference as ref
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.kernels import reference_attention
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import topk_moe

T, BATCH = 32, 2
F, S = "full_attention", "sliding_attention"
CFG = dict(
    model_type="dots3_note", hidden_size=64, num_hidden_layers=5,
    layer_types=[F, F, S, S, S], first_k_dense_replace=1, moe_layer_freq=1,
    num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=32, kv_lora_rank=16,
    rope_theta=80000000, swa_num_attention_heads=2,
    swa_num_key_value_heads=2, swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_q_lora_rank=32,
    swa_kv_lora_rank=32, swa_rope_theta=50000, sliding_window_size=9,
    index_n_heads=4, index_head_dim=16, index_topk=12,
    apply_mla_qkv_lora_rescale=True, attention_gate_type="headwise",
    swa_attention_gate_type="headwise", intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, n_shared_experts=1,
    num_experts_per_tok=3, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="noaux_tc", routed_scaling_factor=1, rms_norm_eps=1e-5,
    vocab_size=512, hidden_act="silu", attention_bias=False,
    tie_word_embeddings=False, rope_scaling=None,
    max_position_embeddings=T)
# one chip's share of the same model: 4 of the 16 experts from the 8th
# on in a buffer that holds every row, half the heads of either geometry,
# half the dense columns
SHARE = dict(CFG, n_routed_experts=4, num_attention_heads=2,
             num_key_value_heads=2, swa_num_attention_heads=1,
             swa_num_key_value_heads=1, share=dict(
                 experts_of=16, expert_offset=8,
                 share_rows_bound=BATCH * T * 3, dense_columns_held=48))
EXPERT_LAYERS, FULL_LAYERS = 4, 2
SELECTED = sum(min(t + 1, CFG["index_topk"]) for t in range(T))
FILE = os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                    "dots3_note_prev.json")


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, sigma=0.08, t=T):
    """Seeded weights under the symbol's argument names: Normal(sigma),
    a unit embedding as the model states it, gammas near 1, the
    indexer's beta and the selection biases away from 0."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=(BATCH, t), softmax_label=(BATCH, t))
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = {"embed_weight": 1.0, "bias": 0.05, "beta": 0.05}.get(
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
    sym = dots3.from_config(cfg, seq_len=T)
    params = _params(sym, 1)
    tokens, labels = _batch(2)
    want = ref.forward(params, tokens, cfg, labels=labels)
    loss, grads = ref.loss_and_grads(params, tokens, labels, cfg)

    mod = _module(sym, params)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert len(outs) == 1 + EXPERT_LAYERS + FULL_LAYERS
    _close(outs[0], want["per_sequence"], "per-sequence loss")
    _close(outs[0].mean(), loss, "loss")
    for layer in range(EXPERT_LAYERS):
        # over all 16 of the router's experts, share or not
        np.testing.assert_array_equal(
            outs[1 + layer], np.asarray(want["expert_counts"][layer]))
        assert outs[1 + layer].sum() == BATCH * T * 3
    for kept in outs[1 + EXPERT_LAYERS:]:   # pairs kept a sequence
        np.testing.assert_array_equal(kept, [SELECTED] * BATCH)
    got = mod._exec_group.execs[0].grad_dict
    assert set(grads) == set(params)
    for name, want_g in grads.items():
        # the head sums the sequences' losses (MXNet's convention)
        _close(got[name].asnumpy() / BATCH, want_g, name, ulps=16)
        if "select_bias" in name or "_index_" in name:
            # they move a choice and nothing else: no gradient
            assert not np.asarray(want_g).any()
            assert not got[name].asnumpy().any()
        elif any(part in name for part in (
                "latent_gamma", "shared", "q_a_norm", "attn_gate_proj")):
            assert np.abs(np.asarray(want_g)).max() > 1e-6, name

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
    reference's own SGD with momentum, the loss falls, and what has no
    gradient (the indexer, the selection biases) stands still."""
    sym = dots3.from_config(SHARE, seq_len=T)
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
        if "select_bias" in name or "_index_" in name:
            np.testing.assert_array_equal(got[name].asnumpy(), params[name])


def test_the_model_states_its_own_initialisation_and_counts_its_call_sites():
    telemetry.reset()
    telemetry.enable()
    try:
        sym = dots3.from_config(SHARE, seq_len=T)
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
        latent = telemetry.REGISTRY.get("attention.latent_lowerings")
        assert latent.value(heads=2, latent=16, rope=8, nope=16, dv=16,
                            impl="composed", select=1, gate="headwise",
                            query_latent=32) == 2
        assert latent.value(heads=1, latent=32, rope=8, nope=24, dv=16,
                            impl="composed", window=9, gate="headwise",
                            query_latent=32) == 3
        assert telemetry.total("attention.latent_lowerings") == 5
        index = telemetry.REGISTRY.get("attention.index_lowerings")
        assert index.value(heads=4, width=16, topk=12, rows=T,
                           impl="jnp") == 2
        mod.forward(batch, is_train=False)
        assert telemetry.total("attention.latent_lowerings") == 5
        assert telemetry.total("attention.index_lowerings") == 2
    finally:
        telemetry.disable()
        telemetry.reset()
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert 0.9 < got["embed_weight"].std() < 1.1
    assert 0.015 < got["layer1_q_b_proj_weight"].std() < 0.025
    assert 0.015 < got["layer0_index_q_weight"].std() < 0.025
    assert not got["layer1_moe_select_bias"].any()
    assert not got["layer0_index_k_beta"].any()
    for name in ("layer0_attn_latent_gamma", "layer0_q_a_norm_gamma",
                 "layer0_index_k_gamma"):
        assert (got[name] == 1).all()
    # a share holds its heads' columns; the indexer and both
    # down-projections are whole
    assert got["layer0_q_b_proj_weight"].shape == (2 * 24, 32)
    assert got["layer0_attn_up_weight"].shape == (2 * 32, 16)
    assert got["layer0_attn_gate_proj_weight"].shape == (2, 64)
    assert got["layer2_attn_up_weight"].shape == (1 * 40, 32)
    assert got["layer0_index_q_weight"].shape == (4 * 16, 32)
    assert got["layer0_index_k_weight"].shape == (16, 64)
    assert got["layer0_index_head_weight"].shape == (4, 64)
    assert got["layer0_gate_proj_weight"].shape == (48, 64)
    assert "layer2_index_q_weight" not in got       # a window layer
    assert "layer0_shared_gate_proj_weight" not in got  # the dense layer


# -- the shares add up --------------------------------------------------------

@pytest.mark.parametrize("kind", [F, S], ids=["full", "sliding"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """THE SHARE-SUM TEST, one layer of either kind. Head shares (4 of 2
    heads in a full layer, 2 of 1 in a window layer) run the PROGRAM's
    ops on their columns of ``q_b_proj``, ``attn_up_weight``,
    ``attn_gate_proj`` and ``o_proj``; both down-projections, their
    norms and the indexer are whole in every share (every member chooses
    the same keys) and the ``o_proj`` outputs add. Then four expert
    shares of four route over all 16 and compute their own experts' part;
    the shared expert and the router are what every chip computes alike
    and count once. The sum is the uncut reference's layer."""
    cfg = dict(CFG, num_hidden_layers=1, layer_types=[kind],
               first_k_dense_replace=0)
    sym = dots3.from_config(cfg, seq_len=T)
    p = {k: jnp.asarray(v) for k, v in _params(sym, 11).items()}
    rng = np.random.RandomState(12)
    h = jnp.asarray(rng.randn(BATCH, T, 64), jnp.float32)
    nope, r, dv, q_rank, kv_rank, theta = ref.geometry(cfg, kind)
    heads = cfg[("" if kind == F else "swa_") + "num_attention_heads"]
    n, eps = "layer0_", cfg["rms_norm_eps"]

    # the uncut reference's layer
    x = ref.rms_norm(h, p[n + "attn_norm_gamma"], eps)
    attn, _ = ref.latent_attention(x, p.__getitem__, n, cfg, kind)
    mid = h + attn
    x2 = ref.rms_norm(mid, p[n + "ffn_norm_gamma"], eps)
    routed, counts, _ = ref.moe(
        x2.reshape(BATCH * T, -1), p[n + "moe_gate_weight"],
        p[n + "moe_gate_up_weight"], p[n + "moe_down_weight"],
        p[n + "moe_select_bias"], 3, True)
    shared = ref.swiglu(x2, p[n + "shared_gate_proj_weight"],
                        p[n + "shared_up_proj_weight"],
                        p[n + "shared_down_proj_weight"])
    want = mid + shared + routed.reshape(BATCH, T, -1)

    # what every member computes alike, once
    c_q = (64 / q_rank) ** 0.5 * tr.rms_norm(
        x @ p[n + "q_a_proj_weight"].T, p[n + "q_a_norm_gamma"], eps)
    latent = x @ p[n + "kv_a_proj_weight"].T
    keep = None
    if kind == F:
        keep, kept = tr.key_indexer(
            c_q, x, *(p[n + "index_" + w] for w in (
                "q_weight", "k_weight", "k_gamma", "k_beta", "head_weight")),
            num_heads=4, rope_dim=r, topk=cfg["index_topk"], theta=theta)
        np.testing.assert_array_equal(np.asarray(kept), [SELECTED] * BATCH)
    total, per = 0.0, heads // 2
    for j in range(0, heads, per):          # two head shares
        rows = lambda w, width: w.reshape(heads, width, -1)[
            j:j + per].reshape(per * width, -1)
        part = tr.latent_attention(
            c_q @ rows(p[n + "q_b_proj_weight"], nope + r).T, latent,
            p[n + "attn_latent_gamma"],
            rows(p[n + "attn_up_weight"], nope + dv), num_heads=per,
            rope_dim=r, v_head_dim=dv, theta=float(theta), eps=eps,
            latent_scale=(64 / kv_rank) ** 0.5,
            window=0 if kind == F else cfg["sliding_window_size"],
            gate=x @ p[n + "attn_gate_proj_weight"][j:j + per].T, keep=keep)
        o_cols = p[n + "o_proj_weight"].reshape(64, heads, dv)[
            :, j:j + per].reshape(64, per * dv)
        total = total + part @ o_cols.T
    _close(total, attn, "sum of the head shares' o_proj outputs", ulps=32)

    ffn = shared                            # counted once
    for offset in range(0, 16, 4):
        held = {"gate_w": p[n + "moe_gate_weight"],
                "select_bias": p[n + "moe_select_bias"],
                "w_gate_up": p[n + "moe_gate_up_weight"][offset:offset + 4],
                "w_down": p[n + "moe_down_weight"][offset:offset + 4]}
        part, part_counts = topk_moe(
            held, x2.reshape(BATCH * T, -1), 3, norm_topk_prob=True,
            scoring="sigmoid", expert_offset=offset,
            share_rows_bound=BATCH * T * 3, renorm_eps=1e-20)
        np.testing.assert_array_equal(np.asarray(part_counts),
                                      np.asarray(counts))
        ffn = ffn + part.reshape(BATCH, T, -1)
    _close(mid + ffn, want, "sum of the shares", ulps=32)
    # adding the shared expert in every share would count it 4 times
    assert float(jnp.abs(shared).max()) > 1e-2


# -- the selection ------------------------------------------------------------

def _indexer_inputs(seed, t, latent=32, d=64, heads=4, width=16):
    rng = np.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    return (draw(BATCH, t, latent), draw(BATCH, t, d),
            0.2 * draw(heads * width, latent), 0.2 * draw(width, d),
            1 + 0.1 * draw(width), 0.1 * draw(width), 0.2 * draw(heads, d))


@pytest.mark.parametrize("t,topk", [(48, 12), (300, 64), (16, 32)])
def test_the_selection_keeps_exactly_min_t_plus_1_k_keys_a_row(t, topk):
    """``KeyIndexer``: row t keeps ``min(t + 1, topk)`` keys, none past
    the diagonal, the reference's choice; a sequence no longer than
    ``topk`` keeps the causal triangle."""
    ins = _indexer_inputs(t, t)
    keep, count = tr.key_indexer(*ins, num_heads=4, rope_dim=8, topk=topk,
                                 theta=8e7)
    keep = np.asarray(keep)
    assert keep.dtype == np.int8 and keep.shape == (BATCH, t, t)
    want = np.minimum(np.arange(t) + 1, topk)
    np.testing.assert_array_equal(keep.sum(axis=-1), np.tile(want, (BATCH, 1)))
    assert not np.triu(keep, 1).any()
    np.testing.assert_array_equal(np.asarray(count), [want.sum()] * BATCH)
    scores = ref.index_scores(*ins, 8e7, 8)
    np.testing.assert_array_equal(keep != 0,
                                  np.asarray(ref.select(scores, topk)))
    if t <= topk:
        np.testing.assert_array_equal(keep[0], np.tril(np.ones((t, t))))


@pytest.mark.parametrize("t,k", [(64, 16), (300, 128), (32, 64)])
def test_keep_top_k_is_lax_top_k_with_ties_to_the_lower_index(t, k):
    """``keep_top_k`` finds a row's k-th value bit by bit; scores rounded
    to quarters tie by the dozen, one row ties all over, and masked
    entries (-inf) fill a short row's k."""
    rng = np.random.RandomState(t)
    s = np.round(rng.randn(2, t, t) * 4) / 4
    s[0, 5] = 0.0
    s[1, 7, ::2] = -0.0
    causal = np.tril(np.ones((t, t), bool))
    s = np.where(causal, s, -np.inf).astype(np.float32)
    got = np.asarray(jax.jit(lambda x: tr.keep_top_k(x, k))(jnp.asarray(s)))
    if t <= k:
        assert got.all()
        return
    _, idx = jax.lax.top_k(jnp.asarray(s), k)
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(got & causal, want & causal)
    np.testing.assert_array_equal(got.sum(-1), k)


def test_a_length_of_whole_lane_rows_chooses_by_the_kernel(monkeypatch):
    """At T 256 the full layer's ``KeyIndexer`` has a row block
    (``kernels.top_k_rows``): its call site counts ``impl="pallas"`` and,
    by the one seam, the kernel of ``ops/kernels/topk.py`` chooses the 48
    keys through the Pallas interpreter; the loss and the pairs kept are
    the reference's."""
    from mxnet_tpu.ops import kernels

    t = 256
    cfg = dict(CFG, num_hidden_layers=2, layer_types=[F, S], index_topk=48,
               max_position_embeddings=t)
    sym = dots3.from_config(cfg, seq_len=t)
    params = _params(sym, 31, t=t)
    tokens, labels = _batch(32, t=t)
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
        assert telemetry.REGISTRY.get("attention.index_lowerings").value(
            heads=4, width=16, topk=48, rows=256, impl="pallas") == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    _close(outs[0], want["per_sequence"], "per-sequence loss", rtol=1e-4)
    np.testing.assert_array_equal(
        outs[-1], [sum(min(i + 1, 48) for i in range(t))] * BATCH)


def _latent_inputs(seed, t, heads=2, nope=16, rope=8, dv=16, latent=32):
    rng = np.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    return (draw(BATCH, t, heads * (nope + rope)),
            draw(BATCH, t, latent + rope), 1 + 0.1 * draw(latent),
            0.2 * draw(heads * (nope + dv), latent))


def _latent(ins, heads=2, rope=8, dv=16, **extra):
    return tr.latent_attention(*ins, num_heads=heads, rope_dim=rope,
                               v_head_dim=dv, theta=1e4, eps=1e-5, **extra)


def test_a_selection_of_every_key_is_plain_causal_latent_attention():
    """Where T <= index_topk the indexer keeps the causal triangle, and
    the selected attention is the plain one, value and gradients."""
    t = 24
    ins = _latent_inputs(3, t)
    keep, _ = tr.key_indexer(*_indexer_inputs(4, t), num_heads=4, rope_dim=8,
                             topk=t, theta=1e4)
    cot = jnp.asarray(np.random.RandomState(5).randn(BATCH, t, 32),
                      jnp.float32)
    for fn in (lambda f: f(*ins),
               lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * cot),
                                  (0, 1, 2, 3))(*ins)):
        got = fn(lambda *a: _latent(a, keep=keep))
        want = fn(lambda *a: _latent(a))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            _close(g, w, "selected against plain", ulps=32)


def test_a_selection_drops_the_keys_it_drops_and_has_no_gradient():
    """The op under a keep-mask against the reference's masked softmax;
    nothing flows back through the mask or into the indexer's inputs."""
    t = 40
    ins, index_ins = _latent_inputs(6, t), _indexer_inputs(7, t)
    cot = jnp.asarray(np.random.RandomState(8).randn(BATCH, t, 32),
                      jnp.float32)

    def loss(index_ins, *a):
        keep, _ = tr.key_indexer(*index_ins, num_heads=4, rope_dim=8,
                                 topk=10, theta=1e4)
        return jnp.sum(_latent(a, keep=keep) * cot)

    index_g, q_g = jax.grad(loss, (0, 1))(index_ins, *ins)
    assert all(not np.asarray(g).any() for g in index_g)
    assert np.abs(np.asarray(q_g)).max() > 1e-3
    # against the reference's attention on the materialised key
    keep, _ = tr.key_indexer(*index_ins, num_heads=4, rope_dim=8, topk=10,
                             theta=1e4)
    q, latent, gamma, up = ins
    c = ref.rms_norm(latent[..., :32], gamma, 1e-5)
    kv = (c @ up.T).reshape(BATCH, t, 2, 32)
    k_rope = ref.rope(latent[..., 32:].reshape(BATCH, t, 1, 8), 1e4, True)
    q4 = q.reshape(BATCH, t, 2, 24)
    q4 = jnp.concatenate([q4[..., :16], ref.rope(q4[..., 16:], 1e4, True)],
                         axis=-1)
    k = jnp.concatenate([kv[..., :16],
                         jnp.broadcast_to(k_rope, (BATCH, t, 2, 8))], axis=-1)
    want = ref.attention(q4, k, kv[..., 16:], keep=jnp.asarray(keep) != 0)
    _close(_latent(ins, keep=keep), want.reshape(BATCH, t, -1), "selected",
           ulps=32)


# -- the window over latent attention, the rescale and the gate a head --------

@pytest.mark.parametrize("t", [600, 513, 40], ids=["t600", "t513", "t40"])
def test_a_window_of_513_keys_matches_reference_attention(t):
    """``LatentAttention(window=513)``: query t sees keys t - 512 .. t.
    Against ``reference_attention`` on the materialised key; at T 600 the
    op goes through the flash dispatch (its ``jax.numpy`` arithmetic off
    the TPU), at T <= 513 the window cuts nothing."""
    ins = _latent_inputs(t, t, nope=24)
    got = _latent(ins, window=513)
    q, latent, gamma, up = ins
    c = tr.rms_norm(latent[..., :32], gamma, 1e-5)
    kv = (c @ up.T).reshape(BATCH, t, 2, 40)
    q4 = tr.rope(q, 2, 1e4, 8, 24, True).reshape(BATCH, t, 2, 32)
    k = jnp.concatenate([kv[..., :24], jnp.broadcast_to(
        tr.rope(latent[..., 32:], 1, 1e4, 8, 0, True)[:, :, None],
        (BATCH, t, 2, 8))], axis=-1)
    want = reference_attention(q4, k, kv[..., 24:], causal=True, window=513)
    _close(got, want.reshape(BATCH, t, -1), "window 513", rtol=1e-4, ulps=64)
    plain = _latent(ins)
    if t <= 513:
        _close(got, plain, "a window wider than the sequence", ulps=32)
    else:       # rows past the window differ from full attention
        assert float(jnp.abs(got - plain)[:, 520:].max()) > 1e-3
        _close(got[:, :513], plain[:, :513], "rows inside the window",
               rtol=1e-4, ulps=64)


def test_the_headwise_gate_is_the_elementwise_one_repeated_over_a_head():
    """``gate`` [B, T, H]: head h's output times ``sigmoid(gate[.., h])``
    — ``gate_output`` with the gate repeated over the head's columns."""
    t = 24
    ins = _latent_inputs(9, t)
    gate = jnp.asarray(np.random.RandomState(10).randn(BATCH, t, 2),
                       jnp.float32)
    got = _latent(ins, gate=gate)
    want = tr.gate_output(_latent(ins), jnp.repeat(gate, 16, axis=-1))
    _close(got, want, "headwise gate")
    g = jax.grad(lambda gate: jnp.sum(_latent(ins, gate=gate) ** 2))(gate)
    assert g.shape == gate.shape and np.abs(np.asarray(g)).max() > 1e-4


def test_the_latent_scale_multiplies_the_normed_latent_alone():
    """``latent_scale`` r: keys' un-rotated part and values times r, the
    rotary key unscaled: the op with gamma scaled by r instead."""
    ins = _latent_inputs(13, 24)
    q, latent, gamma, up = ins
    _close(_latent(ins, latent_scale=10 ** 0.5),
           _latent((q, latent, gamma * 10 ** 0.5, up)), "rescale", ulps=32)


def test_latent_attention_names_and_shapes_its_optional_inputs():
    q, latent = mx.sym.Variable("q"), mx.sym.Variable("latent")
    plain = mx.contrib.sym.LatentAttention(
        q, latent, num_heads=2, rope_dim=8, v_head_dim=16, name="attn")
    assert plain.list_arguments() == [
        "q", "latent", "attn_latent_gamma", "attn_up_weight"]
    both = mx.contrib.sym.LatentAttention(
        q, latent, num_heads=2, rope_dim=8, v_head_dim=16, with_gate=True,
        with_keep=True, name="attn")
    assert both.list_arguments() == [
        "q", "latent", "attn_latent_gamma", "attn_up_weight", "attn_gate",
        "attn_keep"]
    shapes, out, _ = both.infer_shape(q=(3, 20, 48), latent=(3, 20, 40))
    assert shapes[4:] == [(3, 20, 2), (3, 20, 20)] and out == [(3, 20, 32)]
    window = mx.contrib.sym.LatentAttention(
        q, latent, num_heads=2, rope_dim=8, v_head_dim=16, with_keep=True,
        window=9, name="attn")
    with pytest.raises(Exception, match="window"):
        window.infer_shape(q=(3, 20, 48), latent=(3, 20, 40))
    index = mx.contrib.sym.KeyIndexer(
        mx.sym.Variable("c"), mx.sym.Variable("x"), num_heads=4, head_dim=16,
        rope_dim=8, topk=12, name="index")
    assert index.list_arguments() == [
        "c", "x", "index_q_weight", "index_k_weight", "index_k_gamma",
        "index_k_beta", "index_head_weight"]
    assert index.list_outputs() == ["index_keep", "index_count"]
    shapes, out, _ = index.infer_shape(c=(3, 20, 32), x=(3, 20, 64))
    assert shapes[2:] == [(64, 32), (16, 64), (16,), (16,), (4, 64)]
    assert out == [(3, 20, 20), (3,)]


# -- from_config --------------------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", None), ("swa_q_lora_rank", None), ("n_group", 2),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("topk_group", 2), ("topk_method", "greedy"),
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("moe_layer_freq", 2), ("scoring_func", "tanh"),
    ("num_key_value_heads", 2), ("swa_num_key_value_heads", 1),
    ("attention_gate_type", "elementwise"),
    ("swa_attention_gate_type", None), ("model_type", "deepseek_v3"),
    ("layer_types", [F, F, S, S, "linear_attention"]),
    ("num_hidden_layers", 4)])
def test_from_config_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match=key.replace("swa_", "")):
        dots3.from_config(dict(CFG, **{key: value}), seq_len=T)


def test_the_file_is_the_share_the_cell_trains():
    """``bench/configs/dots3_note_prev.json`` through ``from_config``:
    the published widths, the heads, experts, columns and rows the
    deployment's member holds, and the parameters the cut counted."""
    with open(FILE) as f:
        cfg = json.load(f)
    sym = dots3.from_config(cfg, **cfg["kwargs"])
    shapes, outs, _ = sym.infer_shape(data=(1, 4096),
                                      softmax_label=(1, 4096))
    by_name = dict(zip(sym.list_arguments(), shapes))
    assert by_name["layer0_q_a_proj_weight"] == (1024, 5120)
    assert by_name["layer0_q_b_proj_weight"] == (16 * 192, 1024)
    assert by_name["layer0_kv_a_proj_weight"] == (512 + 64, 5120)
    assert by_name["layer0_attn_up_weight"] == (16 * 256, 512)
    assert by_name["layer0_attn_gate_proj_weight"] == (16, 5120)
    assert by_name["layer0_index_q_weight"] == (64 * 128, 1024)
    assert by_name["layer0_index_k_weight"] == (128, 5120)
    assert by_name["layer0_index_head_weight"] == (64, 5120)
    assert by_name["layer2_q_b_proj_weight"] == (8 * 256, 1024)
    assert by_name["layer2_kv_a_proj_weight"] == (1024 + 64, 5120)
    assert by_name["layer2_attn_up_weight"] == (8 * 320, 1024)
    assert by_name["layer0_gate_proj_weight"] == (1728, 5120)
    assert by_name["layer1_moe_gate_weight"] == (5120, 256)
    assert by_name["layer1_moe_down_weight"] == (8, 1536, 5120)
    assert by_name["layer1_shared_gate_proj_weight"] == (1536, 5120)
    assert by_name["embed_weight"] == (19008, 5120)
    count = sum(int(np.prod(s)) for n, s in by_name.items()
                if n not in ("data", "softmax_label"))
    assert 1204.5e6 < count < 1205.5e6, count   # the issue's 1,205 M
    # loss, four expert layers' counts, two full layers' selection counts
    assert outs == [(1,)] + [(256,)] * 4 + [(1,)] * 2


def test_the_two_copies_of_the_reference_are_one_text():
    here = os.path.dirname(__file__)
    with open(os.path.join(here, "..", "mxnet_tpu", "models",
                           "dots3_reference.py")) as a, \
            open(os.path.join(here, "..", "bench", "reference",
                              "dots3_note_prev.py")) as b:
        assert a.read() == b.read()


def test_the_reference_one_precision_below_is_another_result():
    """The reference in bf16 throughout (its index scores and its compare
    too) is off by what float32 is not: the reading a cell's tolerance
    has to fail."""
    sym = dots3.from_config(CFG, seq_len=T)
    params = _params(sym, 21)
    tokens, labels = _batch(22)
    exact = ref.forward(params, tokens, CFG, labels=labels)
    below = ref.forward(params, tokens, CFG, labels=labels,
                        dtype=jnp.bfloat16)
    err = np.abs(np.asarray(below["logits"], np.float32)
                 - np.asarray(exact["logits"])).max() \
        / np.asarray(exact["logits"]).std()
    assert 1e-3 < err < 0.5, err
    report = {"eps": 1e-3}
    ref.forward(params, tokens, dict(CFG, select_report=report),
                labels=labels)
    assert report["float32"]["keys_selected"] == [[SELECTED] * BATCH] * 2
    assert all(0 <= s < 0.2 for s in report["float32"]["near_tie_share"])
