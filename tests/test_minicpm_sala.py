"""MiniCPM-SALA on the normal path against its plain reference.

``models/minicpm_sala.py`` (an ``mx.sym`` graph: ``LinearAttention`` between
five projections in three layers of four, ``BlockSelect`` feeding
``Attention``'s keep-mask in the fourth, a dense SwiGLU in each, under
MiniCPM's three scalings) through ``Module.forward/backward`` and
``Module.fit``'s fused step, against ``models/minicpm_sala_reference.py``
(plain float32 ``jax.numpy``: the linear layers as the token-by-token
recurrence, the block choice by ``argsort``, attention by an explicit mask)
on seeded weights at a tiny size that keeps the cell's ratios: a sequence
three times ``dense_len``, so the sparse layer chooses; a local window of a
block and a half, so its edge cuts a block; more candidate blocks than are
chosen; two query heads a key/value head.

Tolerances. float32 (``_close``): both sides are float32 and only the order
of summation differs (the scan's chunks against the recurrence, the flash
dispatch against the masked softmax), so rtol 1e-5 with an atol of a few
float32 ulps of the tensor's own scale, as ``tests/test_falcon_h1.py``: 8
for losses, 64 for logits, 256 for gradients (long sums over tokens through
the scan, the softmax and four norms a layer; measured 2-20 at these
shapes). The choice of blocks is discrete: at float32 both sides choose the
same blocks at these seeds (asserted), so no token is left out. bf16
(``test_the_bf16_program_stays_near_the_float32_reference``): the program in
bf16 against the float32 reference; a bf16 value carries 8 bits and a layer
rounds its stream a dozen times, so the loss agrees to 2e-3 and each
gradient to 0.15 of its own norm (measured over three seeds: loss 1.0e-4 to
2.5e-4; gradients 0.006-0.043 at the test's seed and at a second; at a
third the linear layers' q / k gammas read 0.23 and 0.28: behind a norm a
gradient is what is left of two near-equal terms, and its relative error
is as large as they cancel); a dropped scale or a wrong decay moves loss
and gradients by far more (the assumed tests).
"""
import hashlib
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import minicpm_sala, minicpm_sala_reference as ref
from mxnet_tpu.ops import kernels as pk
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.transformer import attention as attention_ops
from mxnet_tpu.ops.transformer import blocks, ssm
from mxnet_tpu.parallel import make_mesh

T, BATCH = 96, 2
SPARSE = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=2,
              init_blocks=1, window_size=24, dense_len=32)
# the uncut tiny model: 4 lightning heads of 8; 4 query heads on 2
# key/value heads of 8; SwiGLU of 80; published depth 8 of which 3 are held
CFG = dict(
    model_type="minicpm_sala", vocab_size=512, hidden_size=48,
    intermediate_size=80, num_hidden_layers=3,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=8,
    lightning_scale="1/sqrt(d)", lightning_use_rope=True,
    attn_use_rope=False, qk_norm=True, use_output_gate=True,
    use_output_norm=True, attn_use_output_gate=True, rms_norm_eps=1e-6,
    rope_theta=1e4, scale_emb=3.0, scale_depth=1.4, dim_model_base=16,
    mup_denominator=32, rand_init=False, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False,
    max_position_embeddings=T, sparse_config=SPARSE,
    share=dict(layers_of=8, first_layer=0))


def _share(s):
    """One of the two chips that share each layer: 2 lightning heads
    (published heads 2 s and 2 s + 1), 2 query heads with the key/value
    head they read, 40 of the 80 columns, half the vocabulary."""
    return dict(
        CFG, lightning_nh=2, lightning_nkv=2, num_attention_heads=2,
        num_key_value_heads=1, vocab_size=256,
        share=dict(chips=2, layers_of=8, first_layer=0,
                   lightning_heads_of=4, first_lightning_head=2 * s,
                   attention_heads_of=4, kv_heads_of=2,
                   dense_columns_held=40))


SHARE = _share(0)


def _close(got, want, what, rtol=1e-5, ulps=8):
    """rtol 1e-5, atol ``ulps`` float32 ulps of the largest magnitude of
    ``want`` (summation order is all that differs)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    atol = ulps * np.finfo(np.float32).eps * max(np.abs(want).max(), 1e-30)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def _params(sym, seed, data=(BATCH, T), sigma=0.2):
    """Seeded weights under the symbol's argument names: Normal(sigma), a
    unit embedding, gammas near 1."""
    rng = np.random.RandomState(seed)
    shapes, _, _ = sym.infer_shape(data=data, softmax_label=data)
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = 1.0 if name == "embed_weight" else sigma
        out[name] = (scale * rng.randn(*shape)
                     + name.endswith("_gamma")).astype(np.float32)
    return out


def _batch(seed, vocab, shape=(BATCH, T)):
    tokens = np.random.RandomState(seed).randint(
        0, vocab, (shape[0], shape[1] + 1))
    return tokens[:, :-1].astype(np.float32), tokens[:, 1:].astype(np.float32)


def _module(sym, params, data=(BATCH, T), dtype=None):
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", data)],
             label_shapes=[("softmax_label", data)])
    mod.init_params(arg_params={k: mx.nd.array(v, dtype=dtype)
                                for k, v in params.items()}, aux_params={})
    return mod


def _program(cfg, params, tokens, labels, dtype="float32", seq_len=T):
    """(per-sequence loss, {name: gradient of the mean token loss}) of the
    symbol ``cfg`` builds, through ``Module.forward`` / ``backward``."""
    sym = minicpm_sala.from_config(cfg, seq_len=seq_len, dtype=dtype)
    mod = _module(sym, params, tokens.shape,
                  dtype=None if dtype == "float32" else dtype)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)]), is_train=True)
    mod.backward()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert len(outs) == 1                       # the loss and nothing else
    grads = mod._exec_group.execs[0].grad_dict
    # the head sums the sequences' losses (MXNet's convention)
    return outs[0], {k: np.asarray(grads[k].asnumpy(), np.float32)
                     / tokens.shape[0] for k in params}


def _compare(cfg, program_cfg, seed=1):
    """THE COMPARISON: the program built from ``program_cfg`` against the
    reference given ``cfg``, on the same seeded weights: per-sequence loss
    and the gradient of every parameter at the module's stated
    tolerances."""
    sym = minicpm_sala.from_config(cfg, seq_len=T)
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


@pytest.mark.parametrize("cfg", [CFG, SHARE, _share(1)],
                         ids=["whole", "share0", "share1"])
def test_logits_loss_and_every_gradient_match_the_reference(cfg):
    """One sparse layer that chooses 2 of up to 3 candidate blocks a query
    and two linear layers: per-sequence loss, the last positions' logits and
    the gradient of every parameter, whole (two key/value heads: two
    ``BlockSelect`` nodes) and as either chip's share."""
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
    # the sparse layer chose: some queries have more candidates than it takes
    gap = np.asarray(last["router_gap"])
    assert gap.shape == (1, BATCH * T) and np.isfinite(gap).any()
    kv = cfg["num_key_value_heads"]
    assert [list(k) for k in last["block_keys_kept"]] == [
        [kv * blocks.kept_pairs(T, 16, 2, 1, 24)] * BATCH]


def test_the_bf16_program_stays_near_the_float32_reference():
    """The share in bf16 (as the cell runs it) against the float32
    reference on the same weights, rounded once: the tolerances of the
    module's docstring."""
    sym = minicpm_sala.from_config(SHARE, seq_len=T)
    params = {k: np.asarray(jnp.asarray(v, jnp.bfloat16), np.float32)
              for k, v in _params(sym, 21).items()}
    tokens, labels = _batch(22, SHARE["vocab_size"])
    loss, grads = ref.loss_and_grads(params, tokens, labels, SHARE)
    per_sequence, got = _program(SHARE, params, tokens, labels,
                                 dtype="bfloat16")
    assert abs(float(per_sequence.mean()) - float(loss)) <= 2e-3
    for name, want_g in grads.items():
        want_g = np.asarray(want_g, np.float32)
        err = np.linalg.norm(got[name] - want_g) / np.linalg.norm(want_g)
        assert err <= 0.15, (name, err)


def test_a_short_sequence_reads_every_key():
    """At ``dense_len`` positions or fewer a ``minicpm4`` layer is plain
    grouped attention: no ``BlockSelect`` node, and the program follows the
    reference, which marks no token."""
    t = SPARSE["dense_len"]
    sym = minicpm_sala.from_config(CFG, seq_len=t)
    assert not [n for n in sym.get_internals().list_outputs()
                if "blocks" in n]
    params = _params(sym, 3, data=(BATCH, t))
    tokens, labels = _batch(4, CFG["vocab_size"], (BATCH, t))
    want = ref.forward(params, tokens, CFG, labels=labels)
    per_sequence, _ = _program(CFG, params, tokens, labels, seq_len=t)
    _close(per_sequence, want["per_sequence"], "per-sequence loss")
    assert np.isinf(np.asarray(want["router_gap"])).all()
    assert want["block_keys_kept"] == [None]


# -- the assumed entries of bench/configs/minicpm_sala_9b.json ---------------

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")


def _bench_config():
    with open(os.path.join(BENCH, "configs", "minicpm_sala_9b.json")) as f:
        return json.load(f)


def _nodes(sym):
    """{node name: (op, attrs)} of a symbol."""
    return {n["name"]: (n["op"], n.get("attrs", n.get("attr", {})))
            for n in json.loads(sym.tojson())["nodes"]}


def test_assumed_sparse_config():
    """Pooling 32 by 16, blocks of 64, 64 chosen, 1 initial block, window
    2,048, dense_len 8,192: what the builder takes where the dict has no
    ``sparse_config`` (MiniCPM4.1's), what the file states, and what the
    cell's ``BlockSelect`` node carries."""
    published = dict(kernel_size=32, kernel_stride=16, block_size=64,
                     topk=64, init_blocks=1, window_size=2048,
                     dense_len=8192)
    cfg = _bench_config()
    assert cfg["sparse_config"] == published
    assert minicpm_sala.SPARSE_CONFIG == published == ref.SPARSE_DEFAULTS
    bare = {k: v for k, v in cfg.items() if k != "sparse_config"}
    for given in (cfg, bare):
        op, attrs = _nodes(minicpm_sala.from_config(
            given, **cfg["kwargs"]))["layer0_blocks"]
        assert op == "_contrib_BlockSelect"
        assert {k: int(attrs[k]) for k in (
            "pool", "stride", "block", "topk", "init_blocks", "window",
            "num_heads")} == dict(pool=32, stride=16, block=64, topk=64,
                                  init_blocks=1, window=2048, num_heads=16)
    # dense_len: at 8,192 positions no layer chooses, one more and it does
    short = minicpm_sala.from_config(cfg, seq_len=8192)
    assert "layer0_blocks" not in _nodes(short)
    assert "layer0_blocks" in _nodes(minicpm_sala.from_config(
        cfg, seq_len=8193))
    # the last query of a block keeps 97 blocks (1 + 32 + 64: 6,208 keys),
    # the one before it 98, its own cut short: 2,111 local, 64, 4,096
    assert blocks.kept_pairs(16384) - blocks.kept_pairs(16383) == 6208
    assert blocks.kept_pairs(16383) - blocks.kept_pairs(16382) == 6208 + 63


def test_assumed_slopes():
    """``slope(h, l) = 2^(-8 (h + 1) / 32) (1 - l / 31 + 1e-5)`` for the
    PUBLISHED head and layer: the cell's three linear layers carry heads
    0-15 of 32 at layers 1-3 of 32, and the reference derives the same."""
    cfg = _bench_config()
    nodes = _nodes(minicpm_sala.from_config(cfg, **cfg["kwargs"]))
    for layer in (1, 2, 3):
        op, attrs = nodes["layer%d_linattn" % layer]
        assert op == "_contrib_LinearAttention"
        got = [float(v) for v in attrs["slopes"].strip("()").split(",")]
        want = [2.0 ** (-8.0 * (h + 1) / 32) * (1 - layer / 31.0 + 1e-5)
                for h in range(16)]
        np.testing.assert_allclose(got, want, rtol=1e-12)
        np.testing.assert_allclose(ref.slopes(cfg, layer), want, rtol=1e-12)
    assert tr.lightning_slopes(32, 0, 32)[0] == pytest.approx(
        2.0 ** -0.25 * (1 + 1e-5))
    # the other chip's heads are the published 16-31
    assert tr.lightning_slopes(32, 1, 32, first=16, held=16)[0] == \
        pytest.approx(2.0 ** (-8.0 * 17 / 32) * (1 - 1 / 31.0 + 1e-5))
    # a decay taken from the held counts (16 heads, 4 layers) is another
    assert abs(tr.lightning_slopes(16, 1, 4)[0] - got[0]) > 0.1


def test_assumed_output_norm_is_a_head():
    """The norm behind the linear core is over each head's OWN columns: two
    heads whose outputs differ in size by 100 come out at the same rms, and
    a norm over all heads' columns at once would not."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 40, 2 * 8), jnp.float32)
               for _ in range(3))
    v = v.at[..., 8:].multiply(100.0)
    out = tr.linear_attention(q, k, v, (0.5, 0.25), 2, chunk_size=8,
                              norm_gamma=jnp.ones((8,)))
    rms = np.sqrt(np.mean(np.square(np.asarray(out).reshape(1, 40, 2, 8)),
                          axis=-1))
    np.testing.assert_allclose(rms[:, 1:], 1.0, rtol=1e-3)
    bare = np.asarray(tr.linear_attention(q, k, v, (0.5, 0.25), 2,
                                          chunk_size=8))
    whole = bare / np.sqrt(np.mean(np.square(bare), -1, keepdims=True))
    assert np.abs(whole[..., :8]).max() < 0.2 < np.abs(out[..., :8]).max()


def test_assumed_norm_then_rotation():
    """q and k are normed a head, THEN rotated (the rotation reads the
    norm's output), in the linear layers; the sparse layers norm and do not
    rotate."""
    nodes = json.loads(minicpm_sala.from_config(CFG, seq_len=T).tojson())[
        "nodes"]
    by_name = {n["name"]: n for n in nodes}

    def source(name):  # the first input's producer, through reshapes
        node = nodes[by_name[name]["inputs"][0][0]]
        while node["op"] in ("Reshape", "slice_axis"):
            node = nodes[node["inputs"][0][0]]
        return node["name"]

    for which in ("q", "k"):
        assert source("layer1_linattn_%s_rope" % which) == \
            "layer1_linattn_%s_norm" % which
        assert source("layer1_linattn_%s_norm" % which) == \
            "layer1_linattn_%s_proj" % which
    assert not [n for n in by_name if n.startswith("layer0_") and "rope" in n]
    assert source("layer0_kv0_blocks") == "layer0_q_norm"


def test_assumed_scaling():
    """``scale_emb`` on the embedding's rows, ``scale_depth / sqrt(32)``
    (the PUBLISHED depth, not the four held) on both sub-layers' outputs,
    ``dim_model_base / hidden_size`` on the logits."""
    cfg = _bench_config()
    nodes = _nodes(minicpm_sala.from_config(cfg, **cfg["kwargs"]))

    def scale(name):
        op, attrs = nodes[name]
        assert op == "_contrib_ScaledSum", name
        return float(attrs["scales"].strip("(),"))

    assert scale("embed_scale") == 12.0
    depth = 1.4 / math.sqrt(32)
    for layer in range(4):
        assert scale("layer%d_mixer_scale" % layer) == pytest.approx(depth)
        assert scale("layer%d_down_proj_scale" % layer) == \
            pytest.approx(depth)
    assert scale("lm_head_f32") == 256 / 4096
    # the reference reads the same three; one moved in the program alone
    # fails the comparison
    for key, value in (("scale_emb", 1.0), ("scale_depth", 1.0),
                       ("dim_model_base", 48)):
        with pytest.raises(AssertionError):
            _compare(CFG, dict(CFG, **{key: value}))
            pytest.fail("%s went unseen" % key)


def test_assumed_unread_keys():
    """``mup_denominator``, ``rand_init`` and (where the caller gives a
    length) ``max_position_embeddings`` change nothing of the graph;
    ``lightning_scale`` is read as the string it is."""
    def graph(cfg):  # ops, attributes and wiring; auto-given names aside
        nodes = json.loads(minicpm_sala.from_config(cfg, seq_len=T).tojson())[
            "nodes"]
        return [(n["op"], n.get("attrs", n.get("attr")), n["inputs"])
                for n in nodes]

    want = graph(CFG)
    assert minicpm_sala.ASSUMED_UNREAD == (
        "mup_denominator", "rand_init", "max_position_embeddings")
    for key, value in (("mup_denominator", 8), ("rand_init", True),
                       ("max_position_embeddings", 524288)):
        assert graph(dict(CFG, **{key: value})) == want
    assert graph(dict(CFG, scale_depth=1.0)) != want
    with pytest.raises(ValueError, match="lightning_scale"):
        minicpm_sala.from_config(dict(CFG, lightning_scale="1/d"), seq_len=T)


@pytest.mark.parametrize("key, value", [
    ("attention_bias", True), ("attn_use_rope", True),
    ("lightning_use_rope", False), ("qk_norm", False),
    ("use_output_gate", False), ("use_output_norm", False),
    ("attn_use_output_gate", False), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("model_type", "minicpm"),
    ("lightning_nkv", 2), ("mixer_types", ["minicpm4", "mamba", "minicpm4"]),
    ("num_key_value_heads", 3)])
def test_from_config_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match=key if key != "num_key_value_heads"
                       else "all the query heads that read"):
        minicpm_sala.from_config(dict(CFG, **{key: value}), seq_len=T)


def test_the_bench_configuration_builds_the_cells_symbol():
    """Every published key of the catalog's row stands in the file under its
    own name; the reduced ones hold what is held; the symbol's parameters
    are the issue's arithmetic (631 M)."""
    cfg = _bench_config()
    sym = minicpm_sala.from_config(cfg, **cfg["kwargs"])
    shapes, _, _ = sym.infer_shape(data=(1, 16384), softmax_label=(1, 16384))
    count = sum(int(np.prod(s)) for n, s in zip(sym.list_arguments(), shapes)
                if n not in ("data", "softmax_label"))
    assert 629e6 < count < 632e6
    assert cfg["hidden_size"] == 4096 and cfg["intermediate_size"] == 16384
    assert cfg["head_dim"] == cfg["lightning_head_dim"] == 128
    assert cfg["mixer_types"] == list(minicpm_sala.PUBLISHED_MIXERS[:4])
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_why"])


# -- the share ----------------------------------------------------------------

def _share_of(params, s):
    """Share ``s`` of 2 of the uncut tiny model's parameters: its two
    lightning heads of q / k / v / gate (rows) and of ``o_proj`` (columns),
    its key/value head with the two query heads that read it, its columns of
    the SwiGLU, its rows of the head; norms and embedding whole."""
    lin = np.arange(s * 16, (s + 1) * 16)      # 2 heads of 8
    q_cols = np.arange(s * 16, (s + 1) * 16)
    kv_cols = np.arange(s * 8, (s + 1) * 8)
    mlp_cols = np.arange(s * 40, (s + 1) * 40)
    out = {}
    for name, v in params.items():
        leaf = name.split("_", 1)[1] if name.startswith("layer") else name
        if leaf in ("linattn_q_proj_weight", "linattn_k_proj_weight",
                    "linattn_v_proj_weight", "linattn_g_proj_weight"):
            v = v[lin]
        elif leaf == "linattn_o_proj_weight":
            v = v[:, lin]
        elif leaf in ("q_proj_weight", "attn_gate_proj_weight"):
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
    """THE SHARE-SUM TEST. One ``minicpm4`` layer, one ``lightning-attn``
    layer and the SwiGLU of the uncut tiny model on one normed input: the
    two chips' partial outputs, summed, are the uncut reference's; the two
    chips' block choices are the uncut model's two groups' (a group's choice
    sums over its own heads only, so it needs no exchange); the residual is
    what both hold alike and counts once. The program's share computes the
    reference's share (the node ``layer<i>_mixer_scale`` of its symbol)."""
    whole = _params(minicpm_sala.from_config(CFG, seq_len=T), 5)
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(BATCH, T, 48), jnp.float32)

    def fetch(params):
        return lambda name: jnp.asarray(params[name])

    with jax.default_matmul_precision("highest"):
        stats = {}
        want = (ref.sparse_attention(x, fetch(whole), "layer0_", CFG,
                                     stats=stats),
                ref.lightning(x, fetch(whole), "layer1_", CFG, 1),
                ref.mlp(x, fetch(whole), "layer1_"))
        parts, chosen = [], []
        for s in range(2):
            mine, mine_stats = fetch(_share_of(whole, s)), {}
            parts.append((
                ref.sparse_attention(x, mine, "layer0_", _share(s),
                                     stats=mine_stats),
                ref.lightning(x, mine, "layer1_", _share(s), 1),
                ref.mlp(x, mine, "layer1_")))
            chosen.append(np.asarray(mine_stats["blocks"])[:, 0])
    for name, w, got in zip(("sparse", "lightning", "mlp"), want,
                            zip(*parts)):
        _close(got[0] + got[1], w, "the two shares' %s" % name, ulps=32)
        # a share is a part, not the whole: neither alone is the sum
        assert float(jnp.abs(got[0] - w).max()) > 1e-2, name
    uncut = np.asarray(stats["blocks"])                  # [B, 2, T, blocks]
    for s in range(2):
        np.testing.assert_array_equal(chosen[s], uncut[:, s])
    assert (uncut[:, 0] != uncut[:, 1]).any()        # two choices, not one
    # the other chip's heads decay otherwise: heads 2-3 are not heads 0-1
    assert ref.slopes(_share(1), 1) == ref.slopes(CFG, 1)[2:]

    # the program's share is the reference's share, from the embedding on
    for s in range(2):
        mine = _share_of(whole, s)
        tokens, _ = _batch(7, 256)
        sym = minicpm_sala.from_config(_share(s), seq_len=T)
        record = []
        ref.forward(mine, tokens, _share(s), parts=record)
        for layer in (0, 1):
            node = sym.get_internals()["layer%d_mixer_scale_output" % layer]
            mod = mx.mod.Module(node, context=mx.cpu(0), label_names=None)
            mod.bind(data_shapes=[("data", tokens.shape)],
                     for_training=False)
            held = set(node.list_arguments())
            mod.init_params(arg_params={k: mx.nd.array(v) for k, v in
                                        mine.items() if k in held},
                            aux_params={})
            mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]),
                        is_train=False)
            got = mod.get_outputs()[0].asnumpy().reshape(BATCH, T, 48)
            _close(got, record[layer]["mixer"],
                   "share %d's layer %d" % (s, layer), ulps=64)


# -- the ops ------------------------------------------------------------------

def _block_inputs(seed, t, heads=3, d=8, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(BATCH, t, heads * d), dtype),
            jnp.asarray(rng.randn(BATCH, t, d), dtype))


@pytest.mark.parametrize("t", [300, 96, 20])
def test_block_keys_kept_is_the_closed_form(t):
    """``BlockSelect``'s count of the (query, key) pairs it keeps, the
    causal pairs of its keep-mask counted by hand and ``kept_pairs``' closed
    form agree exactly, a sequence longer than the candidates, one where the
    rule takes every candidate and one shorter than the window; under
    telemetry each execution observes the count once from the device."""
    q, k = _block_inputs(1, t)
    kw = dict(pool=8, stride=4, block=16, topk=3, init_blocks=1, window=40)
    want = blocks.kept_pairs(t, 16, 3, 1, 40)
    telemetry.reset()
    telemetry.enable()
    try:
        keep, count = jax.jit(
            lambda q, k: tr.block_select(q, k, 3, **kw))(q, k)
        jax.effects_barrier()
        seen = telemetry.REGISTRY.get("attention.block_keys_kept")
        assert (seen.count(), seen.sum()) == (1, BATCH * want)
        lowered = telemetry.REGISTRY.get("attention.block_select_lowerings")
        assert lowered.value(blocks=-(-t // 16), chosen=3, window=40,
                             impl="jnp") == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    keep = np.asarray(keep)
    assert keep.shape == (BATCH, t, t) and keep.dtype == np.int8
    assert [float(c) for c in count] == [want] * BATCH
    assert [int(np.tril(row).sum()) for row in keep] == [want] * BATCH
    # the choice is the reference's, block for block
    with jax.default_matmul_precision("highest"):
        sparse = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=3,
                      init_blocks=1, window_size=40)
        for b in range(BATCH):
            kept, _ = ref.choose_blocks(q[b].reshape(t, 3, 8), k[b], sparse)
            np.testing.assert_array_equal(
                keep[b], np.repeat(np.asarray(kept), 16, axis=1)[:, :t])
    # without telemetry the program holds no callback
    text = str(jax.make_jaxpr(
        lambda q, k: tr.block_select(q, k, 3, **kw))(q, k))
    assert "callback" not in text


def test_block_select_has_no_gradient():
    q, k = _block_inputs(2, 64)
    kw = dict(pool=8, stride=4, block=16, topk=1, init_blocks=1, window=20)
    grads = jax.grad(lambda q, k: jnp.sum(
        tr.block_select(q, k, 3, **kw)[1]), argnums=(0, 1))(q, k)
    assert not any(np.asarray(g).any() for g in grads)


HEADS, D, CHUNK = 2, 128, 128      # a shape the scan's kernels take


@pytest.fixture(params=["jnp_branch", "kernels_interpreted"])
def scan_path(request, monkeypatch):
    """``linear_attention`` both ways a CPU test can run it at a shape the
    scan's kernels take: as a step lowered off the TPU runs it (the einsum
    form inside the ``custom_vjp``), and with the kernel pair put through
    the Pallas interpreter (what the TPU's branch computes)."""
    ssm._linattn_block.clear_cache()
    if request.param == "kernels_interpreted":
        monkeypatch.setattr(pk.common, "INTERPRET", True)
    yield request.param
    ssm._linattn_block.clear_cache()


def _recurrence(q, k, v, slopes, gamma, gate, eps=1e-6):
    """The token-by-token scan, as ``ref.lightning`` runs it."""
    b, t, _ = q.shape
    decay = jnp.exp(-jnp.asarray(slopes, jnp.float32))[None, :, None, None]
    q, k, v = (y.reshape(b, t, HEADS, D).transpose(1, 0, 2, 3)
               for y in (q, k, v))

    def token(state, at):
        q_t, k_t, v_t = at
        state = decay * state + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhdp,bhd->bhp", state, q_t)

    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(token, jnp.zeros((b, HEADS, D, D)), (q, k, v))
    o = ref.rms_norm(o.transpose(1, 0, 2, 3) * D ** -0.5, gamma, eps)
    return o.reshape(b, t, HEADS * D) * jax.nn.sigmoid(gate)


def test_linear_attention_on_the_scan_matches_the_recurrence(scan_path):
    """Values and the gradient of every input, float32 to summation order
    (64 ulps: the chunked form sums a chunk's products in another order
    than the recurrence, and its decay tables are exponentials of running
    sums): ``LinearAttention`` runs ``kernels.ssd_scan`` with 2 heads each
    its own group, at the MiniCPM-SALA cell's head and state of 128. T 300
    is not whole chunks; slopes from the fastest decay (0.84 a token: its
    chunk table underflows to zero, as it must) to the slowest."""
    assert pk.ssd_takes(HEADS, D, D, HEADS, CHUNK, jnp.float32)
    rng = np.random.RandomState(0)
    slopes = (2.0 ** -0.25, 2.0 ** -8.0 * 1e-5)
    ins = [jnp.asarray(rng.randn(1, 300, HEADS * D), jnp.float32)
           for _ in range(3)]
    ins[0], ins[1] = ins[0] / 4, ins[1] / 4
    gamma = jnp.asarray(1 + 0.1 * rng.randn(D), jnp.float32)
    gate = jnp.asarray(rng.randn(1, 300, HEADS * D), jnp.float32)

    def op(q, k, v, gamma, gate, remat=False):
        return tr.linear_attention(q, k, v, slopes, HEADS, chunk_size=CHUNK,
                                   norm_gamma=gamma, gate=gate, remat=remat)

    telemetry.reset()
    telemetry.enable()
    try:
        got = op(*ins, gamma, gate)
        lowered = telemetry.REGISTRY.get("linattn.lowerings")
        assert lowered.value(impl="kernel", heads=HEADS, chunk=CHUNK) == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    _close(got, _recurrence(*ins, slopes, gamma, gate), "out", ulps=64)
    got = jax.grad(lambda *a: jnp.sum(op(*a, remat=True) ** 2),
                   tuple(range(5)))(*ins, gamma, gate)
    want = jax.grad(lambda q, k, v, gamma, gate: jnp.sum(
        _recurrence(q, k, v, slopes, gamma, gate) ** 2),
        tuple(range(5)))(*ins, gamma, gate)
    for name, g, w in zip(("dq", "dk", "dv", "dgamma", "dgate"), got, want):
        assert float(jnp.abs(w).max()) > 1e-4, name
        _close(g, w, name, ulps=256)


def test_linear_attention_refuses_slopes_that_are_not_a_heads():
    q = jnp.zeros((1, 8, 16))
    with pytest.raises(ValueError, match="one a head"):
        tr.linear_attention(q, q, q, (0.5,), 2)
    with pytest.raises(ValueError, match="positive"):
        ssm._linear_attention_infer(
            {"num_heads": 2, "slopes": (0.5, -0.1)}, [(1, 8, 16)] * 3)


# the keep-less and the token-keep ``Attention`` as the PARENT commit traced
# them (sha256 of the jaxpr's text, taken from a checkout of a2a447a with
# /root/scratch-style script: 1 x 160 positions, 4 heads on 2 of 16, bf16)
PARENT_JAXPRS = {
    False: "8528b9a5bc4264d23a3a92973d7f202f64dca51ff3bad87891aa744bf5a632e7",
    True: "58a5effee5cb1d2190f8f7defd8f1e132bea27f718153d91e563d264f63e8d2b",
}


@pytest.mark.parametrize("with_keep", [False, True],
                         ids=["keepless", "token_keep"])
def test_attention_traces_what_the_parent_traced(with_keep):
    """``BlockSelect`` hands ``Attention`` the int8 keep-mask a KEY it
    already took: the op is untouched, and both of its forms trace the
    parent's jaxpr text for text."""
    t, heads, kv, d = 160, 4, 2, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, t, heads * d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, t, kv * d), jnp.bfloat16)
    ins = [q, k, k] + ([jnp.ones((1, t, t), jnp.int8)] if with_keep else [])
    attrs = dict(num_heads=heads, num_kv_heads=kv, causal=True,
                 with_keep=with_keep)
    text = str(jax.make_jaxpr(lambda *a: attention_ops._attention(
        attrs, list(a), True)[0])(*ins))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_JAXPRS[with_keep]


def test_kept_attention_raises_past_its_stated_size():
    """``Attention`` under a keep-mask with no selected kernels for its
    shapes used to ask for the float32 scores whatever their size; past
    ``KEPT_SCORES_LIMIT`` it says so."""
    spec = jax.ShapeDtypeStruct
    q = spec((1, 16384, 16, 128), jnp.float16)     # no type Mosaic takes
    k = spec((1, 16384, 1, 128), jnp.float16)
    keep = spec((1, 16384, 16384), jnp.int8)
    assert not pk.flash_select_takes(16384, 16, 1, 128, 128, jnp.float16)
    with pytest.raises(ValueError, match="KEPT_SCORES_LIMIT"):
        jax.eval_shape(lambda q, k, keep: pk.kept_attention(
            q, k, k, keep, 0.1), q, k, keep)
    # the cell's own shapes have kernels, forward and backward
    assert pk.flash_select_takes(16384, 16, 1, 128, 128, jnp.bfloat16)
    assert pk.flash.select_range(16384, 64, 16, 1024, 128, 128,
                                 jnp.bfloat16) == 8192


# -- the model through the fused step ----------------------------------------

def test_fused_fit_trains_the_share_and_the_loss_falls():
    """Module.fit(kvstore='device', mesh dp=1), the fused ShardedTrainStep
    on the share: the first steps follow the reference's own SGD with
    momentum, the loss falls, and each node counts its call site."""
    sym = minicpm_sala.from_config(SHARE, seq_len=T)
    params = _params(sym, 3)
    tokens, labels = _batch(4, SHARE["vocab_size"])
    lr, momentum, steps = 0.2, 0.9, 6

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
    ssm._linattn_block.clear_cache()
    telemetry.reset()
    telemetry.enable()
    try:
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
        jax.effects_barrier()
        lin = telemetry.REGISTRY.get("linattn.lowerings")
        assert lin.value(impl="einsum", heads=2, chunk=128) == 2
        chose = telemetry.REGISTRY.get("attention.block_select_lowerings")
        assert chose.value(blocks=6, chosen=2, window=24, impl="jnp") == 1
        select = telemetry.REGISTRY.get("attention.select_lowerings")
        assert select.value(select=1, heads=2, group=2,
                            impl="composed") == 1
        kept = telemetry.REGISTRY.get("attention.block_keys_kept")
        assert kept.count() == steps
        assert kept.sum() == steps * BATCH * blocks.kept_pairs(T, 16, 2, 1,
                                                               24)
    finally:
        telemetry.disable()
        telemetry.reset()
    _close(seen[:2], losses, "loss of the first two steps")
    assert seen[-1] < seen[0] - 0.05, seen


def test_the_model_states_its_own_initialisation():
    """The embedding at 1 / scale_emb, unit-variance projections, the three
    that write to the stream over the UNCUT fan-in, the head at hidden /
    dim_model_base / sqrt(hidden), the sparse layer's q / k gammas at
    ``SPARSE_QK_GAMMA``, every other gamma one."""
    cfg = dict(SHARE, hidden_size=64, vocab_size=2048)
    sym = minicpm_sala.from_config(cfg, seq_len=T)
    mod = mx.mod.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH, T))],
             label_shapes=[("softmax_label", (BATCH, T))],
             for_training=False)
    mx.random.seed(5)
    np.random.seed(5)
    mod.init_params(initializer=mx.init.Normal(sigma=0.02))
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    want = {
        "embed_weight": 1 / 3.0,
        "layer0_q_proj_weight": 1 / 8, "layer0_v_proj_weight": 1 / 8,
        "layer0_attn_gate_proj_weight": 1 / 8,
        "layer1_linattn_k_proj_weight": 1 / 8,
        "layer1_linattn_g_proj_weight": 1 / 8,
        "layer1_gate_proj_weight": 1 / 8, "layer1_up_proj_weight": 1 / 8,
        # the uncut fan-in: 4 heads of 8, 4 heads of 8, 80 columns
        "layer0_o_proj_weight": 1 / 32 ** 0.5,
        "layer1_linattn_o_proj_weight": 1 / 32 ** 0.5,
        "layer1_down_proj_weight": 1 / 80 ** 0.5,
        "lm_head_weight": 8.0 / 16,
    }
    for name, sigma in want.items():
        assert got[name].std() == pytest.approx(sigma, rel=0.1), name
    for name in ("layer0_q_norm_gamma", "layer0_k_norm_gamma"):
        assert (got[name] == minicpm_sala.SPARSE_QK_GAMMA).all(), name
    for name in ("layer0_attn_norm_gamma", "layer1_ffn_norm_gamma",
                 "layer1_linattn_q_norm_gamma",
                 "layer2_linattn_o_norm_gamma", "final_norm_gamma"):
        assert (got[name] == 1).all(), name
