"""LFM2-24B-A2B (``model_type`` lfm2_moe): a decoder-only hybrid of gated
short-convolution layers and grouped-head attention over a dense SwiGLU
(the leading layers) or sigmoid-routed sparse experts, with a head tied
to the embedding, as an ``mx.sym`` graph that ``Module.fit`` trains —
whole, or as one chip's share of its layers.

The defaults are ``LiquidAI/LFM2-24B-A2B``'s ``config.json`` (hidden
2048; 40 layers whose mixer is an entry of ``layer_types``: ``conv`` for
30 and ``full_attention`` for every fourth from layer 2 on; 32 query
heads on 8 key/value heads of 64, RoPE theta 1e6; the first 2 layers a
dense SwiGLU of 11776, the other 38 with 64 experts of 1536, top-4 by
sigmoid score plus a selection bias, weights renormalised over their sum
plus 1e-6, no shared expert; RMSNorm eps 1e-5; vocabulary 65536). Per
layer:

    x = operator_norm(h)
    h = h + conv_out_proj(ShortConv(conv_in_proj(x)))        # conv, or
    h = h + o_proj(Attention(RoPE(q_norm(q_proj(x))),
                             RoPE(k_norm(k_proj(x))),
                             v_proj(x)))                     # full_attention
    x = ffn_norm(h)
    h = h + down_proj(silu(gate_proj(x)) * up_proj(x))       # dense, or
    h = h + TopKMoE(x, scoring="sigmoid", select_bias,
                    renorm_eps=1e-6)                         # routed

then ``final_norm`` (published as ``embedding_norm``) and the head,
which reads ``embed_weight``. ``ShortConv`` owns the taps
(``layer<i>_conv_weight`` [taps, hidden], no bias); ``conv_in_proj``
(hidden -> 3 x hidden, ``B | C | x``) and ``conv_out_proj`` are
``FullyConnected`` nodes. ``q_norm`` and ``k_norm`` are an RMSNorm over
each head's own ``head_dim`` columns, one gamma of ``head_dim`` shared
by the heads, BEFORE the rotation (half-rotation pairs over the whole
head). ``data`` holds token ids ``[batch, seq_len]`` and
``softmax_label`` the next token at each position.

**One chip's share.** As ``models/kanana2.py``: ``vocab_size`` the rows
held (of the one tied matrix: the embedding's rows and the head's alike),
``experts_held`` of the router's ``num_experts`` from ``expert_offset``
on, their rows compacted into ``share_rows_bound``. The convolution
operators, attention, the router and the dense feed-forward stay whole:
every chip of the deployment computes them alike, on its own sequences.
Nothing stands in for the chips that hold the other experts;
``models/lfm2_reference.py`` is given the same share.

**Initialisation the model states itself** (``sym.Variable(init=)``):
zero selection biases as the other LM symbols; the taps uniform in
+-1/sqrt(taps) (a ``Conv1d``'s default); and the embedding
Normal(``embed_sigma`` = 0.02, the family's ``initializer_range``), NOT
the unit embedding of the untied symbols: the head multiplies the final
norm's unit-rms output by this same matrix, so a unit embedding would
start every logit at a standard deviation of sqrt(hidden) = 45 and the
input token's own logit near hidden / rms(h) in the hundreds.

Outputs and what is float32 are ``models/mimo_v2.py``'s: the loss per
sequence behind ``MakeLoss``, then each expert layer's row counts over
all of the router's experts; router, norm statistics (the heads' too),
RoPE, the convolution's gates and sum, softmax and loss arithmetic in
float32 whatever ``dtype`` is.

Departures from the published training job, shared with the reference:
the selection bias is a parameter with no gradient that no rule moves
(``use_expert_bias`` says that it exists and takes part in the choice),
no auxiliary loss.
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import expert_layer, head_and_loss, linear, swiglu

_PERIOD = ("conv", "conv", "full_attention", "conv")


def get_symbol(vocab_size=65536, hidden_size=2048,
               layer_types=_PERIOD * 10, dense_layers=2, num_heads=32,
               num_kv_heads=8, head_dim=64, rope_theta=1e6, conv_taps=3,
               dense_width=11776, num_experts=64, experts_held=0,
               expert_offset=0, share_rows_bound=0, experts_per_token=4,
               expert_width=1536, routed_scale=1.0, norm_topk_prob=True,
               renorm_eps=1e-6, seq_len=8192, rms_eps=1e-5, dtype="float32",
               embed_sigma=0.02):
    """One layer an entry of ``layer_types`` (``conv`` or
    ``full_attention``); the first ``dense_layers`` have the dense
    feed-forward, the rest routed experts."""
    def norm(x, name):
        return csym.RMSNorm(x, eps=rms_eps, name=name)

    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    def conv(x, p):
        y = csym.ShortConv(
            positions(linear(x, p + "conv_in_proj", 3 * hidden_size),
                      3 * hidden_size),
            conv_weight=sym.Variable(p + "conv_weight", init=init.Uniform(
                scale=conv_taps ** -0.5)),
            conv_kernel=conv_taps, name=p + "conv")
        return linear(sym.Reshape(y, shape=(-1, hidden_size)),
                      p + "conv_out_proj", hidden_size)

    def head_norm(x, name):  # over each head's own columns, one gamma
        return norm(sym.Reshape(x, shape=(-1, head_dim)), name)

    def attention(x, p):
        q, k = (csym.RoPE(positions(head_norm(
            linear(x, p + name + "_proj", heads * head_dim),
            p + name + "_norm"), heads * head_dim),
            num_heads=heads, theta=rope_theta, name=p + name + "_rope")
            for name, heads in (("q", num_heads), ("k", num_kv_heads)))
        v = positions(linear(x, p + "v_proj", num_kv_heads * head_dim),
                      num_kv_heads * head_dim)
        attn = csym.Attention(q, k, v, num_heads=num_heads,
                              num_kv_heads=num_kv_heads, causal=True,
                              name=p + "attn")
        return linear(sym.Reshape(attn, shape=(-1, num_heads * head_dim)),
                      p + "o_proj", hidden_size)

    mixers = {"conv": conv, "full_attention": attention}
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = sym.Variable("embed_weight", init=init.Normal(sigma=embed_sigma))
    # the residual stream is [tokens, hidden] throughout
    h = sym.Embedding(sym.Reshape(data, shape=(-1,)), weight=embed,
                      input_dim=vocab_size, output_dim=hidden_size,
                      dtype=dtype, name="embed")
    counts = []
    for i, kind in enumerate(layer_types):
        if kind not in mixers:
            raise ValueError(
                "lfm2: layer %d of layer_types is %r; only conv and "
                "full_attention are built" % (i, kind))
        p = "layer%d_" % i
        h = h + mixers[kind](norm(h, p + "operator_norm"), p)
        x = norm(h, p + "ffn_norm")
        if i < dense_layers:
            h = h + swiglu(x, p, dense_width, hidden_size)
            continue
        moe, count = expert_layer(
            x, p, num_experts=num_experts, num_hidden=expert_width,
            top_k=experts_per_token, norm_topk_prob=norm_topk_prob,
            scoring="sigmoid", routed_scale=routed_scale,
            renorm_eps=renorm_eps, experts_held=experts_held,
            expert_offset=expert_offset, share_rows_bound=share_rows_bound)
        h = h + moe
        counts.append(count)
    return head_and_loss(h, label, counts, vocab_size, seq_len, rms_eps,
                         tied_to=embed)


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"conv_bias": False, "use_expert_bias": True, "norm_topk_prob": True,
         "tie_word_embeddings": True, "attention_bias": False}


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type``
    lfm2_moe), given as a dict. A key that would change the mathematics
    and that this builder does not implement (a bias on the convolution,
    a mixer ``layer_types`` does not name as ``conv`` or
    ``full_attention``, experts chosen without the bias or weighted
    without the renormalisation, an untied head, a scaled RoPE) raises.

    ``head_dim`` is ``hidden_size / num_attention_heads`` where the file
    gives none. A share of the model is the same dict with the counts
    held in place of the published ones (``vocab_size``,
    ``num_experts``) and a group ``share`` beside them, as
    ``kanana2.from_config`` reads it."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("lfm2.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    layer_types = tuple(config["layer_types"])
    if len(layer_types) != config["num_hidden_layers"]:
        raise ValueError(
            "lfm2.from_config: layer_types has %d entries, "
            "num_hidden_layers=%r" % (len(layer_types),
                                      config["num_hidden_layers"]))
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError("lfm2.from_config: rope_type=%r is not supported "
                         "(only 'default')" % (rope["rope_type"],))
    heads = config["num_attention_heads"]
    share = config.get("share", {})
    held = config["num_experts"]
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=layer_types, dense_layers=config["num_dense_layers"],
        num_heads=heads, num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or config["hidden_size"] // heads,
        rope_theta=float(rope["rope_theta"]),
        conv_taps=config["conv_L_cache"],
        dense_width=config["intermediate_size"], num_experts=of,
        experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        routed_scale=float(config.get("routed_scaling_factor") or 1.0),
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["norm_eps"], dtype=dtype)
