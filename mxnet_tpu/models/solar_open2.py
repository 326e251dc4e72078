"""Solar-Open2-250B (``model_type`` solar_open2; Upstage, 250B total, 15B
active): a decoder-only hybrid of Kimi Delta Attention layers whose
write strengths reach 2 (negative eigenvalues) and gated grouped-query
attention layers with no positional signal, three to one, over one shared
and 320 sigmoid-routed sparse experts in EVERY layer, as an ``mx.sym``
graph that ``Module.fit`` trains — whole, or as one chip's share of its
layers: a share of the experts, of both mixers' heads and of the
vocabulary.

The defaults are ``upstage/Solar-Open2-250B``'s ``config.json`` (hidden
4096; 48 pre-norm layers, RMSNorm eps 1e-5, numbered from 0 as
``gqa_layers`` numbers them: layers 0, 4, ..., 44 grouped attention, the
other 36 KDA (``gqa_interval`` 3); KDA of 64 heads with keys and values
of 128 and 4 conv taps, write strengths ``2 sigmoid``
(``kda_allow_neg_eigval``), the decay's and the gate's pre-activations
through low-rank pairs (``kda_use_full_proj`` false); grouped attention
of 64 query heads on 8 key/value heads of 128, NOTHING rotated
(``use_rope`` false), its output times a sigmoid gate
(``use_gqa_gate``); no dense layer (``first_k_dense_replace`` 0): every
layer one shared expert of 1280 beside 320 routed experts of 1280, top-8
by sigmoid score plus a selection bias, weights renormalised, scale 1;
vocabulary 196608, untied head). Per layer:

    h = h + mixer(attn_norm(h));   h = h + ffn(ffn_norm(h))

    KDA:  kda_o_proj(GatedDeltaNet(
              kda_q_proj(x), kda_k_proj(x), kda_v_proj(x),
              gate=kda_g_b_proj(kda_g_a_proj(x)),    # 4096 -> 128 -> H 128
              a=kda_f_b_proj(kda_f_a_proj(x)),       # 4096 -> 128 -> H 128
              b=kda_b_proj(x),                       # 4096 -> H
              gate_act="sigmoid", allow_neg_eigval=True))
    GQA:  o_proj(Attention(q_proj(x), k_proj(x), v_proj(x),
                           gate=attn_gate_proj(x), causal))
    ffn:  shared SwiGLU(x) + TopKMoE(x, scoring="sigmoid", select_bias,
                                     renorm_eps=1e-20)

then ``final_norm`` and ``lm_head``. The KDA mixer is
``lm_blocks.kda_mixer``, Kimi Linear's with one argument changed: the
write strength is ``2 sigmoid(b)``, so the transition ``I - beta k k^T``
of a token has an eigenvalue in (-1, 1) where Kimi's stays in (0, 1).
``Attention(with_gate=True)`` multiplies the kernel's output by
``sigmoid(gate)``, a gate per head and channel in float32, before
``o_proj`` (as ``models/afmoe.py``'s full layers, without their head
norms). ``data`` holds token ids ``[batch, seq_len]`` and
``softmax_label`` the next token at each position.

**One chip's share.** ``vocab_size`` the rows held, ``experts_held`` of
the router's ``num_experts`` from ``expert_offset`` on, their rows
compacted into ``share_rows_bound``; ``kda_heads``, ``num_heads`` and
``num_kv_heads`` the heads held. A held head is a whole head: its columns
of every projection (the second halves of the low-rank pairs among
them), its ``a_log``, its 128 channels of ``dt_bias``, its taps. The
first halves of the low-rank pairs, the router, the shared expert and the
norms are whole on every chip. Each mixer's ``o_proj`` and each expert
layer give this chip's part of the layer's result, and that partial
result goes on to the next layer. Nothing stands in for the chips that
hold the other heads and experts or for the exchange with them;
``models/solar_open2_reference.py`` is given the same share.

**Initialisation the model states itself** (``sym.Variable(init=)``), as
``models/kimi_linear.py``'s but for the embedding: zero selection biases,
the taps uniform in +-1/sqrt(taps), ``a_log = log(U(1, 16))`` a head and
``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in [0.001, 0.1]
over every one of the H K channels; the embedding Normal(``STREAM_RMS``)
= 8, ``models/afmoe.py``'s constant and for its reason. A token's own
vector has to stay the largest part of what the routers read, so that
seeded weights route near-uniformly, as a trained model's balanced
routers do. Here every mixer's ``o_proj`` is 4096 columns wide under
Normal(0.02), so a sub-layer's output joins the stream at an rms of
about 0.6, a KDA layer's a slowly varying one that neighbouring tokens
share: under a unit embedding the chip read a layer's held rows at
0.67-1.44 of uniform (1.07 at the most in layer 0, which reads the
embedding alone) and ``train_samples_s`` followed their sum to a quartile
spread of 0.25-0.35% of a 1% bound; at a stream of 8 0.90-1.16 and
0.16% (``PERF.md`` section 6, PR 62). The first loss does not depend on
it: the head reads the final norm's output.

Outputs: the loss per sequence behind ``MakeLoss``, then each layer's
row counts over all of the router's experts. Router, norm statistics,
the convolution's sum, write strengths, decays and their sums, the
triangular solve, the carried state, both gates, softmaxes and loss
arithmetic are float32 whatever ``dtype`` is.

What no key states, taken by the family's convention and listed under
``assumed`` in the configuration's file: the form of ``use_gqa_gate``
(the elementwise sigmoid gate on the attention output from a projection
of the block's input, Qiu et al., arXiv:2505.06708, as Qwen3-Next and
``afmoe`` ship it), the router's score (the Solar Open family's, which is
``glm4_moe``'s: sigmoid plus a selection bias, renormalised over ``sum +
1e-20``), no query/key norm and no bias. Departures from the published
training job, shared with the reference: the selection bias is a
parameter with no gradient that no rule moves, no auxiliary loss.
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import (expert_layer, head_and_loss, kda_mixer, linear,
                        mixer_block, swiglu)

KDA, GQA = "kda", "gqa"
# rms the embedding starts the stream at (the docstring says why)
STREAM_RMS = 8.0


def get_symbol(vocab_size=196608, hidden_size=4096,
               layer_types=tuple(GQA if i % 4 == 0 else KDA
                                 for i in range(48)),
               kda_heads=64, kda_head_dim=128, conv_kernel=4, kda_rank=128,
               allow_neg_eigval=True, chunk_size=64, num_heads=64,
               num_kv_heads=8, head_dim=128, num_experts=320,
               experts_held=0, expert_offset=0, share_rows_bound=0,
               experts_per_token=8, expert_width=1280, shared_experts=1,
               routed_scale=1.0, norm_topk_prob=True, seq_len=4096,
               rms_eps=1e-5, dtype="float32", embed_sigma=STREAM_RMS):
    """One layer an entry of ``layer_types`` (``kda`` or ``gqa``), every
    one over shared and routed experts. ``chunk_size`` is the program's
    own (tokens a chunk of the delta rule): it changes no mathematics."""
    q_width, kv_width = num_heads * head_dim, num_kv_heads * head_dim

    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    def delta_attention(x, p):
        return kda_mixer(x, p, hidden_size, seq_len, kda_heads, kda_head_dim,
                         kda_rank, conv_kernel, chunk_size, rms_eps,
                         allow_neg_eigval=allow_neg_eigval)

    def gated_attention(x, p):
        def proj(name, width):
            return positions(linear(x, p + name + "_proj", width), width)

        attn = csym.Attention(
            proj("q", q_width), proj("k", kv_width), proj("v", kv_width),
            with_gate=True, gate=proj("attn_gate", q_width),
            num_heads=num_heads, num_kv_heads=num_kv_heads, causal=True,
            name=p + "attn")
        return linear(sym.Reshape(attn, shape=(-1, q_width)), p + "o_proj",
                      hidden_size)

    counts = []

    def experts(x, p):
        moe, count = expert_layer(
            x, p, num_experts=num_experts, num_hidden=expert_width,
            top_k=experts_per_token, norm_topk_prob=norm_topk_prob,
            scoring="sigmoid", routed_scale=routed_scale, renorm_eps=1e-20,
            experts_held=experts_held, expert_offset=expert_offset,
            share_rows_bound=share_rows_bound)
        counts.append(count)
        if shared_experts:
            moe = moe + swiglu(x, p + "shared_",
                               shared_experts * expert_width, hidden_size)
        return moe

    mixers = {KDA: delta_attention, GQA: gated_attention}
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    # the residual stream is [tokens, hidden] throughout
    h = sym.Embedding(
        sym.Reshape(data, shape=(-1,)),
        weight=sym.Variable("embed_weight",
                            init=init.Normal(sigma=embed_sigma)),
        input_dim=vocab_size, output_dim=hidden_size, dtype=dtype,
        name="embed")
    for i, kind in enumerate(layer_types):
        if kind not in mixers:
            raise ValueError(
                "solar_open2: layer_types[%d] is %r; only %s and %s are "
                "built" % (i, kind, KDA, GQA))
        p = "layer%d_" % i
        # the norms are layer<i>_attn_norm and layer<i>_ffn_norm
        h = mixer_block(h, p + "attn_", rms_eps,
                        lambda x, _, p=p, kind=kind: mixers[kind](x, p))
        h = mixer_block(h, p + "ffn_", rms_eps,
                        lambda x, _, p=p: experts(x, p))
    return head_and_loss(h, label, counts, vocab_size, seq_len, rms_eps)


# keys whose value changes the mathematics and that this builder takes in
# one form only; ``ASSUMED_UNREAD`` are the keys nothing here reads (the
# configuration file lists them under ``assumed``)
_ONLY = {"kda_use_full_proj": False, "use_rope": False,
         "use_gqa_gate": True, "first_k_dense_replace": 0,
         "tie_word_embeddings": False}
ASSUMED_UNREAD = ("partial_rotary_factor", "rope_theta",
                  "intermediate_size", "max_position_embeddings")


def layer_kinds(config):
    """``gqa`` / ``kda`` a layer, numbered from 0 as ``gqa_layers`` is;
    ``gqa_interval`` (KDA layers between two grouped-attention layers)
    says the same and is held to the list."""
    n, listed = config["num_hidden_layers"], list(config["gqa_layers"])
    if listed != sorted(set(listed)) or any(not 0 <= i < n for i in listed):
        raise ValueError(
            "solar_open2.from_config: gqa_layers %s is not a rising list "
            "of layers in 0..%d" % (listed, n - 1))
    every = config.get("gqa_interval")
    if every is not None and listed != list(range(0, n, every + 1)):
        raise ValueError(
            "solar_open2.from_config: gqa_layers %s is not every %d-th "
            "layer from 0 (gqa_interval=%d)" % (listed, every + 1, every))
    return tuple(GQA if i in set(listed) else KDA for i in range(n))


def from_config(config, seq_len=None, dtype="float32", chunk_size=64):
    """The symbol of a published ``config.json`` (``model_type``
    solar_open2), given as a dict (``chunk_size`` is no key of it: the
    program's own, ``get_symbol``). A key that would change the
    mathematics and that this builder does not implement raises: full
    projections into the decay and the gate (``kda_use_full_proj`` true:
    their shape is not in the published row), a rotated attention layer
    (``use_rope`` true), an attention layer without its gate, a leading
    dense layer (``first_k_dense_replace`` > 0), tied embeddings, key/value
    heads of the delta rule other than its heads
    (``linear_attn_config.num_kv_heads`` other than null), a
    ``gqa_layers`` that ``gqa_interval`` contradicts.
    ``partial_rotary_factor``, ``rope_theta``, ``intermediate_size`` and
    ``max_position_embeddings`` (but as the default sequence length) are
    read by nothing (``ASSUMED_UNREAD``): nothing is rotated and no layer
    is dense.

    A share of the model is the same dict with the counts held in place
    of the published ones (``vocab_size``, ``n_routed_experts``,
    ``linear_attn_config.num_heads``, ``num_attention_heads``,
    ``num_key_value_heads``) and a group ``share`` beside them, as
    ``kanana2.from_config`` reads it."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("solar_open2.from_config: %s=%r is not "
                             "supported (only %r)" % (key, config[key], value))
    linear_cfg = config["linear_attn_config"]
    if linear_cfg.get("num_kv_heads") is not None:
        raise ValueError(
            "solar_open2.from_config: linear_attn_config.num_kv_heads=%r is "
            "not supported (only null: a key and a value a head)"
            % (linear_cfg["num_kv_heads"],))
    share = config.get("share", {})
    held = config["n_routed_experts"]
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=layer_kinds(config),
        kda_heads=linear_cfg["num_heads"],
        kda_head_dim=linear_cfg["head_dim"],
        conv_kernel=linear_cfg["short_conv_kernel_size"],
        kda_rank=linear_cfg["head_dim"],
        allow_neg_eigval=bool(config["kda_allow_neg_eigval"]),
        chunk_size=chunk_size, num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], num_experts=of,
        experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config.get("n_shared_experts") or 0,
        routed_scale=float(config.get("routed_scaling_factor") or 1.0),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
