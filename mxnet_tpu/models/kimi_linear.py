"""Kimi-Linear-48B-A3B (``model_type`` kimi_linear; Kimi Linear,
arXiv:2510.26692): a decoder-only hybrid of Kimi Delta Attention layers
(a delta rule whose decay is a vector a head) and latent-attention layers
with no positional signal, three to one, over shared and sigmoid-routed
sparse experts, as an ``mx.sym`` graph that ``Module.fit`` trains —
whole, or as one chip's share of its layers.

The defaults are ``moonshotai/Kimi-Linear-48B-A3B-Instruct``'s
``config.json`` (hidden 2304; 27 pre-norm layers, RMSNorm eps 1e-5; the
1-based lists ``linear_attn_config.kda_layers`` (20 layers) and
``full_attn_layers`` (4, 8, ..., 24, 27) say which mixer a layer has;
KDA of 32 heads with keys and values of 128 and 4 conv taps; latent
attention of 32 heads whose keys and values are projected up from one
512-wide latent a token, a head's key 128 dimensions of its own beside
64 that every head shares, NOTHING rotated (``mla_use_nope``); layer 1 a
dense SwiGLU of 9216 (``first_k_dense_replace`` 1), every other layer
one shared expert of 1024 beside 256 routed experts of 1024, top-8 by
sigmoid score plus a selection bias, weights renormalised and times
2.446; vocabulary 163840, untied head). Per layer:

    h = h + mixer(attn_norm(h));   h = h + ffn(ffn_norm(h))

    KDA:     kda_o_proj(GatedDeltaNet(
                 kda_q_proj(x), kda_k_proj(x), kda_v_proj(x),
                 gate=kda_g_b_proj(kda_g_a_proj(x)),    # 2304 -> 128 -> 4096
                 a=kda_f_b_proj(kda_f_a_proj(x)),       # 2304 -> 128 -> 4096
                 b=kda_b_proj(x),                       # 2304 -> 32
                 gate_act="sigmoid", allow_neg_eigval=False))
    latent:  o_proj(LatentAttention(q_proj(x), kv_a_proj(x),
                                    rotary=False))
    ffn:     down_proj(silu(gate_proj(x)) * up_proj(x))        # dense, or
             shared SwiGLU(x) + TopKMoE(x, scoring="sigmoid",
                                        select_bias, routed_scale=2.446)

then ``final_norm`` and ``lm_head``. ``GatedDeltaNet`` takes the decay a
CHANNEL by the shape of ``a`` (``ops/transformer.py::channel_delta_rule``)
and owns the convolution's taps (``layer<i>_kda_conv_weight`` [taps, 3 H
K], no bias), the decay rates (``_a_log`` [H]), the step sizes' bias
(``_dt_bias`` [H K]) and the gated norm's scale (``_norm_gamma`` [V]).
The two low-rank pairs (``f_a`` / ``f_b`` into the decay, ``g_a`` /
``g_b`` into the gate; rank ``linear_attn_config.head_dim``, no bias) and
``b_proj`` are ``FullyConnected`` nodes beside the four wide ones
(``q``, ``k``, ``v``, ``o``). ``LatentAttention`` owns the latent's norm
and the up-projection as in ``models/kanana2.py``. The symbol's layers
are numbered from 0: ``layer0_`` is the published layer 1. ``data``
holds token ids ``[batch, seq_len]`` and ``softmax_label`` the next
token at each position.

**One chip's share.** As ``models/kanana2.py``: ``vocab_size`` the rows
held, ``experts_held`` of the router's ``num_experts`` from
``expert_offset`` on, their rows compacted into ``share_rows_bound``.
Both mixers, the shared expert, the router and the dense layer stay
whole: every chip of the deployment computes them alike, on its own
sequences. Nothing stands in for the chips that hold the other experts
or for the exchange with them; ``models/kimi_linear_reference.py`` is
given the same share.

**Initialisation the model states itself** (``sym.Variable(init=)``), as
``models/olmo_hybrid.py``'s: a unit embedding, zero selection biases,
the taps uniform in +-1/sqrt(taps), ``a_log = log(U(1, 16))`` a head and
``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in [0.001, 0.1]
over every one of the H K channels.

Outputs: the loss per sequence behind ``MakeLoss``, then each expert
layer's row counts over all of the router's experts. Router, norm
statistics (the latent's too), the convolution's sum, write strengths,
decays and their sums, the triangular solve, the carried state, the
gate, softmaxes and loss arithmetic are float32 whatever ``dtype`` is.

Departures from the published training job, shared with the reference:
the selection bias is a parameter with no gradient that no rule moves,
no auxiliary loss, no multi-token-prediction layer
(``num_nextn_predict_layers`` 0).
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import (expert_layer, head_and_loss, kda_mixer, linear,
                        mixer_block, swiglu)

KDA, FULL = "kda", "full_attention"
_PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)


def get_symbol(vocab_size=163840, hidden_size=2304,
               layer_types=tuple(FULL if i in _PUBLISHED_FULL else KDA
                                 for i in range(1, 28)),
               dense_layers=1, kda_heads=32, kda_head_dim=128, conv_kernel=4,
               kda_rank=128, chunk_size=64, num_heads=32, nope_head_dim=128,
               rope_head_dim=64, v_head_dim=128, latent_width=512,
               dense_width=9216, num_experts=256, experts_held=0,
               expert_offset=0, share_rows_bound=0, experts_per_token=8,
               expert_width=1024, shared_experts=1, routed_scale=2.446,
               norm_topk_prob=True, scoring="sigmoid", seq_len=8192,
               rms_eps=1e-5, dtype="float32", embed_sigma=1.0):
    """One layer an entry of ``layer_types`` (``kda`` or
    ``full_attention``); the first ``dense_layers`` have the dense
    feed-forward, the rest shared and routed experts. ``chunk_size`` is
    the program's own (tokens a chunk of the delta rule): it changes no
    mathematics."""
    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    def delta_attention(x, p):
        return kda_mixer(x, p, hidden_size, seq_len, kda_heads, kda_head_dim,
                         kda_rank, conv_kernel, chunk_size, rms_eps,
                         allow_neg_eigval=False)

    q_width = num_heads * (nope_head_dim + rope_head_dim)

    def latent_attention(x, p):
        attn = csym.LatentAttention(
            positions(linear(x, p + "q_proj", q_width), q_width),
            positions(linear(x, p + "kv_a_proj",
                             latent_width + rope_head_dim),
                      latent_width + rope_head_dim),
            num_heads=num_heads, rope_dim=rope_head_dim,
            v_head_dim=v_head_dim, eps=rms_eps, rotary=False,
            name=p + "attn")
        return linear(sym.Reshape(attn, shape=(-1, num_heads * v_head_dim)),
                      p + "o_proj", hidden_size)

    counts = []

    def experts(x, p):
        moe, count = expert_layer(
            x, p, num_experts=num_experts, num_hidden=expert_width,
            top_k=experts_per_token, norm_topk_prob=norm_topk_prob,
            scoring=scoring, routed_scale=routed_scale,
            experts_held=experts_held, expert_offset=expert_offset,
            share_rows_bound=share_rows_bound)
        counts.append(count)
        if shared_experts:
            moe = moe + swiglu(x, p + "shared_",
                               shared_experts * expert_width, hidden_size)
        return moe

    def dense(x, p):
        return swiglu(x, p, dense_width, hidden_size)

    mixers = {KDA: delta_attention, FULL: latent_attention}
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
                "kimi_linear: layer_types[%d] is %r; only %s and %s are "
                "built" % (i, kind, KDA, FULL))
        p = "layer%d_" % i
        # the norms are layer<i>_attn_norm and layer<i>_ffn_norm
        h = mixer_block(h, p + "attn_", rms_eps,
                        lambda x, _, p=p, kind=kind: mixers[kind](x, p))
        ffn = dense if i < dense_layers else experts
        h = mixer_block(h, p + "ffn_", rms_eps,
                        lambda x, _, p=p, ffn=ffn: ffn(x, p))
    return head_and_loss(h, label, counts, vocab_size, seq_len, rms_eps)


# keys whose value changes the mathematics and that this builder takes in
# one form only; ``ASSUMED_UNREAD`` are the keys nothing here reads (the
# configuration file lists them under ``assumed``)
_ONLY = {"hidden_act": "silu", "tie_word_embeddings": False,
         "q_lora_rank": None, "rope_scaling": None, "moe_layer_freq": 1,
         "num_expert_group": 1, "topk_group": 1,
         "num_nextn_predict_layers": 0}
ASSUMED_UNREAD = ("head_dim", "num_key_value_heads", "rope_theta",
                  "use_grouped_topk", "model_max_length")


def layer_kinds(config):
    """``kda`` / ``full_attention`` a layer, from the 1-based lists
    ``linear_attn_config.kda_layers`` and ``full_attn_layers``, which
    must partition 1..``num_hidden_layers``."""
    linear_cfg = config["linear_attn_config"]
    n = config["num_hidden_layers"]
    kda, full = linear_cfg["kda_layers"], linear_cfg["full_attn_layers"]
    both = sorted(list(kda) + list(full))
    if both != list(range(1, n + 1)):
        raise ValueError(
            "kimi_linear.from_config: kda_layers %s and full_attn_layers %s "
            "do not partition the layers 1..%d (an overlap, a gap or an "
            "index out of range)" % (list(kda), list(full), n))
    return tuple(FULL if i in set(full) else KDA for i in range(1, n + 1))


def from_config(config, seq_len=None, dtype="float32", chunk_size=64):
    """The symbol of a published ``config.json`` (``model_type``
    kimi_linear), given as a dict (``chunk_size`` is no key of it: the
    program's own, ``get_symbol``). A key that would change the
    mathematics and that this builder does not implement raises: a
    rotated latent attention (``mla_use_nope`` false: the rotated
    variant's convention is not in the published row), a query latent,
    grouped routing, a router activation other than sigmoid or softmax,
    a layer frequency other than every layer after the dense ones, tied
    embeddings, a multi-token-prediction layer.
    ``head_dim`` 72, ``num_key_value_heads`` and ``rope_theta`` are read
    by nothing (``ASSUMED_UNREAD``): the latent layers' widths are the
    five MLA keys, and nothing is rotated.

    A share of the model is the same dict with the counts held in place
    of the published ones (``vocab_size``, ``num_experts``) and a group
    ``share`` beside them, as ``kanana2.from_config`` reads it."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("kimi_linear.from_config: %s=%r is not "
                             "supported (only %r)" % (key, config[key], value))
    if config["moe_router_activation_func"] not in ("sigmoid", "softmax"):
        raise ValueError(
            "kimi_linear.from_config: moe_router_activation_func=%r is not "
            "supported" % (config["moe_router_activation_func"],))
    if not config.get("mla_use_nope", False):
        raise ValueError(
            "kimi_linear.from_config: mla_use_nope=%r is not supported (only "
            "true: latent attention without a rotary embedding)"
            % (config.get("mla_use_nope"),))
    linear_cfg = config["linear_attn_config"]
    share = config.get("share", {})
    held = config["num_experts"]
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=layer_kinds(config),
        dense_layers=config["first_k_dense_replace"],
        kda_heads=linear_cfg["num_heads"],
        kda_head_dim=linear_cfg["head_dim"],
        conv_kernel=linear_cfg["short_conv_kernel_size"],
        kda_rank=linear_cfg["head_dim"], chunk_size=chunk_size,
        num_heads=config["num_attention_heads"],
        nope_head_dim=config["qk_nope_head_dim"],
        rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], latent_width=config["kv_lora_rank"],
        dense_width=config["intermediate_size"],
        num_experts=of, experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_token"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config.get("num_shared_experts") or 0,
        routed_scale=config.get("routed_scaling_factor") or 1.0,
        norm_topk_prob=config["moe_renormalize"],
        scoring=config["moe_router_activation_func"],
        seq_len=seq_len or config["model_max_length"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
