"""MiMo-V2-Flash: a decoder-only LM of window-128 and full-attention
layers over sigmoid-routed sparse experts, as an ``mx.sym`` graph that
``Module.fit`` trains — whole, or as one chip's share of its layers.

The defaults are ``XiaomiMiMo/MiMo-V2-Flash``'s ``config.json`` (hidden
4096; 48 layers, layer 0 and every sixth after layer 5 with full
attention over 64 query / 4 key-value heads, the rest with a causal
window of 128 over 64 / 8 heads and a learnable sink logit per head;
query/key heads of 192 with RoPE on the first 64 dimensions, theta 5e6
in full and 1e4 in window layers; value heads of 128 scaled by 0.707;
layer 0 a dense SwiGLU of 16384, every other layer 256 experts of 2048,
top-8 by sigmoid score plus a selection bias, weights renormalised;
RMSNorm eps 1e-5; vocabulary 152576, untied head). Per layer:

    x = attn_norm(h)
    h = h + o_proj(Attention(RoPE(q_proj(x)), RoPE(k_proj(x)),
                             value_scale * v_proj(x)[, sink]))
    x = ffn_norm(h)
    h = h + down_proj(silu(gate_proj(x)) * up_proj(x))     # dense, or
    h = h + TopKMoE(x, scoring="sigmoid", select_bias)     # experts

then ``final_norm`` and ``lm_head``. ``data`` holds token ids ``[batch,
seq_len]`` and ``softmax_label`` the next token at each position.

**One chip's share.** Every count may be the share a chip holds of a
layer that several chips divide: ``num_heads`` / ``num_kv_heads`` (and
the ``swa_`` pair) the heads held, ``dense_width`` the dense columns
held, ``vocab_size`` the rows held, and ``experts_held`` of the
router's ``num_experts`` from ``expert_offset`` on, their rows
compacted into ``share_rows_bound`` (``TopKMoE``). Every width (hidden,
head, expert) stays the model's. The layer then computes its part of
each sum — the held heads' part of ``o_proj``, the held columns' part
of ``down_proj``, the held experts' part of the expert layer — and
nothing stands in for the chips that hold the rest or for the exchange
with them; ``models/mimo_v2_reference.py`` is given the same share.

Outputs: (0) the loss, one value per sequence — that sequence's mean
next-token cross-entropy, from float32 logits — behind ``MakeLoss``
(see ``models/olmoe.py``); then, per expert layer in order, the rows
each of the router's ``num_experts`` experts received, behind
``BlockGrad``. The logits are the internal ``lm_head_f32_output``.

Initialisation is part of the model (``sym.Variable(init=...)``, which
every initializer honours): the embedding is Normal(``embed_sigma`` =
1), sinks and selection biases are zeros; matrices take the caller's
initializer (Normal(0.02) in the benchmark) and gammas are 1. A unit
embedding keeps a token's own vector the largest part of what the
routers read, so seeded weights route near-uniformly, as a trained
model's balanced routers do (PERF.md section 6).

Departures from the published training job, shared with the reference:
no multi-token-prediction layers (not in ``config.json``), the
selection bias is a parameter with no gradient that no rule moves, no
auxiliary loss; router, norm statistics, RoPE, softmaxes, sink and loss
arithmetic are float32 whatever ``dtype`` is. ``dtype="bfloat16"``
makes every parameter bf16.
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import expert_layer, head_and_loss, linear, swiglu


def get_symbol(vocab_size=152576, hidden_size=4096, layer_pattern=None,
               moe_pattern=None, num_heads=64, num_kv_heads=4,
               swa_num_heads=64, swa_num_kv_heads=8, head_dim=192,
               v_head_dim=128, rotary=64, rope_theta=5e6,
               swa_rope_theta=1e4, window=128, swa_sink=True,
               full_sink=False, value_scale=0.707, dense_width=16384,
               num_experts=256, experts_held=0, expert_offset=0,
               share_rows_bound=0, experts_per_token=8, expert_width=2048,
               norm_topk_prob=True, scoring="sigmoid", seq_len=4096,
               rms_eps=1e-5, dtype="float32", embed_sigma=1.0):
    """``layer_pattern[l]`` 1: a window layer, 0: full attention;
    ``moe_pattern[l]`` 1: experts, 0: the dense feed-forward. The
    defaults are the published 48 layers."""
    if layer_pattern is None:
        layer_pattern = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]
    if moe_pattern is None:
        moe_pattern = [0] + [1] * (len(layer_pattern) - 1)
    if len(moe_pattern) != len(layer_pattern):
        raise ValueError("mimo_v2: layer_pattern and moe_pattern differ "
                         "in length")

    def norm(x, name):
        return csym.RMSNorm(x, eps=rms_eps, name=name)

    def heads(x, width):  # [B*T, w] -> [B, T, w]: the ops see positions
        return sym.Reshape(x, shape=(-1, seq_len, width))

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    # the residual stream is [tokens, hidden] throughout
    h = sym.Embedding(
        sym.Reshape(data, shape=(-1,)),
        weight=sym.Variable("embed_weight",
                            init=init.Normal(sigma=embed_sigma)),
        input_dim=vocab_size, output_dim=hidden_size, dtype=dtype,
        name="embed")
    counts = []
    for i, (windowed, experts) in enumerate(zip(layer_pattern,
                                                moe_pattern)):
        p = "layer%d_" % i
        n_q, n_kv = ((swa_num_heads, swa_num_kv_heads) if windowed
                     else (num_heads, num_kv_heads))
        theta = swa_rope_theta if windowed else rope_theta
        x = norm(h, p + "attn_norm")
        q = heads(linear(x, p + "q_proj", n_q * head_dim), n_q * head_dim)
        k = heads(linear(x, p + "k_proj", n_kv * head_dim),
                  n_kv * head_dim)
        v = heads(linear(x, p + "v_proj", n_kv * v_head_dim) * value_scale,
                  n_kv * v_head_dim)
        q = csym.RoPE(q, num_heads=n_q, theta=theta, rotary_dim=rotary,
                      name=p + "q_rope")
        k = csym.RoPE(k, num_heads=n_kv, theta=theta, rotary_dim=rotary,
                      name=p + "k_rope")
        sink = {}
        if swa_sink if windowed else full_sink:
            sink = dict(with_sink=True, sink=sym.Variable(
                p + "attn_sink", init=init.Zero()))
        attn = csym.Attention(
            q, k, v, num_heads=n_q, num_kv_heads=n_kv, causal=True,
            window=window if windowed else 0, name=p + "attn", **sink)
        attn = sym.Reshape(attn, shape=(-1, n_q * v_head_dim))
        h = h + linear(attn, p + "o_proj", hidden_size)
        x = norm(h, p + "ffn_norm")
        if not experts:
            h = h + swiglu(x, p, dense_width, hidden_size)
            continue
        moe, count = expert_layer(
            x, p, num_experts=num_experts, num_hidden=expert_width,
            top_k=experts_per_token, norm_topk_prob=norm_topk_prob,
            scoring=scoring, experts_held=experts_held,
            expert_offset=expert_offset,
            share_rows_bound=share_rows_bound)
        h = h + moe
        counts.append(count)
    return head_and_loss(h, label, counts, vocab_size, seq_len, rms_eps)


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"attention_bias": False, "hidden_act": "silu",
         "tie_word_embeddings": False, "n_shared_experts": None,
         "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
         "routed_scaling_factor": None, "rope_scaling": None}


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type``
    mimo_v2_flash), given as a dict. A key that would change the
    mathematics and that this builder does not implement raises.

    A share of the model is the same dict with the counts held in place
    of the published ones (``num_attention_heads``, ``vocab_size``,
    ``n_routed_experts``, ...) and a group ``share`` beside them:
    ``experts_of`` (the router's width where ``n_routed_experts`` counts
    the experts held), ``expert_offset``, ``share_rows_bound`` and
    ``dense_columns_held`` (of ``intermediate_size``)."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("mimo_v2.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    if config.get("scoring_func", "sigmoid") not in ("sigmoid", "softmax"):
        raise ValueError("mimo_v2.from_config: scoring_func=%r is not "
                         "supported" % (config["scoring_func"],))
    window = config["sliding_window"]
    for key in ("sliding_window_size", "attention_chunk_size"):
        # two more names the config gives the window; a chunk of another
        # size would be another mask
        if config.get(key, window) != window:
            raise ValueError(
                "mimo_v2.from_config: %s=%r differs from sliding_window=%r"
                % (key, config[key], window))
    for key in ("head_dim", "v_head_dim", "num_attention_heads"):
        # one Attention/RoPE geometry for both kinds of layer but the
        # key/value head count
        if config.get("swa_" + key, config[key]) != config[key]:
            raise ValueError(
                "mimo_v2.from_config: swa_%s=%r differs from %s=%r"
                % (key, config["swa_" + key], key, config[key]))
    layers = config["num_hidden_layers"]
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        if len(config[key]) != layers:
            raise ValueError(
                "mimo_v2.from_config: %s has %d entries for %d layers"
                % (key, len(config[key]), layers))
    share = config.get("share", {})
    # its own count of the rotated dimensions, not the reference's
    rotary = int(config["head_dim"] * config.get("partial_rotary_factor", 1.0))
    held = config["n_routed_experts"]
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_pattern=config["hybrid_layer_pattern"],
        moe_pattern=config["moe_layer_freq"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        swa_num_heads=config["swa_num_attention_heads"],
        swa_num_kv_heads=config["swa_num_key_value_heads"],
        head_dim=config["head_dim"], v_head_dim=config["v_head_dim"],
        rotary=rotary - rotary % 2,
        rope_theta=float(config["rope_theta"]),
        swa_rope_theta=float(config["swa_rope_theta"]), window=window,
        swa_sink=config["add_swa_attention_sink_bias"],
        full_sink=config["add_full_attention_sink_bias"],
        value_scale=config["attention_value_scale"],
        dense_width=share.get("dense_columns_held",
                              config["intermediate_size"]),
        num_experts=of, experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        scoring=config["scoring_func"],
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["layernorm_epsilon"], dtype=dtype)
