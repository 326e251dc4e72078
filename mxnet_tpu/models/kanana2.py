"""Kanana-2-30B-A3B (``model_type`` deepseek_v3): a decoder-only LM of
latent attention (MLA) over shared and sigmoid-routed sparse experts, as
an ``mx.sym`` graph that ``Module.fit`` trains — whole, or as one chip's
share of its layers.

The defaults are ``kakaocorp/kanana-2-30b-a3b-instruct-2601``'s
``config.json`` (hidden 2048; 48 layers; 32 heads whose keys and values
are projected up from one 512-wide latent a token, no query latent; a
head's query and key are 128 un-rotated dimensions beside 64 rotary
ones, the rotary key shared by every head, interleaved pairs, theta
1e6; value heads of 128; layer 0 a dense SwiGLU of 6144, every other
layer 2 shared experts (one SwiGLU of 2 x 768) beside 128 routed
experts of 768, top-6 by sigmoid score plus a selection bias, weights
renormalised and times 2.448; RMSNorm eps 1e-6; vocabulary 128256,
untied head). Per layer:

    x = attn_norm(h)
    h = h + o_proj(LatentAttention(q_proj(x), kv_a_proj(x)))
    x = ffn_norm(h)
    h = h + down_proj(silu(gate_proj(x)) * up_proj(x))     # dense, or
    h = h + shared_down_proj(silu(shared_gate_proj(x))
                             * shared_up_proj(x))          # shared and
          + TopKMoE(x, scoring="sigmoid", select_bias,
                    routed_scale=2.448)                    # routed

then ``final_norm`` and ``lm_head``. ``LatentAttention`` owns the
latent's norm (``layer<i>_attn_latent_gamma``) and the up-projection
(``layer<i>_attn_up_weight``: ``kv_b_proj``, a head's 128 key rows then
its 128 value rows). ``data`` holds token ids ``[batch, seq_len]`` and
``softmax_label`` the next token at each position.

**One chip's share.** As ``models/mimo_v2.py``: ``vocab_size`` the rows
held, ``experts_held`` of the router's ``num_experts`` from
``expert_offset`` on, their rows compacted into ``share_rows_bound``.
Attention, the latent projections, the shared experts, the router and
the dense layer stay whole: every chip of the deployment computes them
alike, on its own sequences. Nothing stands in for the chips that hold
the other experts or for the exchange with them;
``models/kanana2_reference.py`` is given the same share.

Outputs, initialisation and what is float32 are ``models/mimo_v2.py``'s:
the loss per sequence behind ``MakeLoss``, then each expert layer's row
counts over all of the router's experts; a unit embedding and zero
selection biases stated through ``sym.Variable(init=)``; router, norm
statistics (the latent's too), RoPE, softmax and loss arithmetic in
float32 whatever ``dtype`` is.

Departures from the published training job, shared with the reference:
the selection bias is a parameter with no gradient that no rule moves,
no auxiliary loss.
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import expert_layer, head_and_loss, linear, swiglu


def get_symbol(vocab_size=128256, hidden_size=2048, num_layers=48,
               dense_layers=1, num_heads=32, nope_head_dim=128,
               rope_head_dim=64, v_head_dim=128, latent_width=512,
               rope_theta=1e6, rope_interleave=True, dense_width=6144,
               num_experts=128, experts_held=0, expert_offset=0,
               share_rows_bound=0, experts_per_token=6, expert_width=768,
               shared_experts=2, routed_scale=2.448, norm_topk_prob=True,
               scoring="sigmoid", seq_len=8192, rms_eps=1e-6,
               dtype="float32", embed_sigma=1.0):
    """The first ``dense_layers`` layers have the dense feed-forward,
    the rest shared and routed experts."""
    def norm(x, name):
        return csym.RMSNorm(x, eps=rms_eps, name=name)

    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    q_width = num_heads * (nope_head_dim + rope_head_dim)
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
    for i in range(num_layers):
        p = "layer%d_" % i
        x = norm(h, p + "attn_norm")
        attn = csym.LatentAttention(
            positions(linear(x, p + "q_proj", q_width), q_width),
            positions(linear(x, p + "kv_a_proj", latent_width + rope_head_dim),
                      latent_width + rope_head_dim),
            num_heads=num_heads, rope_dim=rope_head_dim,
            v_head_dim=v_head_dim, theta=rope_theta, eps=rms_eps,
            interleave=rope_interleave, name=p + "attn")
        attn = sym.Reshape(attn, shape=(-1, num_heads * v_head_dim))
        h = h + linear(attn, p + "o_proj", hidden_size)
        x = norm(h, p + "ffn_norm")
        if i < dense_layers:
            h = h + swiglu(x, p, dense_width, hidden_size)
            continue
        moe, count = expert_layer(
            x, p, num_experts=num_experts, num_hidden=expert_width,
            top_k=experts_per_token, norm_topk_prob=norm_topk_prob,
            scoring=scoring, routed_scale=routed_scale,
            experts_held=experts_held, expert_offset=expert_offset,
            share_rows_bound=share_rows_bound)
        if shared_experts:
            moe = moe + swiglu(x, p + "shared_",
                               shared_experts * expert_width, hidden_size)
        h = h + moe
        counts.append(count)
    return head_and_loss(h, label, counts, vocab_size, seq_len, rms_eps)


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"attention_bias": False, "hidden_act": "silu",
         "tie_word_embeddings": False, "q_lora_rank": None, "n_group": 1,
         "topk_group": 1, "topk_method": "noaux_tc", "rope_scaling": None,
         "moe_layer_freq": 1}


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type``
    deepseek_v3 as Kanana-2 ships it), given as a dict. A key that would
    change the mathematics and that this builder does not implement (a
    query latent, grouped routing, a scaled RoPE, a projection bias, a
    layer frequency other than every layer after the dense ones) raises.

    A share of the model is the same dict with the counts held in place
    of the published ones (``vocab_size``, ``n_routed_experts``) and a
    group ``share`` beside them: ``experts_of`` (the router's width where
    ``n_routed_experts`` counts the experts held), ``expert_offset`` and
    ``share_rows_bound``."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("kanana2.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    if config.get("scoring_func", "sigmoid") not in ("sigmoid", "softmax"):
        raise ValueError("kanana2.from_config: scoring_func=%r is not "
                         "supported" % (config["scoring_func"],))
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    if config.get("qk_head_dim", nope + rope) != nope + rope:
        raise ValueError(
            "kanana2.from_config: qk_head_dim=%r is not qk_nope_head_dim + "
            "qk_rope_head_dim = %d" % (config["qk_head_dim"], nope + rope))
    if config.get("head_dim", rope) != rope:
        # deepseek_v3's head_dim is the rotary width, nothing else
        raise ValueError(
            "kanana2.from_config: head_dim=%r differs from "
            "qk_rope_head_dim=%r" % (config["head_dim"], rope))
    heads = config["num_attention_heads"]
    if config.get("num_key_value_heads", heads) != heads:
        raise ValueError(
            "kanana2.from_config: num_key_value_heads=%r differs from "
            "num_attention_heads=%r (the up-projection gives every head "
            "its own key and value)" % (config["num_key_value_heads"], heads))
    share = config.get("share", {})
    held = config["n_routed_experts"]
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"], num_heads=heads,
        nope_head_dim=nope, rope_head_dim=rope,
        v_head_dim=config["v_head_dim"],
        latent_width=config["kv_lora_rank"],
        rope_theta=float(config["rope_theta"]),
        rope_interleave=config.get("rope_interleave", False),
        dense_width=config["intermediate_size"],
        num_experts=of, experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config.get("n_shared_experts") or 0,
        routed_scale=config.get("routed_scaling_factor") or 1.0,
        norm_topk_prob=config["norm_topk_prob"],
        scoring=config["scoring_func"],
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
