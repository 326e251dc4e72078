"""Trinity-Mini (``model_type`` afmoe; Arcee AI, 26B total, 3B active): a
decoder-only LM of sliding-window and full-attention layers, three to
one, whose attention output passes a sigmoid gate, with a norm on BOTH
sides of every sub-layer, over a shared and sigmoid-routed sparse
experts, as an ``mx.sym`` graph that ``Module.fit`` trains — whole, or
as one chip's share of its layers.

The defaults are ``arcee-ai/Trinity-Mini``'s ``config.json`` (hidden
2048; 32 layers whose attention is an entry of ``layer_types``:
``sliding_attention`` with a causal window of 2048 keys for three,
``full_attention`` for every fourth; 32 query heads on 4 key/value heads
of 128; RoPE theta 1e4 in the sliding layers, NOTHING rotated in the full
ones; the first 2 layers a dense SwiGLU of 6144, the other 30 one shared
expert of 1024 beside 128 routed experts of 1024, top-8 by sigmoid score
plus a selection bias, weights renormalised and times 2.826; RMSNorm eps
1e-5; ``mup_enabled``: the embedding times sqrt(hidden); vocabulary
200192, untied head). What no key states is the published modelling code
for ``model_type`` afmoe (transformers' ``modeling_afmoe.py``). Per
layer:

    x = attn_norm(h)
    q, k = q_norm(q_proj(x)), k_norm(k_proj(x))      # a head's own 128
    q, k = RoPE(q), RoPE(k)                          # sliding layers ONLY
    a = Attention(q, k, v_proj(x), gate=attn_gate_proj(x)[, window])
    h = h + attn_post_norm(o_proj(a))
    x = ffn_norm(h)
    m = down_proj(silu(gate_proj(x)) * up_proj(x))   # dense, or
    m = shared SwiGLU(x) + TopKMoE(x, scoring="sigmoid", select_bias,
                                   routed_scale=2.826, renorm_eps=1e-20)
    h = h + ffn_post_norm(m)

then ``final_norm`` and ``lm_head``. ``Attention(with_gate=True)``
multiplies the kernel's output by ``sigmoid(gate)``, a gate per head and
channel, before ``o_proj``. ``q_norm`` and ``k_norm`` are an RMSNorm over
each head's own ``head_dim`` columns, one gamma of ``head_dim`` shared by
the heads, BEFORE the rotation (half-rotation pairs over the whole head).
The stream starts as ``embed_scale`` = sqrt(hidden) times the embedding.
``data`` holds token ids ``[batch, seq_len]`` and ``softmax_label`` the
next token at each position.

**One chip's share.** As ``models/kanana2.py``: ``vocab_size`` the rows
held, ``experts_held`` of the router's ``num_experts`` from
``expert_offset`` on, their rows compacted into ``share_rows_bound``.
Attention (all heads), the shared expert, the router and the dense layer
stay whole: every chip of the deployment computes them alike, on its own
sequences. Nothing stands in for the chips that hold the other experts or
for the exchange with them; ``models/afmoe_reference.py`` is given the
same share.

**Initialisation the model states itself** (``sym.Variable(init=)``):
zero selection biases, and the embedding Normal(``embed_sigma``), by
default ``STREAM_RMS`` / sqrt(hidden) under ``mup`` (``STREAM_RMS``
without it), so that the scaled stream starts at an rms of 8. The other
LM symbols' unit embedding keeps a token's own vector the largest part of
what the routers read, so that seeded weights route near-uniformly, as a
trained model's balanced routers do; there a sub-layer's output is small
beside it. Here every sub-layer's output passes a norm whose gamma starts
at 1 and joins the stream at unit rms, attention's a running mean that
neighbouring tokens share: by the fourth expert layer a unit embedding is
a tenth of what the router reads, and the chip read a layer's held rows
at 0.64-1.21 of uniform at the first step and one expert at up to 5.9
times its share (at a stream of 8: 0.89-1.11 and 2.1; ``PERF.md``
section 6, PR 55). The first loss does not depend on it: the head reads
the final norm's output.

Outputs: the loss per sequence behind ``MakeLoss``, then each expert
layer's row counts over all of the router's experts. Router, norm
statistics (the heads' too), RoPE, softmax, the gate and the loss are
float32 whatever ``dtype`` is.

Departures from the published training job, shared with the reference:
the selection bias is a parameter with no gradient that no rule moves,
no auxiliary loss (``load_balance_coeff`` is the published job's), no
multi-token prediction (the model has none).
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import (expert_layer, head_and_loss, linear, post_norm_block,
                        scaled, swiglu)

SLIDING, FULL = "sliding_attention", "full_attention"
_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)
# rms the scaled embedding starts the stream at (the docstring says why)
STREAM_RMS = 8.0


def get_symbol(vocab_size=200192, hidden_size=2048,
               layer_types=_PERIOD * 8, dense_layers=2, num_heads=32,
               num_kv_heads=4, head_dim=128, rope_theta=1e4, window=2048,
               dense_width=6144, num_experts=128, experts_held=0,
               expert_offset=0, share_rows_bound=0, experts_per_token=8,
               expert_width=1024, shared_experts=1, routed_scale=2.826,
               norm_topk_prob=True, scoring="sigmoid", renorm_eps=1e-20,
               mup=True, seq_len=8192, rms_eps=1e-5, dtype="float32",
               embed_sigma=None):
    """One layer an entry of ``layer_types`` (``sliding_attention`` or
    ``full_attention``); the first ``dense_layers`` have the dense
    feed-forward, the rest shared and routed experts."""
    q_width, kv_width = num_heads * head_dim, num_kv_heads * head_dim

    def norm(x, name):
        return csym.RMSNorm(x, eps=rms_eps, name=name)

    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    def head_norm(x, name):  # over each head's own columns, one gamma
        return norm(sym.Reshape(x, shape=(-1, head_dim)), name)

    def attention(x, p, kind):
        def normed(name, heads):
            y = positions(head_norm(
                linear(x, p + name + "_proj", heads * head_dim),
                p + name + "_norm"), heads * head_dim)
            if kind == FULL:  # no positional signal at all
                return y
            return csym.RoPE(y, num_heads=heads, theta=rope_theta,
                             name=p + name + "_rope")

        attn = csym.Attention(
            normed("q", num_heads), normed("k", num_kv_heads),
            positions(linear(x, p + "v_proj", kv_width), kv_width),
            with_gate=True,
            gate=positions(linear(x, p + "attn_gate_proj", q_width),
                           q_width),
            num_heads=num_heads, num_kv_heads=num_kv_heads, causal=True,
            window=window if kind == SLIDING else 0, name=p + "attn")
        return linear(sym.Reshape(attn, shape=(-1, q_width)), p + "o_proj",
                      hidden_size)

    counts = []

    def experts(x, p):
        moe, count = expert_layer(
            x, p, num_experts=num_experts, num_hidden=expert_width,
            top_k=experts_per_token, norm_topk_prob=norm_topk_prob,
            scoring=scoring, routed_scale=routed_scale,
            renorm_eps=renorm_eps, experts_held=experts_held,
            expert_offset=expert_offset, share_rows_bound=share_rows_bound)
        counts.append(count)
        if shared_experts:
            moe = moe + swiglu(x, p + "shared_",
                               shared_experts * expert_width, hidden_size)
        return moe

    def dense(x, p):
        return swiglu(x, p, dense_width, hidden_size)

    multiplier = hidden_size ** 0.5 if mup else 1.0
    if embed_sigma is None:
        embed_sigma = STREAM_RMS / multiplier
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    # the residual stream is [tokens, hidden] throughout
    h = scaled(sym.Embedding(
        sym.Reshape(data, shape=(-1,)),
        weight=sym.Variable("embed_weight",
                            init=init.Normal(sigma=embed_sigma)),
        input_dim=vocab_size, output_dim=hidden_size, dtype=dtype,
        name="embed"), "embed_scale", multiplier)
    for i, kind in enumerate(layer_types):
        if kind not in (SLIDING, FULL):
            raise ValueError(
                "afmoe: layer_types[%d] is %r; only %s and %s are built"
                % (i, kind, SLIDING, FULL))
        p = "layer%d_" % i
        ffn = dense if i < dense_layers else experts
        # four norms a block: a sub-layer reads a normed stream and its
        # output is normed again before the residual add
        h = post_norm_block(
            h, p, "attn_post_norm", rms_eps,
            lambda h, p, kind=kind: attention(
                norm(h, p + "attn_norm"), p, kind))
        h = post_norm_block(
            h, p, "ffn_post_norm", rms_eps,
            lambda h, p, ffn=ffn: ffn(norm(h, p + "ffn_norm"), p))
    return head_and_loss(h, label, counts, vocab_size, seq_len, rms_eps)


# keys whose value changes the mathematics and that this builder takes in
# one form only; ``ASSUMED_UNREAD`` are the keys nothing here reads (the
# configuration file lists them under ``assumed``)
_ONLY = {"hidden_act": "silu", "tie_word_embeddings": False,
         "rope_scaling": None, "n_group": 1, "topk_group": 1,
         "num_expert_groups": 1, "num_limited_groups": 1,
         "attention_bias": False}
ASSUMED_UNREAD = ("use_grouped_mm", "load_balance_coeff",
                  "max_position_embeddings")


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type`` afmoe),
    given as a dict. A key that would change the mathematics and that
    this builder does not implement raises: a scaled RoPE, grouped or
    group-limited routing (``n_group``, ``topk_group``,
    ``num_expert_groups``, ``num_limited_groups`` other than 1), an
    activation other than silu, tied embeddings, a bias on the attention
    projections, a router activation other than sigmoid or softmax, a
    ``layer_types`` entry that is neither ``sliding_attention`` nor
    ``full_attention``, or one that ``global_attn_every_n_layers``
    contradicts (every n-th layer full, the rest sliding: the key says
    nothing ``layer_types`` does not, and is held to it).
    ``max_position_embeddings`` is the sequence length only where the
    caller gives none (``ASSUMED_UNREAD``).

    A share of the model is the same dict with the counts held in place
    of the published ones (``vocab_size``, ``num_experts``) and a group
    ``share`` beside them, as ``kanana2.from_config`` reads it."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("afmoe.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    if config.get("score_func", "sigmoid") not in ("sigmoid", "softmax"):
        raise ValueError("afmoe.from_config: score_func=%r is not "
                         "supported" % (config["score_func"],))
    layer_types = tuple(config["layer_types"])
    if len(layer_types) != config["num_hidden_layers"]:
        raise ValueError(
            "afmoe.from_config: layer_types has %d entries, "
            "num_hidden_layers=%r" % (len(layer_types),
                                      config["num_hidden_layers"]))
    every = config.get("global_attn_every_n_layers")
    if every and layer_types != tuple(
            FULL if (i + 1) % every == 0 else SLIDING
            for i in range(len(layer_types))):
        raise ValueError(
            "afmoe.from_config: layer_types %s is not every %d-th layer "
            "full_attention (global_attn_every_n_layers)"
            % (list(layer_types), every))
    heads = config["num_attention_heads"]
    share = config.get("share", {})
    held = config["num_experts"]
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=layer_types, dense_layers=config["num_dense_layers"],
        num_heads=heads, num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or config["hidden_size"] // heads,
        rope_theta=float(config["rope_theta"]),
        window=config["sliding_window"],
        dense_width=config["intermediate_size"], num_experts=of,
        experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config.get("num_shared_experts") or 0,
        routed_scale=float(config.get("route_scale") or 1.0),
        norm_topk_prob=bool(config.get("route_norm", True)),
        scoring=config.get("score_func", "sigmoid"),
        mup=bool(config.get("mup_enabled", False)),
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
