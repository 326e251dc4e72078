"""dots3-note-prev (``model_type`` dots3_note; the language model of a
288B-A17B release): a decoder-only LM whose attention is latent attention
(MLA) in two geometries a ``layer_types`` entry picks, over shared and
sigmoid-routed sparse experts, as an ``mx.sym`` graph that ``Module.fit``
trains — whole, or as one chip's share of its layers.

The defaults are ``dots-studio/dots3-note-prev``'s ``config.json``
(hidden 5120; 46 layers, 13 ``full_attention`` and 33
``sliding_attention``; RMSNorm eps 1e-5; layer 0 a dense SwiGLU of 13824,
every other layer one shared SwiGLU of 1536 beside 256 routed experts of
1536, top-8 by sigmoid score plus a selection bias, weights renormalised;
vocabulary 152064, untied head). Per layer, ``x = attn_norm(h)``:

    c_q = r_q * q_a_norm(q_a_proj(x))           # the query latent
    q   = q_b_proj(c_q)                         # H heads of N + R
    a   = LatentAttention(q, kv_a_proj(x), latent_scale=r_kv,
                          gate=attn_gate_proj(x)[, keep | window])
    h   = h + o_proj(a)
    h   = h + ffn(ffn_norm(h))

``full_attention``: 128 heads of 128 un-rotated + 64 rotary dimensions
(values 128) from a query latent of 1024 and a key/value latent of 512,
``rope_theta`` 8e7, and **the keys are chosen**: ``KeyIndexer``
(``layer<i>_index``; 64 heads of 128 over the query latent against ONE
LayerNormed 128-wide key a token, RoPE on the first 64 dimensions of
both, a weight a head and token; DeepSeek-V3.2-Exp's indexer, whose keys
``index_n_heads`` / ``index_head_dim`` / ``index_topk`` are) keeps the
``min(t + 1, 2048)`` best keys of query t, and every head's softmax runs
over those alone. The indexer has no gradient and its weights get none.
``sliding_attention``: the same latent attention at sizes of its own
(``swa_*``: 64 heads of 192 + 64, values 128, both latents 1024, theta
5e4) under a causal window of 513 keys (query t sees t - 512 .. t), no
indexer. Both gate a head's output by ``sigmoid(attn_gate_proj(x))``, one
scalar a head (``attention_gate_type`` headwise), before ``o_proj``.
``apply_mla_qkv_lora_rescale``: ``r_q = sqrt(hidden / q_lora_rank)`` and
``r_kv = sqrt(hidden / kv_lora_rank)``, constants on the normed latents
(the rotary key unscaled); a positive constant on ``c_q`` scales a whole
row of the index scores and cannot change a selection, so the indexer
reads ``c_q`` behind it.

**One chip's share.** As ``models/mimo_v2.py``: ``vocab_size`` the rows
held, the head counts the heads held (a head's columns of ``q_b_proj``,
``attn_up_weight``, ``attn_gate_proj`` and ``o_proj``), ``experts_held``
of the router's ``num_experts`` from ``expert_offset`` on in a buffer of
``share_rows_bound`` rows, ``dense_width`` the dense layer's columns
held. Whole on every chip: both down-projections and their norms, the
indexer (every member must choose the same keys), the router, the shared
expert. Nothing stands in for the other chips;
``models/dots3_reference.py`` is given the same share.

Outputs: the loss per sequence behind ``MakeLoss``, each expert layer's
row counts over all of the router's experts, then each full layer's
selection count a sequence (``layer<i>_keys_selected``). Initialisation
and what is float32 are ``models/mimo_v2.py``'s (a unit embedding, zero
selection biases; router, norm statistics, RoPE, softmaxes, gates and
loss in float32), the indexer's ReLU, weights, sum over heads and compare
too; its two products take operands of ``dtype`` and accumulate in
float32.

Departures from the published training job, shared with the reference:
the selection bias is a parameter with no gradient that no rule moves, no
auxiliary loss, no loss of the indexer's own (its weights stand still),
no vision or audio tower and no multi-token-prediction layer (their
settings are not in ``config.json``); the indexer's Hadamard rotation
(applied to both sides: every product as it was) and its FP8 cast (a
deployment's precision) are left out.
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import expert_layer, head_and_loss, linear, scaled, swiglu

SLIDING, FULL = "sliding_attention", "full_attention"
_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)


def geometry(num_heads, nope_head_dim, rope_head_dim, v_head_dim,
             q_latent_width, kv_latent_width, rope_theta):
    """One attention geometry: head count and widths, the two latents'
    widths, the rotary base."""
    return dict(num_heads=num_heads, nope=nope_head_dim, rope=rope_head_dim,
                dv=v_head_dim, q_latent=q_latent_width,
                kv_latent=kv_latent_width, theta=float(rope_theta))


FULL_GEOMETRY = geometry(128, 128, 64, 128, 1024, 512, 8e7)
SLIDING_GEOMETRY = geometry(64, 192, 64, 128, 1024, 1024, 5e4)


def get_symbol(vocab_size=152064, hidden_size=5120,
               layer_types=(FULL, FULL) + _PERIOD * 11, dense_layers=1,
               full=FULL_GEOMETRY, sliding=SLIDING_GEOMETRY, window=513,
               index_heads=64, index_head_dim=128, index_topk=2048,
               index_rope_dim=64, latent_rescale=True, headwise_gate=True,
               rope_interleave=True, dense_width=13824, num_experts=256,
               experts_held=0, expert_offset=0, share_rows_bound=0,
               experts_per_token=8, expert_width=1536, shared_experts=1,
               routed_scale=1.0, norm_topk_prob=True, scoring="sigmoid",
               seq_len=4096, rms_eps=1e-5, dtype="float32",
               embed_sigma=1.0):
    """One layer an entry of ``layer_types``; the first ``dense_layers``
    have the dense feed-forward, the rest shared and routed experts.
    ``full`` / ``sliding``: the two ``geometry`` dicts."""
    def norm(x, name):
        return csym.RMSNorm(x, eps=rms_eps, name=name)

    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    def rescale(rank):  # the fixed scalar on a normed latent of ``rank``
        return (hidden_size / rank) ** 0.5 if latent_rescale else 1.0

    selected = []

    def attention(x, p, kind):
        g = full if kind == FULL else sliding
        heads, q_width = g["num_heads"], g["num_heads"] * (g["nope"]
                                                           + g["rope"])
        c_q = scaled(norm(linear(x, p + "q_a_proj", g["q_latent"]),
                          p + "q_a_norm"), p + "q_a_scale",
                     rescale(g["q_latent"]))
        extra = {}
        if headwise_gate:
            extra.update(with_gate=True, gate=positions(
                linear(x, p + "attn_gate_proj", heads), heads))
        if kind == FULL:
            index = csym.KeyIndexer(
                positions(c_q, g["q_latent"]), positions(x, hidden_size),
                num_heads=index_heads, head_dim=index_head_dim,
                rope_dim=index_rope_dim, topk=index_topk, theta=g["theta"],
                name=p + "index")
            extra.update(with_keep=True, keep=index[0])
            selected.append(sym.BlockGrad(index[1],
                                          name=p + "keys_selected"))
        else:
            extra.update(window=window)
        attn = csym.LatentAttention(
            positions(linear(c_q, p + "q_b_proj", q_width), q_width),
            positions(linear(x, p + "kv_a_proj", g["kv_latent"] + g["rope"]),
                      g["kv_latent"] + g["rope"]),
            num_heads=heads, rope_dim=g["rope"], v_head_dim=g["dv"],
            theta=g["theta"], eps=rms_eps, interleave=rope_interleave,
            latent_scale=rescale(g["kv_latent"]),
            query_latent=g["q_latent"],
            name=p + "attn", **extra)
        return linear(sym.Reshape(attn, shape=(-1, heads * g["dv"])),
                      p + "o_proj", hidden_size)

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
    for i, kind in enumerate(layer_types):
        if kind not in (SLIDING, FULL):
            raise ValueError(
                "dots3: layer_types[%d] is %r; only %s and %s are built"
                % (i, kind, SLIDING, FULL))
        p = "layer%d_" % i
        h = h + attention(norm(h, p + "attn_norm"), p, kind)
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
    return head_and_loss(h, label, counts + selected, vocab_size, seq_len,
                         rms_eps)


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"attention_bias": False, "hidden_act": "silu",
         "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
         "topk_method": "noaux_tc", "rope_scaling": None,
         "moe_layer_freq": 1, "model_type": "dots3_note"}
_GATES = (None, "headwise")


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type``
    dots3_note), given as a dict. A key that would change the mathematics
    and that this builder does not implement raises: a projection bias,
    an activation other than silu, tied embeddings, grouped routing, a
    scaled RoPE, a layer frequency other than every layer after the dense
    ones, a gate other than headwise (or none) or one that differs
    between the two geometries, a ``layer_types`` entry that is neither
    ``full_attention`` nor ``sliding_attention``, key/value head counts
    that differ from the query heads', a missing query latent
    (``q_lora_rank`` null).

    A share of the model is the same dict with the counts held in place
    of the published ones (``vocab_size``, ``n_routed_experts``, the four
    head counts) and a group ``share`` beside them: ``experts_of`` (the
    router's width where ``n_routed_experts`` counts the experts held),
    ``expert_offset``, ``share_rows_bound`` and ``dense_columns_held``
    (of ``intermediate_size``)."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("dots3.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    if config.get("scoring_func", "sigmoid") not in ("sigmoid", "softmax"):
        raise ValueError("dots3.from_config: scoring_func=%r is not "
                         "supported" % (config["scoring_func"],))
    gate = config.get("attention_gate_type")
    if gate not in _GATES or config.get("swa_attention_gate_type",
                                        gate) != gate:
        raise ValueError(
            "dots3.from_config: attention_gate_type=%r / "
            "swa_attention_gate_type=%r: only one of %r on both is built"
            % (gate, config.get("swa_attention_gate_type"), _GATES))
    layer_types = tuple(config["layer_types"])
    if len(layer_types) != config["num_hidden_layers"]:
        raise ValueError(
            "dots3.from_config: layer_types has %d entries, "
            "num_hidden_layers=%r" % (len(layer_types),
                                      config["num_hidden_layers"]))

    def one(prefix):
        heads = config[prefix + "num_attention_heads"]
        if config.get(prefix + "num_key_value_heads", heads) != heads:
            raise ValueError(
                "dots3.from_config: %snum_key_value_heads=%r differs from "
                "%snum_attention_heads=%r (the up-projection gives every "
                "head its own key and value)" % (
                    prefix, config[prefix + "num_key_value_heads"], prefix,
                    heads))
        if not config.get(prefix + "q_lora_rank"):
            raise ValueError(
                "dots3.from_config: %sq_lora_rank=%r: a query without a "
                "latent is not built" % (
                    prefix, config.get(prefix + "q_lora_rank")))
        return geometry(
            heads, config[prefix + "qk_nope_head_dim"],
            config[prefix + "qk_rope_head_dim"],
            config[prefix + "v_head_dim"], config[prefix + "q_lora_rank"],
            config[prefix + "kv_lora_rank"], config[prefix + "rope_theta"])

    share = config.get("share", {})
    held = config["n_routed_experts"]
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=layer_types,
        dense_layers=config["first_k_dense_replace"],
        full=one(""), sliding=one("swa_"),
        window=config["sliding_window_size"],
        index_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        index_rope_dim=config["qk_rope_head_dim"],
        latent_rescale=bool(config.get("apply_mla_qkv_lora_rescale", False)),
        headwise_gate=gate == "headwise",
        rope_interleave=config.get("rope_interleave", True),
        dense_width=share.get("dense_columns_held",
                              config["intermediate_size"]),
        num_experts=of, experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config.get("n_shared_experts") or 0,
        routed_scale=float(config.get("routed_scaling_factor") or 1.0),
        norm_topk_prob=config["norm_topk_prob"],
        scoring=config.get("scoring_func", "sigmoid"),
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
