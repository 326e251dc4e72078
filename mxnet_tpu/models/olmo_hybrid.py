"""Olmo-Hybrid-7B (``model_type`` olmo_hybrid): a dense decoder-only
hybrid of gated delta-rule linear-attention layers and full-attention
layers, three to one, as an ``mx.sym`` graph that ``Module.fit`` trains
— whole, or as one pipeline stage's layers over a slice of the
vocabulary.

The defaults are ``allenai/Olmo-Hybrid-7B``'s ``config.json`` (hidden
3840; 32 layers whose mixer is an entry of ``layer_types``, ``linear,
linear, linear, full`` eight times over; linear attention of 30 heads
with keys of 96 and values of 192, 4 conv taps, write strengths up to 2
(``linear_allow_neg_eigval``); full attention of 30 heads of 128 without
key/value grouping and without a rotary embedding
(``rope_parameters.rope_theta`` null); SwiGLU of 11008; RMSNorm eps
1e-6; vocabulary 100352, untied head). Every block norms the OUTPUT of
its two sub-layers (``lm_blocks.post_norm_block``: the Olmo 2 / Olmo 3
order; every other LM symbol here norms the input):

    h = h + attn_norm(mixer(h));   h = h + ffn_norm(SwiGLU(h))

    linear:  gdn_o_proj(GatedDeltaNet(gdn_q_proj(h), gdn_k_proj(h),
                 gdn_v_proj(h), gdn_g_proj(h), gdn_a_proj(h),
                 gdn_b_proj(h)))      # ``ops/transformer.gated_delta_net``
    full:    o_proj(Attention(q_norm(q_proj(h)), k_norm(k_proj(h)),
                              v_proj(h)))

then ``final_norm`` and ``lm_head``. No positional signal is added
anywhere: the order of the tokens reaches the model through the linear
layers' convolutions and recurrence and the causal mask.
``GatedDeltaNet`` owns the convolution's taps (``layer<i>_gdn_conv_weight``
[taps, 2 H K + H V], no bias), the decay rates and the step sizes' bias
(``_a_log``, ``_dt_bias``, one a head) and the gated norm's scale
(``_norm_gamma`` [V]); the seven projections are ``FullyConnected``
nodes. ``data`` holds token ids ``[batch, seq_len]`` and
``softmax_label`` the next token at each position.

**A pipeline stage.** ``num_hidden_layers`` and ``layer_types`` are the
layers held here, ``vocab_size`` the rows of the embedding and the head
held (vocabulary parallelism): ids, logits and loss are over the slice.
Nothing stands in for the other stages; ``models/olmo_hybrid_reference.py``
is given the same configuration.

**Initialisation the model states itself** (``sym.Variable(init=)``): a
unit embedding as the other LM symbols; the convolution's taps uniform
in +-1/sqrt(taps); and the published Gated DeltaNet rule for the two
parameters that decide the dynamics, ``a_log = log(U(1, 16))`` and
``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in [0.001, 0.1]
and not under 1e-4. Under a plain ``Normal(0.02)`` every head would
forget at once and nothing would cross a chunk.

Outputs: the loss per sequence behind ``MakeLoss`` and nothing else (no
expert counts). Norm statistics, the convolution's sum, write
strengths, decays, the triangular solve, the carried state, the gate,
softmax and loss arithmetic are float32 whatever ``dtype`` is.
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import head_and_loss, linear, post_norm_block, swiglu

LINEAR, FULL = "linear_attention", "full_attention"


def get_symbol(vocab_size=100352, hidden_size=3840, intermediate_size=11008,
               layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 8, num_heads=30,
               num_kv_heads=30, linear_heads=30, linear_key_dim=96,
               linear_value_dim=192, conv_kernel=4, allow_neg_eigval=True,
               chunk_size=64, seq_len=4096, rms_eps=1e-6, dtype="float32",
               embed_sigma=1.0):
    """One block an entry of ``layer_types``. ``chunk_size`` is the
    program's own (tokens a chunk of the delta rule): it changes no
    mathematics."""
    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    key_width = linear_heads * linear_key_dim
    value_width = linear_heads * linear_value_dim
    head_dim = hidden_size // num_heads

    def linear_attention(x, p):
        p += "gdn_"
        streams = [positions(linear(x, p + name + "_proj", width), width)
                   for name, width in (
                       ("q", key_width), ("k", key_width),
                       ("v", value_width), ("g", value_width),
                       ("a", linear_heads), ("b", linear_heads))]
        y = csym.GatedDeltaNet(
            *streams,
            conv_weight=sym.Variable(p + "conv_weight", init=init.Uniform(
                scale=conv_kernel ** -0.5)),
            a_log=sym.Variable(p + "a_log", init=init.LogOfUniform(
                low=1.0, high=16.0)),
            dt_bias=sym.Variable(p + "dt_bias", init=init.InverseSoftplus(
                low=0.001, high=0.1, floor=1e-4)),
            norm_gamma=sym.Variable(p + "norm_gamma", init=init.One()),
            num_heads=linear_heads, conv_kernel=conv_kernel,
            chunk_size=chunk_size, eps=rms_eps,
            allow_neg_eigval=allow_neg_eigval, name=p[:-1])
        return linear(sym.Reshape(y, shape=(-1, value_width)), p + "o_proj",
                      hidden_size)

    def full_attention(x, p):
        kv_width = num_kv_heads * head_dim
        q = csym.RMSNorm(linear(x, p + "q_proj", hidden_size), eps=rms_eps,
                         name=p + "q_norm")
        k = csym.RMSNorm(linear(x, p + "k_proj", kv_width), eps=rms_eps,
                         name=p + "k_norm")
        v = linear(x, p + "v_proj", kv_width)
        attn = csym.Attention(
            positions(q, hidden_size), positions(k, kv_width),
            positions(v, kv_width), num_heads=num_heads,
            num_kv_heads=num_kv_heads, causal=True, name=p + "attn")
        return linear(sym.Reshape(attn, shape=(-1, hidden_size)),
                      p + "o_proj", hidden_size)

    def mlp(x, p):
        return swiglu(x, p, intermediate_size, hidden_size)

    mixers = {LINEAR: linear_attention, FULL: full_attention}
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
                "olmo_hybrid: layer_types[%d] is %r; only %s and %s are "
                "built" % (i, kind, LINEAR, FULL))
        p = "layer%d_" % i
        h = post_norm_block(h, p, "attn_norm", rms_eps, mixers[kind])
        h = post_norm_block(h, p, "ffn_norm", rms_eps, mlp)
    return head_and_loss(h, label, [], vocab_size, seq_len, rms_eps)


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"attention_bias": False, "tie_word_embeddings": False,
         "hidden_act": "silu"}


def from_config(config, seq_len=None, dtype="float32", chunk_size=64):
    """The symbol of a published ``config.json`` (``model_type``
    olmo_hybrid), given as a dict (``chunk_size`` is no key of it: the
    program's own, ``get_symbol``). A key that would change the
    mathematics and that this builder does not implement (a layer type
    other than the two, a bias on a projection, tied embeddings, a
    rotary embedding, grouped key/value heads in the linear layers,
    another activation) raises.

    A pipeline stage is the same dict with the layers and the vocabulary
    rows held in place of the published ones (``num_hidden_layers``,
    ``layer_types``, ``vocab_size``)."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("olmo_hybrid.from_config: %s=%r is not "
                             "supported (only %r)" % (key, config[key], value))
    theta = (config.get("rope_parameters") or {}).get("rope_theta")
    if theta is not None:
        raise ValueError(
            "olmo_hybrid.from_config: rope_parameters.rope_theta=%r is not "
            "supported (only null: no rotary embedding)" % (theta,))
    heads = config["linear_num_key_heads"]
    if config["linear_num_value_heads"] != heads:
        raise ValueError(
            "olmo_hybrid.from_config: linear_num_value_heads=%r differs "
            "from linear_num_key_heads=%r (grouped linear-attention heads "
            "are not supported)" % (config["linear_num_value_heads"], heads))
    layer_types = tuple(config["layer_types"])
    if len(layer_types) != config["num_hidden_layers"]:
        raise ValueError(
            "olmo_hybrid.from_config: layer_types has %d entries, "
            "num_hidden_layers=%r"
            % (len(layer_types), config["num_hidden_layers"]))
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        layer_types=layer_types, num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], linear_heads=heads,
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        allow_neg_eigval=config["linear_allow_neg_eigval"],
        chunk_size=chunk_size,
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
