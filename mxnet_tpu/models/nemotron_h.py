"""Nemotron-3-Nano-30B-A3B (``model_type`` nemotron_h): a decoder-only
hybrid of Mamba-2 state-space layers, sigmoid-routed un-gated relu²
experts and a few grouped-head attention layers without positions, as
an ``mx.sym`` graph that ``Module.fit`` trains — whole, or as one chip's
share of its layers.

The defaults are ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s
``config.json`` (hidden 2688; 52 blocks whose kind is a character of
``hybrid_override_pattern``: ``M`` Mamba-2, ``E`` experts, ``*``
attention; Mamba-2 of 64 heads of 64, state 128, 8 groups, 4 conv taps,
chunks of 128; attention of 32 query heads on 2 key/value heads of 128;
128 routed experts of 1856, top-6 by sigmoid score plus a selection
bias, weights renormalised and times 2.5, beside one shared expert of
3712; RMSNorm eps 1e-5; vocabulary 131072, untied head). Every block is
one norm and one mixer, ``h = h + mixer(norm(h))``
(``lm_blocks.mixer_block``):

    M:  Mamba2(in_proj(x))  ->  out_proj     # ``ops/transformer.mamba2``
    *:  o_proj(Attention(q_proj(x), k_proj(x), v_proj(x)))
    E:  TopKMoE(x, scoring="sigmoid", activation="relu2", select_bias,
                routed_scale=2.5)
        + shared_down_proj(relu(shared_up_proj(x))^2)

then ``final_norm`` and ``lm_head``. No positional signal is added
anywhere: the published ``nemotron_h`` attention applies no rotary
embedding (``rope_theta`` and ``partial_rotary_factor`` are read by
nothing), the order of the tokens reaches the model through the
state-space layers and the causal mask. ``Mamba2`` owns the convolution
(``layer<i>_ssm_conv_weight`` [taps, channels], ``_conv_bias``), the
step sizes' bias, the decay rates and the skip (``_dt_bias``, ``_a_log``,
``_d``, one a head) and the gated norm's scale (``_norm_gamma``);
``in_proj`` and ``out_proj`` are ``FullyConnected`` nodes. ``data`` holds
token ids ``[batch, seq_len]`` and ``softmax_label`` the next token at
each position.

**One chip's share.** As ``models/kanana2.py``: ``vocab_size`` the rows
held, ``experts_held`` of the router's ``num_experts`` from
``expert_offset`` on, their rows compacted into ``share_rows_bound``.
The Mamba-2 and attention layers, the shared expert and the router stay
whole: every chip of the deployment computes them alike, on its own
sequences. Nothing stands in for the chips that hold the other experts;
``models/nemotron_h_reference.py`` is given the same share.

**Initialisation the model states itself** (``sym.Variable(init=)``):
a unit embedding and zero selection biases as the other LM symbols; the
skip ``d`` ones, the convolution's bias zeros and its taps uniform in
+-1/sqrt(taps) (the published code's ``Conv1d`` default); and the
published Mamba-2 rule for the two parameters that decide the dynamics,
``a_log = log(U(1, 16))`` and ``dt_bias = softplus^-1(dt)`` with ``dt``
log-uniform in [``time_step_min``, ``time_step_max``] and not under
``time_step_floor``. Under a plain ``Normal(0.02)`` every head would
forget in two tokens, nothing would cross a chunk, and the taps would
shrink ``x``, ``B`` and ``C`` until the skip were all of ``y``.

Outputs and what is float32 are ``models/mimo_v2.py``'s: the loss per
sequence behind ``MakeLoss``, then each expert layer's row counts over
all of the router's experts; router, norm statistics, the convolution's
sum, step sizes, decays, the carried state, the gate, softmax and loss
arithmetic in float32 whatever ``dtype`` is.

Departures from the published training job, shared with the reference:
the selection bias is a parameter with no gradient that no rule moves,
no auxiliary loss; the published ``rescale_prenorm_residual`` is a rule
for initial weights and is left to the caller's initializer.
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import (expert_layer, head_and_loss, linear, mixer_block,
                        relu2_mlp)


def get_symbol(vocab_size=131072, hidden_size=2688,
               pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
               mamba_heads=64, mamba_head_dim=64, state_size=128,
               num_groups=8, conv_kernel=4, chunk_size=128,
               time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
               num_heads=32, num_kv_heads=2, head_dim=128, num_experts=128,
               experts_held=0, expert_offset=0, share_rows_bound=0,
               experts_per_token=6, expert_width=1856, shared_width=3712,
               routed_scale=2.5, norm_topk_prob=True, seq_len=8192,
               rms_eps=1e-5, dtype="float32", embed_sigma=1.0):
    """One block a character of ``pattern``: ``M``, ``E`` or ``*``."""
    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    d_in = mamba_heads * mamba_head_dim
    proj_width = 2 * d_in + 2 * num_groups * state_size + mamba_heads

    def mamba(x, p):
        def var(name, rule):
            return sym.Variable(p + "ssm_" + name, init=rule)

        y = csym.Mamba2(
            positions(linear(x, p + "in_proj", proj_width), proj_width),
            conv_weight=var("conv_weight", init.Uniform(
                scale=conv_kernel ** -0.5)),
            conv_bias=var("conv_bias", init.Zero()),
            dt_bias=var("dt_bias", init.InverseSoftplus(
                low=time_step_min, high=time_step_max,
                floor=time_step_floor)),
            a_log=var("a_log", init.LogOfUniform(low=1.0, high=16.0)),
            d=var("d", init.One()),
            num_heads=mamba_heads, head_dim=mamba_head_dim,
            state_size=state_size, num_groups=num_groups,
            conv_kernel=conv_kernel, chunk_size=chunk_size, eps=rms_eps,
            name=p + "ssm")
        return linear(sym.Reshape(y, shape=(-1, d_in)), p + "out_proj",
                      hidden_size)

    def attention(x, p):
        q, k, v = (positions(linear(x, p + name, heads * head_dim),
                             heads * head_dim)
                   for name, heads in (("q_proj", num_heads),
                                       ("k_proj", num_kv_heads),
                                       ("v_proj", num_kv_heads)))
        attn = csym.Attention(q, k, v, num_heads=num_heads,
                              num_kv_heads=num_kv_heads, causal=True,
                              name=p + "attn")
        return linear(sym.Reshape(attn, shape=(-1, num_heads * head_dim)),
                      p + "o_proj", hidden_size)

    counts = []

    def experts(x, p):
        moe, count = expert_layer(
            x, p, num_experts=num_experts, num_hidden=expert_width,
            top_k=experts_per_token, norm_topk_prob=norm_topk_prob,
            scoring="sigmoid", routed_scale=routed_scale,
            activation="relu2", experts_held=experts_held,
            expert_offset=expert_offset, share_rows_bound=share_rows_bound)
        counts.append(count)
        if shared_width:
            moe = moe + relu2_mlp(x, p + "shared_", shared_width,
                                  hidden_size)
        return moe

    kinds = {"M": mamba, "*": attention, "E": experts}
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    # the residual stream is [tokens, hidden] throughout
    h = sym.Embedding(
        sym.Reshape(data, shape=(-1,)),
        weight=sym.Variable("embed_weight",
                            init=init.Normal(sigma=embed_sigma)),
        input_dim=vocab_size, output_dim=hidden_size, dtype=dtype,
        name="embed")
    for i, kind in enumerate(pattern):
        if kind not in kinds:
            raise ValueError(
                "nemotron_h: block %d of the pattern %r is %r; only M "
                "(Mamba-2), E (experts) and * (attention) are built"
                % (i, pattern, kind))
        h = mixer_block(h, "layer%d_" % i, rms_eps, kinds[kind])
    return head_and_loss(h, label, counts, vocab_size, seq_len, rms_eps)


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"attention_bias": False, "mlp_bias": False, "use_bias": False,
         "mamba_proj_bias": False, "use_conv_bias": True,
         "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
         "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
         "sliding_window": None}


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type``
    nemotron_h), given as a dict. A key that would change the
    mathematics and that this builder does not implement (a dense MLP
    block ``-`` in the pattern, grouped routing, a bias on a projection,
    tied embeddings, another activation) raises.

    A share of the model is the same dict with the counts held in place
    of the published ones (``vocab_size``, ``n_routed_experts``) and a
    group ``share`` beside them, as ``kanana2.from_config`` reads it."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("nemotron_h.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"]:
        raise ValueError(
            "nemotron_h.from_config: hybrid_override_pattern %r has %d "
            "blocks, num_hidden_layers=%r"
            % (pattern, len(pattern), config["num_hidden_layers"]))
    eps = config["layer_norm_epsilon"]
    if config.get("norm_eps", eps) != eps:
        raise ValueError(
            "nemotron_h.from_config: norm_eps=%r differs from "
            "layer_norm_epsilon=%r (one eps for every norm)"
            % (config["norm_eps"], eps))
    share = config.get("share", {})
    held = config["n_routed_experts"]
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        pattern=pattern, mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        state_size=config["ssm_state_size"], num_groups=config["n_groups"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], num_experts=of,
        experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=(config["moe_shared_expert_intermediate_size"]
                      if config.get("n_shared_experts") else 0),
        routed_scale=config.get("routed_scaling_factor") or 1.0,
        norm_topk_prob=config["norm_topk_prob"],
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=eps, dtype=dtype)
