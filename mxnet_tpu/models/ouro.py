"""Ouro-2.6B (``model_type`` ouro; ByteDance, a looped language model:
Zhu et al., "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): a stack of Llama-shaped layers run ``total_ut_steps``
times over ONE set of weights, the final norm, an exit gate and the head
after every pass, trained on the exit-weighted loss, as an ``mx.sym``
graph that ``Module.fit`` trains — whole, or as one pipeline stage's
layers over a slice of the vocabulary.

The defaults are ``ByteDance/Ouro-2.6B``'s ``config.json`` (hidden 2048;
48 layers, all ``full_attention``; 16 heads of 128 on 16 key/value heads;
rotate-half RoPE over the whole head, theta 1e6; SwiGLU of 5632; no bias;
RMSNorm eps 1e-6; vocabulary 49152, untied head; ``total_ut_steps`` 4).
A layer has four norms, each with a gamma of its own:

    a = input_layernorm(h)
    q, k, v = RoPE(q_proj(a)), RoPE(k_proj(a)), v_proj(a)
    h = h + input_layernorm_2(o_proj(Attention(q, k, v)))
    m = post_attention_layernorm(h)
    h = h + post_attention_layernorm_2(down_proj(silu(gate_proj(m))
                                                 * up_proj(m)))

and the model, for ``t = 1..T`` with the SAME weights every pass:

    h   = layer_L(... layer_1(h))
    n_t = final_norm(h);  h = n_t        # the NORMED state is carried on
    z_t = lm_head(n_t)                   # float32 logits
    g_t = exit_gate(n_t)                 # Linear(hidden, 1) with a bias

``lambda_t = sigmoid(g_t)``; a token leaves after pass t with ``p_t =
lambda_t prod_{j<t}(1 - lambda_j)`` and after the last with what is
left, ``p_T = prod_{j<T}(1 - lambda_j)``. The loss is the paper's stage
I, the entropy-regularised expected loss: a token's ``sum_t p_t l_t -
beta H(p)`` with ``l_t`` the next-token cross-entropy of ``z_t`` (one
``pick_log_softmax`` node a pass, ``loop<t>_lm_head_pick``: its backward
rule keeps ``z_t`` as the head rounded it and one number a token, no
[tokens, vocab] table) and ``H(p) = -sum_t p_t log p_t``; gradients flow
through ``p_t`` into the gate and the stream (nothing is detached).
``beta`` is no key of ``config.json`` (``exit_beta``, 0.05 by default).

**One set of weights.** A layer's seven matrices and four gammas, the
final norm's gamma, the head and the gate are ``sym.Variable``s made
ONCE (``layer<i>_{q,k,v,o}_proj_weight``, ``layer<i>_{gate,up,down}_
proj_weight``, ``layer<i>_{input_layernorm, input_layernorm_2,
post_attention_layernorm, post_attention_layernorm_2}_gamma``,
``final_norm_gamma``, ``lm_head_weight``, ``exit_gate_weight``,
``exit_gate_bias``) and handed to one node a pass
(``loop<t>_layer<i>_q_proj`` ..., ``loop<t>_final_norm``,
``loop<t>_lm_head``, ``loop<t>_lm_head_f32``, ``loop<t>_exit_gate``;
passes count from 1): ``list_arguments`` holds each once, its gradient
is the sum over its T uses, the optimizer has one state and makes one
update for it, and a checkpoint holds it once. A scope in a trace says
which pass (``fc/loop3_layer0_q_proj``). The symbol is unrolled, as
``mx.rnn`` unrolls a cell.

``lambda_T`` is built (``loop<T>_exit_gate``, as the published code
computes it) and read by nothing: its column enters ``exit_mix``
(``ops/transformer.py::exit_mix``), which leaves it out, so the compiler
removes it and ``exit_gate_weight``'s gradient is the sum over the T - 1
gates that are read.

**A pipeline stage.** ``num_hidden_layers`` and ``layer_types`` are the
layers held here, ``vocab_size`` the rows of the embedding and the head
held (vocabulary parallelism): ids, logits and loss are over the slice,
and the held layers run all T passes. Nothing stands in for the other
stages; ``models/ouro_reference.py`` is given the same configuration.

**Initialisation the model states itself** (``sym.Variable(init=)``): a
unit embedding as the other LM symbols, every gamma 1, the gate's bias
0. The matrices, the gate's among them, are the caller's initializer's.

Outputs: the loss per sequence behind ``MakeLoss`` (``loss``), then
``exit_mass`` [T] behind ``BlockGrad``: the mean of ``p_t`` over the
batch's tokens, where training has put the exits. ``data`` holds token
ids ``[batch, seq_len]`` and ``softmax_label`` the next token at each
position. Norm statistics, RoPE, the softmaxes, the gate's sigmoid,
``p``, ``log p``, the entropy and the loss are float32 whatever
``dtype`` is; every product takes ``dtype`` operands and accumulates in
float32 (the gate's pre-activation, like the logits, is rounded once to
``dtype`` by ``FullyConnected`` and cast up).

Left out of the step, of program and reference alike: the paper's stage
II (the gate trained against detached per-exit losses) and the early
exit at inference (``early_exit_threshold``: a pass count chosen at run
time by the gate).
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import linear, post_norm_block, swiglu

FULL = "full_attention"
PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
               "up_proj", "down_proj")
NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")


def get_symbol(vocab_size=49152, hidden_size=2048, intermediate_size=5632,
               num_layers=48, num_heads=16, num_kv_heads=16, head_dim=128,
               rope_theta=1e6, total_ut_steps=4, exit_beta=0.05,
               seq_len=4096, rms_eps=1e-6, dtype="float32", embed_sigma=1.0):
    """``num_layers`` layers run ``total_ut_steps`` times over one set
    of weights; the normed state ``n_t`` is what the next pass reads
    (``tests/test_ouro.py`` pins that against the symbol rewired to
    carry the un-normed stream)."""
    q_width, kv_width = num_heads * head_dim, num_kv_heads * head_dim

    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    def gamma(name):
        return sym.Variable(name + "_gamma", init=init.One())

    # every weight ONCE: the T passes' nodes read these objects (two
    # ``Variable`` calls under one name would be two arguments)
    layers = [dict(
        [(n, sym.Variable("layer%d_%s_weight" % (i, n))) for n in PROJECTIONS]
        + [(n, gamma("layer%d_%s" % (i, n))) for n in NORMS])
        for i in range(num_layers)]
    final_gamma = gamma("final_norm")
    head = sym.Variable("lm_head_weight")
    gate_weight = sym.Variable("exit_gate_weight")
    gate_bias = sym.Variable("exit_gate_bias", init=init.Zero())

    def attention(h, p, w):
        x = csym.RMSNorm(h, gamma=w["input_layernorm"], eps=rms_eps,
                         name=p + "input_layernorm")
        q = csym.RoPE(
            positions(linear(x, p + "q_proj", q_width, weight=w["q_proj"]),
                      q_width),
            num_heads=num_heads, theta=rope_theta, name=p + "q_rope")
        k = csym.RoPE(
            positions(linear(x, p + "k_proj", kv_width, weight=w["k_proj"]),
                      kv_width),
            num_heads=num_kv_heads, theta=rope_theta, name=p + "k_rope")
        v = positions(linear(x, p + "v_proj", kv_width, weight=w["v_proj"]),
                      kv_width)
        attn = csym.Attention(q, k, v, num_heads=num_heads,
                              num_kv_heads=num_kv_heads, causal=True,
                              name=p + "attn")
        return linear(sym.Reshape(attn, shape=(-1, q_width)), p + "o_proj",
                      hidden_size, weight=w["o_proj"])

    def mlp(h, p, w):
        x = csym.RMSNorm(h, gamma=w["post_attention_layernorm"],
                         eps=rms_eps, name=p + "post_attention_layernorm")
        return swiglu(x, p, intermediate_size, hidden_size, weights=(
            w["gate_proj"], w["up_proj"], w["down_proj"]))

    data = sym.Variable("data")
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    # the residual stream is [tokens, hidden] throughout
    h = sym.Embedding(
        sym.Reshape(data, shape=(-1,)),
        weight=sym.Variable("embed_weight",
                            init=init.Normal(sigma=embed_sigma)),
        input_dim=vocab_size, output_dim=hidden_size, dtype=dtype,
        name="embed")
    gates, nll = [], []
    for t in range(1, total_ut_steps + 1):
        for i, w in enumerate(layers):
            p = "loop%d_layer%d_" % (t, i)
            h = post_norm_block(
                h, p, "input_layernorm_2", rms_eps,
                lambda h, p, w=w: attention(h, p, w),
                gamma=w["input_layernorm_2"])
            h = post_norm_block(
                h, p, "post_attention_layernorm_2", rms_eps,
                lambda h, p, w=w: mlp(h, p, w),
                gamma=w["post_attention_layernorm_2"])
        p = "loop%d_" % t
        # the NORMED state is carried on, as the published code reassigns it
        h = csym.RMSNorm(h, gamma=final_gamma, eps=rms_eps,
                         name=p + "final_norm")
        logits = sym.Cast(
            linear(h, p + "lm_head", vocab_size, weight=head),
            dtype="float32", name=p + "lm_head_f32")
        picked = sym.pick_log_softmax(logits, label,
                                      name=p + "lm_head_pick")
        nll.append(sym.Reshape(0 - picked, shape=(-1, 1)))
        gates.append(sym.FullyConnected(
            h, weight=gate_weight, bias=gate_bias, num_hidden=1,
            name=p + "exit_gate"))
    # T columns of [tokens, 1] -> [tokens, T]
    mix = csym.ExitMix(
        sym.Cast(sym.Concat(*gates, dim=1, name="exit_gates"),
                 dtype="float32", name="exit_gates_f32"),
        sym.Concat(*nll, dim=1, name="exit_nll"), beta=exit_beta,
        visits=total_ut_steps * num_layers, name="exit_mix")
    loss = sym.MakeLoss(
        sym.mean(sym.Reshape(mix[0], shape=(-1, seq_len)), axis=1,
                 name="exit_loss_mean"), name="loss")
    mass = sym.BlockGrad(sym.mean(mix[1], axis=0, name="exit_mass_mean"),
                         name="exit_mass")
    return sym.Group([loss, mass])


# keys whose value changes the mathematics and that this builder takes in
# one form only; ``ASSUMED_UNREAD`` are the keys nothing here reads (the
# configuration file lists them under ``assumed``)
_ONLY = {"hidden_act": "silu", "tie_word_embeddings": False,
         "rope_scaling": None, "use_sliding_window": False,
         "sliding_window": None, "attention_bias": False}
ASSUMED_UNREAD = ("max_window_layers", "early_exit_threshold",
                  "max_position_embeddings")


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type`` ouro),
    given as a dict. A key that would change the mathematics and that
    this builder does not implement raises with the key's name: a
    sliding window (``use_sliding_window`` true or a ``sliding_window``),
    a ``layer_types`` entry other than ``full_attention`` or a list that
    is not ``num_hidden_layers`` long, a scaled RoPE, tied embeddings, a
    bias on the projections, an activation other than silu,
    ``total_ut_steps`` under 1. ``exit_beta`` is read where the dict
    has it under ``assumed`` (it is no published key; 0.05 otherwise);
    ``max_position_embeddings`` is the sequence length only where the
    caller gives none, ``max_window_layers`` and ``early_exit_threshold``
    are read by nothing (``ASSUMED_UNREAD``).

    A pipeline stage is the same dict with the layers and the vocabulary
    rows held in place of the published ones (``num_hidden_layers``,
    ``layer_types``, ``vocab_size``)."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("ouro.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    layers = config["num_hidden_layers"]
    layer_types = list(config.get("layer_types") or [FULL] * layers)
    if len(layer_types) != layers:
        raise ValueError(
            "ouro.from_config: layer_types has %d entries, "
            "num_hidden_layers=%r" % (len(layer_types), layers))
    for i, kind in enumerate(layer_types):
        if kind != FULL:
            raise ValueError("ouro.from_config: layer_types[%d]=%r is not "
                             "supported (only %r)" % (i, kind, FULL))
    steps = config["total_ut_steps"]
    if not isinstance(steps, int) or steps < 1:
        raise ValueError("ouro.from_config: total_ut_steps=%r is not "
                         "supported (a whole number of passes, at least 1)"
                         % (steps,))
    heads = config["num_attention_heads"]
    beta = (config.get("assumed") or {}).get("exit_beta", 0.05)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"], num_layers=layers,
        num_heads=heads,
        num_kv_heads=config.get("num_key_value_heads") or heads,
        head_dim=config.get("head_dim") or config["hidden_size"] // heads,
        rope_theta=float(config["rope_theta"]), total_ut_steps=steps,
        exit_beta=float(beta),
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
