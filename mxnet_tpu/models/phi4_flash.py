"""Phi-4-mini-flash-reasoning (``model_type`` phi4flash; the SambaY
decoder-hybrid-decoder, Ren et al., arXiv:2507.06607): a self-decoder of
Mamba-1 and windowed differential-attention layers, ONE full-attention
layer whose keys and values every later attention layer reads, and a
cross-decoder that alternates Gated Memory Units (reading ONE Mamba layer's
scan output) with differential cross-attention, as an ``mx.sym`` graph that
``Module.fit`` trains — whole, or as one pipeline stage's layers over a
slice of the vocabulary.

The defaults are ``microsoft/Phi-4-mini-flash-reasoning``'s ``config.json``
(hidden 2560; 32 layers; 40 query heads on 20 key/value heads of 64;
SwiGLU of 10240; a 512-key window; LayerNorm eps 1e-5; vocabulary 200064,
the head tied to the embedding). Every layer ``l`` of ``N`` (PUBLISHED
numbering):

    h = h + Mixer_l(LayerNorm(h));   h = h + SwiGLU(LayerNorm(h))

    l < N/2, even     Mamba-1 (``Mamba1``: Gu & Dao, arXiv:2312.00752)
    l < N/2, odd      differential attention under the window
                      (``DiffAttention``: Ye et al., arXiv:2410.05258)
    l = N/2           Mamba-1, and its scan output ``m`` (before the gate)
                      is kept: the memory
    l = N/2 + 1       differential attention, full, and its keys ``K*`` and
                      values ``V*`` are kept
    l > N/2 + 1, even GMU: gmu_out_proj(m * silu(gmu_in_proj(x)))
    l > N/2 + 1, odd  differential cross-attention: its own ``q_proj`` and
                      ``o_proj`` only, against ``K*``, ``V*``, causal, full

then a final LayerNorm and the logits through the transposed embedding. No
positional signal is added anywhere: the Mamba layers carry position.

``m``, ``K*`` and ``V*`` are plain ``Symbol``s that several later nodes
read: one copy each in the step, alive from the middle of the forward pass
to the middle of the backward pass, their gradients the sums over the
readers (the graph program evaluates a node once and ``jax.vjp`` sums a
value's cotangents; ``tests/test_phi4_flash.py`` holds the sums to the
model in which every reader has its own copy of the projections).

Arguments, by layer ``l`` (``FullyConnected`` weights ``[out, in]``):
``layer<l>_norm_{gamma,beta}``, ``layer<l>_ffn_norm_{gamma,beta}``,
``layer<l>_{gate,up,down}_proj_weight``; a Mamba layer's
``layer<l>_mamba_in_proj_weight`` [2 D, hidden], the node
``layer<l>_mamba``'s ``_conv_weight`` [taps, D], ``_conv_bias``,
``_x_proj_weight`` [R + 2 N, D], ``_dt_proj_weight`` [D, R], ``_dt_bias``,
``_a_log`` [D, N], ``_d``, and ``layer<l>_mamba_out_proj_weight``; an
attention layer's ``layer<l>_{q,k,v,o}_proj_{weight,bias}`` (a cross layer
has no ``k`` and ``v``) and the node ``layer<l>_attn``'s ``_lambda_q1``,
``_lambda_k1``, ``_lambda_q2``, ``_lambda_k2`` [64], ``_subln_gamma``
[128]; a GMU layer's ``layer<l>_gmu_{in,out}_proj_weight``;
``embed_weight``, ``final_norm_{gamma,beta}``.

**A pipeline stage.** ``layers_held`` lists the published numbers of the
layers held here (the kind and ``lambda_init`` of each read its published
number), ``published.num_hidden_layers`` is ``N``, ``vocab_size`` the rows
of the tied matrix held (vocabulary parallelism): ids, logits and loss are
over the slice. A stage that holds a cross-decoder layer must hold layers
``N/2`` and ``N/2 + 1``: no code stands in for ``m``, ``K*``, ``V*``
arriving from another stage. ``models/phi4_flash_reference.py`` is given
the same configuration.

**Initialisation the model states itself** (``sym.Variable(init=)``): the
taps uniform in +-1/sqrt(taps) (a ``Conv1d``'s default); Mamba's rule for
the parameters that decide the dynamics, ``a_log = log(1..N)`` a channel,
``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in [0.001, 0.1] and
not under 1e-4, ``d`` 1; the four lambda vectors Normal(0.1) (Ye et al.);
and the embedding Normal(``embed_sigma`` = 0.02), NOT the unit embedding of
the untied symbols: the head multiplies the final norm's unit-variance
output by this same matrix (``models/lfm2.py``).

Outputs: the loss per sequence behind ``MakeLoss`` and nothing else. Norm
statistics, the convolution's sum, ``dt``, the decays, the state and its
sum over the state index, the gates, ``lambda``, the difference of the two
maps and its norm, softmaxes and loss arithmetic are float32 whatever
``dtype`` is.
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import head_and_loss, linear, swiglu

MAMBA, WINDOW, MEMORY, FULL, GMU, CROSS = (
    "mamba", "window_attention", "mamba_memory", "full_attention", "gmu",
    "cross_attention")


def layer_kind(number, depth):
    """The mixer of published layer ``number`` of ``depth``."""
    half = depth // 2
    if number < half:
        return MAMBA if number % 2 == 0 else WINDOW
    if number == half:
        return MEMORY
    if number == half + 1:
        return FULL
    return GMU if number % 2 == 0 else CROSS


def get_symbol(vocab_size=200064, hidden_size=2560, intermediate_size=10240,
               depth=32, layers_held=None, num_heads=40, num_kv_heads=20,
               window=512, state_size=16, conv_kernel=4, expand=2,
               dt_rank=None, seq_len=4096, norm_eps=1e-5, dtype="float32",
               embed_sigma=0.02):
    """One block a published layer number of ``layers_held`` (all ``depth``
    by default). ``state_size``, ``conv_kernel``, ``expand`` and
    ``dt_rank`` (hidden / 16 rounded up by default) are the Mamba family's
    convention: ``config.json`` has no key for them."""
    layers_held = tuple(range(depth) if layers_held is None else layers_held)
    if depth % 4 or list(layers_held) != sorted(set(layers_held)) or \
            not all(0 <= n < depth for n in layers_held):
        raise ValueError(
            "phi4_flash: depth=%r must be a multiple of 4 (Mamba and "
            "attention alternate in both halves) and layers_held=%r "
            "ascending numbers below it" % (depth, layers_held))
    half = depth // 2
    if any(n > half + 1 for n in layers_held) and not (
            half in layers_held and half + 1 in layers_held):
        raise ValueError(
            "phi4_flash: layers_held=%r holds a cross-decoder layer "
            "without layers %d and %d, whose memory, keys and values it "
            "reads" % (layers_held, half, half + 1))
    d_in = expand * hidden_size
    dt_rank = -(-hidden_size // 16) if dt_rank is None else dt_rank
    head_dim = hidden_size // num_heads
    kv_width = num_kv_heads * head_dim
    shared = {}                      # "memory", "key", "value": Symbols

    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    def norm(x, name):
        return csym.LayerNorm(x, eps=norm_eps, name=name)

    def biased(x, name, width):
        return sym.FullyConnected(x, num_hidden=width, name=name)

    def mamba(x, p, number):
        node = csym.Mamba1(
            positions(linear(x, p + "mamba_in_proj", 2 * d_in), 2 * d_in),
            conv_weight=sym.Variable(
                p + "mamba_conv_weight",
                init=init.Uniform(scale=conv_kernel ** -0.5)),
            a_log=sym.Variable(p + "mamba_a_log", init=init.LogOfIndex()),
            dt_bias=sym.Variable(p + "mamba_dt_bias", init=init.InverseSoftplus(
                low=0.001, high=0.1, floor=1e-4)),
            d=sym.Variable(p + "mamba_d", init=init.One()),
            channels=d_in, state_size=state_size, dt_rank=dt_rank,
            conv_kernel=conv_kernel, name=p + "mamba")
        if number == half:
            shared["memory"] = node[1]
        return linear(sym.Reshape(node[0], shape=(-1, d_in)),
                      p + "mamba_out_proj", hidden_size)

    def attention(x, p, number):
        kind = layer_kind(number, depth)
        q = positions(biased(x, p + "q_proj", hidden_size), hidden_size)
        if kind == CROSS:
            k, v = shared["key"], shared["value"]
        else:
            k = positions(biased(x, p + "k_proj", kv_width), kv_width)
            v = positions(biased(x, p + "v_proj", kv_width), kv_width)
        if kind == FULL:
            shared["key"], shared["value"] = k, v
        lambdas = {
            name: sym.Variable(p + "attn_" + name,
                               init=init.Normal(sigma=0.1))
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}
        attn = csym.DiffAttention(
            q, k, v, num_heads=num_heads, num_kv_heads=num_kv_heads,
            depth=number, window=window if kind == WINDOW else 0,
            eps=norm_eps, cross=kind == CROSS,
            kv_from="layer%d" % (half + 1) if kind == CROSS else "",
            name=p + "attn", **lambdas)
        return biased(sym.Reshape(attn, shape=(-1, hidden_size)),
                      p + "o_proj", hidden_size)

    def gmu(x, p, number):
        gate = sym.Activation(linear(x, p + "gmu_in_proj", d_in),
                              act_type="silu", name=p + "gmu_gate")
        return linear(
            sym.elemwise_mul(sym.Reshape(shared["memory"],
                                         shape=(-1, d_in)), gate,
                             name=p + "gmu"),
            p + "gmu_out_proj", hidden_size)

    mixers = {MAMBA: mamba, MEMORY: mamba, WINDOW: attention,
              FULL: attention, CROSS: attention, GMU: gmu}
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = sym.Variable("embed_weight", init=init.Normal(sigma=embed_sigma))
    # the residual stream is [tokens, hidden] throughout
    h = sym.Embedding(sym.Reshape(data, shape=(-1,)), weight=embed,
                      input_dim=vocab_size, output_dim=hidden_size,
                      dtype=dtype, name="embed")
    for number in layers_held:
        p = "layer%d_" % number
        h = h + mixers[layer_kind(number, depth)](
            norm(h, p + "norm"), p, number)
        h = h + swiglu(norm(h, p + "ffn_norm"), p, intermediate_size,
                       hidden_size)
    return head_and_loss(h, label, [], vocab_size, seq_len, norm_eps,
                         tied_to=embed, norm=norm)


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"hidden_act": "silu", "tie_word_embeddings": True,
         "mlp_bias": False, "lm_head_bias": False, "mb_per_layer": 2,
         "embd_pdrop": 0, "resid_pdrop": 0}


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type``
    phi4flash), given as a dict. A key that would change the mathematics
    and that this builder does not implement (another activation, an
    untied head, a bias in the feed-forward or the head, a Mamba mixer
    other than every second layer, dropout) raises. The sizes the
    published file has no key for are read from ``assumed_sizes`` where
    the dict has it (``state_size``, ``conv_kernel``, ``expand``,
    ``dt_rank``), else the Mamba family's convention.

    A pipeline stage is the same dict with ``layers_held`` (the published
    numbers of the layers held), ``num_hidden_layers`` their count,
    ``published.num_hidden_layers`` the model's depth and ``vocab_size``
    the rows held."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("phi4_flash.from_config: %s=%r is not "
                             "supported (only %r)" % (key, config[key], value))
    depth = config.get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    held = config.get("layers_held")
    if held is not None and len(held) != config["num_hidden_layers"]:
        raise ValueError(
            "phi4_flash.from_config: layers_held has %d entries, "
            "num_hidden_layers=%r" % (len(held), config["num_hidden_layers"]))
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"], depth=depth,
        layers_held=held, num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        window=config["sliding_window"],
        seq_len=seq_len or config["max_position_embeddings"],
        norm_eps=config["layer_norm_eps"], dtype=dtype,
        **config.get("assumed_sizes", {}))
