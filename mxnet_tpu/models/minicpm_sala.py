"""MiniCPM-SALA (``model_type`` minicpm_sala; OpenBMB, 9B dense, context
524,288): a decoder-only hybrid of Lightning linear attention (a fixed decay
a head) in three layers of four and InfLLM-V2 block-sparse attention in the
fourth, under MiniCPM's width and depth scaling, as an ``mx.sym`` graph that
``Module.fit`` trains — whole, or as one chip's share of layers that two
chips divide by tensor parallelism.

The defaults are ``openbmb/MiniCPM-SALA``'s ``config.json`` (hidden 4096; 32
layers, ``mixer_types`` naming each; ``lightning-attn``: 32 heads of 128 for
queries, keys and values, ``qk_norm``, RoPE theta 1e4 over the whole head,
an output norm and an output gate; ``minicpm4``: 32 query heads on 2
key/value heads of 128, no rotation, the same norms and gate; SwiGLU 16384;
RMSNorm eps 1e-6; ``scale_emb`` 12, ``scale_depth`` 1.4, ``dim_model_base``
256; vocabulary 73448, untied head). With ``s = scale_depth / sqrt(32)``
(the PUBLISHED depth whatever is held; MiniCPM, arXiv:2404.06395) and ``x =
attn_norm(h)``:

    h = scale_emb * E[token]                             # embed_scale
    lightning-attn (layer<i>_linattn_*):
      q, k = RoPE(norm(q_proj(x))), RoPE(norm(k_proj(x)))   # a head's own
      y = LinearAttention(q, k, v_proj(x), norm, gate=g_proj(x))
      h = h + s * o_proj(y)                              # layer<i>_mixer_scale
    minicpm4 (layer<i>_*):
      q, k = q_norm(q_proj(x)), k_norm(k_proj(x))        # no rotation
      keep = BlockSelect(q, k)          # a key/value head; T > dense_len
      a = Attention(q, k, v_proj(x), keep=keep, gate=attn_gate_proj(x))
      h = h + s * o_proj(a)
    h = h + s * SwiGLU(ffn_norm(h))                      # down_proj_scale
    logits = lm_head(final_norm(h)) * dim_model_base / hidden_size

``LinearAttention`` (``ops/transformer/ssm.py``) is ``S_t = lambda S_{t-1} +
k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(128)`` with ``lambda = exp(-slope)``,
``slope(h, l) = 2^(-8 (h + 1) / 32) (1 - l / 31 + 1e-5)`` for the published
head h and layer l (``lightning_slopes``), an RMSNorm over each head's own
columns of o and the sigmoid gate, on the state-space scan's kernels.
``BlockSelect`` (``ops/transformer/blocks.py``) scores mean-pooled keys
(windows of 32 every 16) with the group's own queries and keeps, a query,
block 0, the blocks of its last 2,048 keys and the 64 best-scored blocks of
64 keys between; ``Attention`` reads its int8 keep-mask through the selected
flash pair. At ``sparse_config.dense_len`` (8,192) positions or fewer a
``minicpm4`` layer reads every key and no ``BlockSelect`` is built. Where
several key/value heads are held, each has a ``BlockSelect`` and an
``Attention`` node of its own (``layer<i>_kv<g>_*``): a group's choice is
its own.

**One chip's share.** ``from_config`` reads the counts HELD in place of the
published ones (``lightning_nh`` / ``lightning_nkv``, ``num_attention_heads``
with the ``num_key_value_heads`` they read, ``vocab_size``,
``num_hidden_layers`` with ``mixer_types``) and a group ``share`` beside
them: ``layers_of`` / ``lightning_heads_of`` / ``attention_heads_of`` /
``kv_heads_of`` the uncut counts, ``first_layer`` / ``first_lightning_head``
where the held layers and heads lie among the published ones (the slopes
read both), ``dense_columns_held`` the columns held of ``intermediate_size``,
which stays as published. A share holds a key/value head with ALL the query
heads that read it: the choice of blocks sums over a group's heads.
``models/minicpm_sala_reference.py`` is given the same share.

**Initialisation the model states itself** (``sym.Variable(init=)``; the row
gives none): the embedding Normal(1 / ``scale_emb``), so the stream starts at
unit rms; every matrix that reads a normed input Normal(1 / sqrt(hidden));
the three that write to the stream Normal(1 / sqrt(fan_in)) over the UNCUT
model's fan-in; the head Normal(hidden / dim_model_base / sqrt(hidden)), so
the scaled logits have unit variance. The sparse layers' ``q_norm`` /
``k_norm`` gammas start at ``SPARSE_QK_GAMMA``: scores of standard deviation
2.25, a softmax that a few keys carry, as a trained model's does; under
gammas of one a query at 16k averages six thousand values to nothing and no
comparison could see a wrong choice of blocks. (A gain of 4 on the sparse
layer's ``o_proj`` was tried and taken out: it made ONE marginal block that
bf16 queries and keys choose otherwise than float32 ones a worst token of
0.058 sd of the logits, ``PERF.md`` section 6, PR 79.)

Outputs: the loss per sequence behind ``MakeLoss`` and nothing else. Norm
statistics, RoPE, the carried state, the block scores and their compare, the
gates, the scale products, softmaxes and the loss are float32 whatever
``dtype`` is.
"""
import math

from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from ..ops.transformer import lightning_slopes
from .lm_blocks import head_and_loss, linear, scaled, swiglu

PUBLISHED_MIXERS = tuple(
    "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
    for i in range(32))
# MiniCPM4.1's published ``sparse_config`` (the row says only "block top-64")
SPARSE_CONFIG = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                 "topk": 64, "init_blocks": 1, "window_size": 2048,
                 "dense_len": 8192}
# where the sparse layers' q / k gammas start (the module's docstring)
SPARSE_QK_GAMMA = 1.5


def get_symbol(vocab_size=73448, hidden_size=4096,
               mixer_types=PUBLISHED_MIXERS, num_heads=32, num_kv_heads=2,
               head_dim=128, lightning_heads=32, lightning_head_dim=128,
               dense_width=16384, rope_theta=1e4, scale_emb=12.0,
               scale_depth=1.4, dim_model_base=256, layers_of=None,
               first_layer=0, lightning_heads_of=None,
               first_lightning_head=0, attn_fan_in=None, dense_fan_in=None,
               sparse_config=None, seq_len=16384, rms_eps=1e-6,
               dtype="float32"):
    """One layer a ``mixer_types`` entry. The counts and ``dense_width`` are
    what is HELD here; ``layers_of`` / ``lightning_heads_of`` are the uncut
    model's (the held ones by default) and ``first_layer`` /
    ``first_lightning_head`` where the held ones lie among them;
    ``attn_fan_in`` / ``dense_fan_in`` the uncut widths into ``o_proj`` and
    ``down_proj``, which only the initialisation reads."""
    sparse = dict(SPARSE_CONFIG, **(sparse_config or {}))
    layers_of = layers_of or len(mixer_types)
    lightning_heads_of = lightning_heads_of or lightning_heads
    depth = scale_depth / math.sqrt(layers_of)
    group = num_heads // num_kv_heads
    unit = init.Normal(sigma=hidden_size ** -0.5)

    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    def head_norm(x, name, d, gamma=1.0):  # an RMSNorm over a head's own d
        return csym.RMSNorm(
            sym.Reshape(x, shape=(-1, d)), eps=rms_eps, name=name,
            gamma=sym.Variable(name + "_gamma", init=init.Constant(
                value=gamma)))

    def out_proj(x, name, held, fan_in):
        return linear(sym.Reshape(x, shape=(-1, held)), name, hidden_size,
                      init.Normal(sigma=(fan_in or held) ** -0.5))

    def lightning(x, p, layer):
        n, d, heads = p + "linattn_", lightning_head_dim, lightning_heads
        width = heads * d

        def rotated(name):
            y = head_norm(linear(x, n + name + "_proj", width, unit),
                          n + name + "_norm", d)
            return csym.RoPE(positions(y, width), num_heads=heads,
                             theta=rope_theta, name=n + name + "_rope")

        y = csym.LinearAttention(
            rotated("q"), rotated("k"),
            positions(linear(x, n + "v_proj", width, unit), width),
            norm=sym.Variable(n + "o_norm_gamma", init=init.One()),
            gate=positions(linear(x, n + "g_proj", width, unit), width),
            num_heads=heads, eps=rms_eps, slopes=lightning_slopes(
                lightning_heads_of, first_layer + layer, layers_of,
                first=first_lightning_head, held=heads),
            name=n[:-1])
        return out_proj(y, n + "o_proj", width, lightning_heads_of * d)

    def sparse_attention(x, p):
        d = head_dim
        q_width, kv_width = num_heads * d, num_kv_heads * d
        q = positions(head_norm(linear(x, p + "q_proj", q_width, unit),
                                p + "q_norm", d, SPARSE_QK_GAMMA), q_width)
        k = positions(head_norm(linear(x, p + "k_proj", kv_width, unit),
                                p + "k_norm", d, SPARSE_QK_GAMMA), kv_width)
        v = positions(linear(x, p + "v_proj", kv_width, unit), kv_width)
        gate = positions(linear(x, p + "attn_gate_proj", q_width, unit),
                         q_width)

        def columns(y, g, width):  # key/value head g's columns of y
            if num_kv_heads == 1:
                return y
            return sym.slice_axis(y, axis=2, begin=g * width,
                                  end=(g + 1) * width)

        def chosen(g):  # key/value head g and its group, over chosen blocks
            n = p if num_kv_heads == 1 else "%skv%d_" % (p, g)
            q_g, k_g = columns(q, g, group * d), columns(k, g, d)
            blocks = csym.BlockSelect(
                q_g, k_g, num_heads=group, pool=sparse["kernel_size"],
                stride=sparse["kernel_stride"], block=sparse["block_size"],
                topk=sparse["topk"], init_blocks=sparse["init_blocks"],
                window=sparse["window_size"], name=n + "blocks")
            return csym.Attention(
                q_g, k_g, columns(v, g, d), with_keep=True, keep=blocks[0],
                with_gate=True, gate=columns(gate, g, group * d),
                num_heads=group, num_kv_heads=1, causal=True,
                name=n + "attn")

        if seq_len <= sparse["dense_len"]:  # every key: plain grouped heads
            outs = [csym.Attention(
                q, k, v, with_gate=True, gate=gate, num_heads=num_heads,
                num_kv_heads=num_kv_heads, causal=True, name=p + "attn")]
        else:
            outs = [chosen(g) for g in range(num_kv_heads)]
        attn = outs[0] if len(outs) == 1 else sym.Concat(
            *outs, dim=2, name=p + "attn_heads")
        return out_proj(attn, p + "o_proj", q_width, attn_fan_in)

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    # the residual stream is [tokens, hidden] throughout
    h = scaled(sym.Embedding(
        sym.Reshape(data, shape=(-1,)),
        weight=sym.Variable("embed_weight",
                            init=init.Normal(sigma=1.0 / scale_emb)),
        input_dim=vocab_size, output_dim=hidden_size, dtype=dtype,
        name="embed"), "embed_scale", scale_emb)
    for i, kind in enumerate(mixer_types):
        p = "layer%d_" % i
        x = csym.RMSNorm(h, eps=rms_eps, name=p + "attn_norm")
        mixed = lightning(x, p, i) if kind == "lightning-attn" \
            else sparse_attention(x, p)
        h = h + scaled(mixed, p + "mixer_scale", depth)
        h = h + swiglu(
            csym.RMSNorm(h, eps=rms_eps, name=p + "ffn_norm"), p,
            dense_width, hidden_size, out_scale=depth,
            inits=(unit, unit, init.Normal(
                sigma=(dense_fan_in or dense_width) ** -0.5)))
    return head_and_loss(
        h, label, [], vocab_size, seq_len, rms_eps,
        logit_scale=dim_model_base / hidden_size,
        init=init.Normal(sigma=hidden_size ** 0.5 / dim_model_base))


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"attention_bias": False, "attn_use_rope": False,
         "lightning_use_rope": True, "qk_norm": True, "use_output_gate": True,
         "use_output_norm": True, "attn_use_output_gate": True,
         "hidden_act": "silu", "tie_word_embeddings": False,
         "lightning_scale": "1/sqrt(d)", "model_type": "minicpm_sala"}
# keys the forward pass of a training step does not read (the
# configuration's file says of each why not)
ASSUMED_UNREAD = ("mup_denominator", "rand_init", "max_position_embeddings")
MIXERS = ("lightning-attn", "minicpm4")


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type``
    minicpm_sala), given as a dict. A key that would change the mathematics
    and that this builder does not implement raises: a bias on the attention
    projections, rotated sparse layers or unrotated linear ones, no norm on
    q and k, no output norm or gate, another activation, tied embeddings,
    another ``lightning_scale`` than ``1/sqrt(d)``, a mixer it does not
    know, ``lightning_nkv`` other than ``lightning_nh`` (every linear head
    has its own keys and values). ``ASSUMED_UNREAD`` lists the keys nothing
    reads (``max_position_embeddings`` is the sequence length only where
    the caller gives none). ``sparse_config``, where the dict has one, lays
    its keys over MiniCPM4.1's published ones (``SPARSE_CONFIG``).

    A share of the model is the same dict with the counts held in place of
    the published ones and a group ``share`` beside them (the module's
    docstring)."""
    def refuse(text, *values):
        raise ValueError("minicpm_sala.from_config: " + text % values)

    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            refuse("%s=%r is not supported (only %r)", key, config[key],
                   value)
    mixers = tuple(config["mixer_types"])
    if len(mixers) != config["num_hidden_layers"] or set(mixers) - set(MIXERS):
        refuse("mixer_types=%r must name one of %r for each of the %d "
               "layers", mixers, MIXERS, config["num_hidden_layers"])
    if config["lightning_nkv"] != config["lightning_nh"]:
        refuse("lightning_nkv=%r differs from lightning_nh=%r: a linear "
               "head has keys and values of its own",
               config["lightning_nkv"], config["lightning_nh"])
    share = config.get("share", {})
    q_heads, kv_heads = (config["num_attention_heads"],
                         config["num_key_value_heads"])
    q_of = share.get("attention_heads_of", q_heads)
    kv_of = share.get("kv_heads_of", kv_heads)
    if (kv_heads < 1 or q_heads % kv_heads
            or q_heads * kv_of != q_of * kv_heads):
        refuse("%r of %d query heads on %r of %d key/value heads: a share "
               "holds a key/value head with all the query heads that read "
               "it (the choice of blocks sums over them)", q_heads, q_of,
               kv_heads, kv_of)
    width = config["intermediate_size"]
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        mixer_types=mixers, num_heads=q_heads, num_kv_heads=kv_heads,
        head_dim=config["head_dim"], lightning_heads=config["lightning_nh"],
        lightning_head_dim=config["lightning_head_dim"],
        dense_width=share.get("dense_columns_held", width),
        rope_theta=float(config["rope_theta"]),
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        dim_model_base=config["dim_model_base"],
        layers_of=share.get("layers_of"),
        first_layer=share.get("first_layer", 0),
        lightning_heads_of=share.get("lightning_heads_of"),
        first_lightning_head=share.get("first_lightning_head", 0),
        attn_fan_in=q_of * config["head_dim"], dense_fan_in=width,
        sparse_config=config.get("sparse_config"),
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
