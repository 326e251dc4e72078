"""Falcon-H1 (``model_type`` falcon_h1): a dense decoder-only hybrid
whose every block runs a Mamba-2 state-space mixer AND grouped-head
attention side by side on ONE normed input, under fourteen fixed scalar
multipliers, as an ``mx.sym`` graph that ``Module.fit`` trains — whole,
or as one chip's share of layers that several chips divide by tensor
parallelism.

The defaults are ``tiiuae/Falcon-H1-34B-Instruct``'s ``config.json``
(hidden 5120; 72 identical layers; Mamba-2 of 32 heads of 128, so 4096
columns (``mamba_d_ssm``, stated, not ``mamba_expand`` x hidden), state
256, 2 groups, 4 conv taps with bias, chunks of 128; attention of 20
query heads on 4 key/value heads of 128, RoPE over the whole head with
theta 1e11, the halves rotated; SwiGLU of 21504; RMSNorm eps 1e-5;
vocabulary 261120, untied head). A layer, with ``n = norm(h)``
(``lm_blocks.parallel_block``):

    h = h + ssm_out * out_proj(Mamba2(in_proj(n), multipliers=ssm_in * m))
          + attn_out * o_proj(Attention(rope(q_proj(a)),
                               rope(key * k_proj(a)), v_proj(a)))
    h = h + mlp_1 * down_proj(up_proj(f) * silu(mlp_0 * gate_proj(f)))

with ``a = attn_in * n`` and ``f = ffn_norm(h)``; the embedding's rows
times ``embedding_multiplier``, then ``final_norm``, ``lm_head`` and the
logits times ``lm_head_multiplier``. **Where each of the fourteen
multipliers lands is the published ``falcon_h1`` modelling code's**:
``ssm_multipliers`` m[0..4] over the five segments ``z | x | B | C |
dt`` of ``in_proj``'s output and ``ssm_in_multiplier`` on its input
(here both inside ``Mamba2``, ``ops/transformer.mamba2(multipliers=)``:
a product commutes with a scalar, and no scaled copy of ``[T, 9248]`` is
made), ``ssm_out_multiplier`` and ``attention_out_multiplier`` on the two
mixers' outputs (one ``ScaledSum`` node, ``layer<i>_mixer_sum``),
``attention_in_multiplier`` on the input of ``q``, ``k`` and ``v``,
``key_multiplier`` on ``k_proj``'s output, ``mlp_multipliers`` on the
gate's pre-activation and on ``down_proj``'s output. A multiplier of 1
adds no node. ``Mamba2`` owns the convolution
(``layer<i>_ssm_conv_weight`` [taps, channels], ``_conv_bias``), the
step sizes' bias, the decay rates and the skip (``_dt_bias``, ``_a_log``,
``_d``, one a head) and the gated norm's scale (``_norm_gamma``); the
nine projections of a layer are ``FullyConnected`` nodes. ``data`` holds
token ids ``[batch, seq_len]`` and ``softmax_label`` the next token at
each position.

**One chip's share** (``from_config``): ``mamba_n_heads`` /
``mamba_n_groups`` and ``num_attention_heads`` / ``num_key_value_heads``
are the counts HELD, ``share.ssm_columns_held`` and
``share.dense_columns_held`` the columns of ``mamba_d_ssm`` and
``intermediate_size`` (which stay as published: a width), ``vocab_size``
the rows held. ``in_proj``, ``q`` / ``k`` / ``v`` and ``gate`` / ``up``
are divided by columns, ``out_proj``, ``o_proj`` and ``down_proj`` by
rows; the norms and the residual stream are whole. A chip's three
outputs are PARTIAL sums: what the other chips would add, and the
all-reduce that adds it, are left out here and in
``models/falcon_h1_reference.py`` alike, and nothing stands in for them.
**A share holds whole groups**: the gated RMSNorm's statistic is over one
group's columns, so a group divided between chips would either norm a
part of it (not what a deployment computes) or need an exchange inside
the mixer; ``from_config`` refuses it.

**Initialisation the model states itself** (``sym.Variable(init=)``;
the published row gives none). Under a plain ``Normal(0.02)`` the
multipliers (0.011 to 0.09 on the sub-layers' outputs) would leave every
sub-layer under a thousandth of the residual stream, and no comparison
could see a broken mixer. Every matrix is ``Normal(gain / (sqrt(fan_in)
* m))``, ``m`` the fixed multipliers on its path and ``fan_in`` the
UNCUT model's (a share holds a slice of the whole model's matrices), so
that at step 0 the multipliers cancel: the embedding's rows times
``embedding_multiplier`` have unit rms, ``q``, the scaled ``k``, ``v``,
``x`` (segment 1 of the scaled projection), the gate's scaled
pre-activation and ``up`` have unit rms, the scaled logits ``gain``
``head``; the three gains on ``o_proj``, ``out_proj`` and ``down_proj``
(``INIT_GAINS``) are chosen, and measured at the published widths, so
that each scaled sub-layer output is between a tenth of and one times
the rms of the stream it is added to, in every layer. The taps are
uniform in +-1/sqrt(taps), the convolution's bias zeros, the skip ones,
``a_log = log(U(1, 16))`` and ``dt_bias = softplus^-1(dt)`` with ``dt``
log-uniform in [0.001, 0.1], as ``models/nemotron_h.py``; gammas one.

Outputs: the loss per sequence behind ``MakeLoss`` and nothing else.
Norm statistics, the convolution's sum, step sizes, decays, the carried
state, the gate, every multiplier's product, RoPE, softmax and loss
arithmetic are float32 whatever ``dtype`` is.
"""
import math

from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import head_and_loss, linear, parallel_block, scaled, swiglu

# the gains of the three projections that write to the residual stream
# and of the head, chosen at the published widths (see the module's
# docstring; bench/configs/falcon_h1_34b.json holds the measured ratios)
INIT_GAINS = {"o_proj": 3.0, "out_proj": 0.5, "down_proj": 1.2, "head": 1.0}


def get_symbol(vocab_size=261120, hidden_size=5120, num_layers=72,
               mamba_heads=32, mamba_head_dim=128, state_size=256,
               num_groups=2, conv_kernel=4, chunk_size=128, num_heads=20,
               num_kv_heads=4, head_dim=128, rope_theta=1e11,
               dense_width=21504, embedding_multiplier=5.656854249492381,
               lm_head_multiplier=0.0078125, attention_in_multiplier=1.0,
               attention_out_multiplier=0.0375,
               key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
               ssm_out_multiplier=0.08838834764831845,
               ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369,
                                0.5, 0.3535533905932738),
               mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
               ssm_fan_in=None, attn_fan_in=None, dense_fan_in=None,
               seq_len=4096, rms_eps=1e-5, dtype="float32"):
    """``num_layers`` identical layers. The counts and ``dense_width``
    are what is HELD here; ``ssm_fan_in`` / ``attn_fan_in`` /
    ``dense_fan_in`` are the uncut model's widths into ``out_proj``,
    ``o_proj`` and ``down_proj`` (the held ones by default), which only
    the initialisation reads."""
    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    d_in = mamba_heads * mamba_head_dim
    proj_width = 2 * d_in + 2 * num_groups * state_size + mamba_heads
    m_gate, m_down = mlp_multipliers

    def normal(fan_in, multiplier, gain=1.0):
        return init.Normal(sigma=gain / (math.sqrt(fan_in) * multiplier))

    def mamba(x, p):
        def var(name, rule):
            return sym.Variable(p + "ssm_" + name, init=rule)

        y = csym.Mamba2(
            positions(linear(x, p + "in_proj", proj_width, normal(
                hidden_size, ssm_in_multiplier * ssm_multipliers[1])),
                proj_width),
            conv_weight=var("conv_weight", init.Uniform(
                scale=conv_kernel ** -0.5)),
            conv_bias=var("conv_bias", init.Zero()),
            dt_bias=var("dt_bias", init.InverseSoftplus(
                low=0.001, high=0.1, floor=1e-4)),
            a_log=var("a_log", init.LogOfUniform(low=1.0, high=16.0)),
            d=var("d", init.One()),
            norm_gamma=var("norm_gamma", init.One()),
            num_heads=mamba_heads, head_dim=mamba_head_dim,
            state_size=state_size, num_groups=num_groups,
            conv_kernel=conv_kernel, chunk_size=chunk_size, eps=rms_eps,
            multipliers=tuple(ssm_in_multiplier * m
                              for m in ssm_multipliers),
            name=p + "ssm")
        return linear(sym.Reshape(y, shape=(-1, d_in)), p + "out_proj",
                      hidden_size, normal(ssm_fan_in or d_in,
                                          ssm_out_multiplier,
                                          INIT_GAINS["out_proj"]))

    def attention(x, p):
        x = scaled(x, p + "attn_in_scale", attention_in_multiplier)
        q, k, v = (
            linear(x, p + name + "_proj", heads * head_dim,
                   normal(hidden_size, attention_in_multiplier * m))
            for name, heads, m in (("q", num_heads, 1.0),
                                   ("k", num_kv_heads, key_multiplier),
                                   ("v", num_kv_heads, 1.0)))
        k = scaled(k, p + "k_proj_scale", key_multiplier)
        q, k = (csym.RoPE(positions(t, heads * head_dim), num_heads=heads,
                          theta=rope_theta, name=p + name + "_rope")
                for t, name, heads in ((q, "q", num_heads),
                                       (k, "k", num_kv_heads)))
        attn = csym.Attention(
            q, k, positions(v, num_kv_heads * head_dim),
            num_heads=num_heads, num_kv_heads=num_kv_heads, causal=True,
            name=p + "attn")
        return linear(sym.Reshape(attn, shape=(-1, num_heads * head_dim)),
                      p + "o_proj", hidden_size, normal(
                          attn_fan_in or num_heads * head_dim,
                          attention_out_multiplier, INIT_GAINS["o_proj"]))

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    # the residual stream is [tokens, hidden] throughout
    h = scaled(sym.Embedding(
        sym.Reshape(data, shape=(-1,)),
        weight=sym.Variable("embed_weight",
                            init=normal(1, embedding_multiplier)),
        input_dim=vocab_size, output_dim=hidden_size, dtype=dtype,
        name="embed"), "embed_scale", embedding_multiplier)
    for i in range(num_layers):
        p = "layer%d_" % i
        h = parallel_block(h, p, rms_eps, [
            ("mamba2", ssm_out_multiplier, mamba),
            ("attention", attention_out_multiplier, attention)])
        h = h + swiglu(
            csym.RMSNorm(h, eps=rms_eps, name=p + "ffn_norm"), p,
            dense_width, hidden_size, gate_scale=m_gate, out_scale=m_down,
            inits=(normal(hidden_size, m_gate), normal(hidden_size, 1.0),
                   normal(dense_fan_in or dense_width, m_down,
                          INIT_GAINS["down_proj"])))
    return head_and_loss(h, label, [], vocab_size, seq_len, rms_eps,
                         logit_scale=lm_head_multiplier,
                         init=normal(hidden_size, lm_head_multiplier,
                                     INIT_GAINS["head"]))


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"attention_bias": False, "mlp_bias": False, "projectors_bias": False,
         "mamba_proj_bias": False, "mamba_conv_bias": True,
         "mamba_rms_norm": True, "mamba_norm_before_gate": False,
         "mamba_use_mlp": True, "tie_word_embeddings": False,
         "hidden_act": "silu", "rope_scaling": None,
         "attn_layer_indices": None}


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type``
    falcon_h1), given as a dict. A key that would change the mathematics
    and that this builder does not implement (a bias on a projection, a
    convolution without one, the norm before the gate or no gated norm,
    tied embeddings, a scaled RoPE, attention in some layers only,
    another activation) raises.

    A share of the model is the same dict with the counts held in place
    of the published ones (``mamba_n_heads``, ``mamba_n_groups``,
    ``num_attention_heads``, ``num_key_value_heads``, ``vocab_size``,
    ``num_hidden_layers``) and a group ``share`` beside them:
    ``mamba_heads_of`` / ``mamba_groups_of`` / ``attention_heads_of`` /
    ``kv_heads_of`` the uncut counts, ``ssm_columns_held`` /
    ``dense_columns_held`` the columns held of ``mamba_d_ssm`` and
    ``intermediate_size``, which stay as published."""
    def refuse(text, *values):
        raise ValueError("falcon_h1.from_config: " + text % values)

    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            refuse("%s=%r is not supported (only %r)", key, config[key],
                   value)
    share = config.get("share", {})
    heads, groups = config["mamba_n_heads"], config["mamba_n_groups"]
    p = config["mamba_d_head"]
    heads_of = share.get("mamba_heads_of", heads)
    groups_of = share.get("mamba_groups_of", groups)
    if heads_of * p != config["mamba_d_ssm"]:
        refuse("mamba_d_ssm=%r is not %d heads of mamba_d_head=%r (the "
               "uncut model's: share.mamba_heads_of where heads are held)",
               config["mamba_d_ssm"], heads_of, p)
    if groups < 1 or heads % groups or heads * groups_of != heads_of * groups:
        refuse("%r of %d Mamba-2 heads with %r of %d groups divide a group "
               "between chips (a whole group is %d heads): the gated "
               "RMSNorm's statistic is over one group's %d columns, so such "
               "a share would norm a part of a group, which no deployment "
               "computes, or need an exchange inside the mixer; hold whole "
               "groups", heads, heads_of, groups, groups_of,
               heads_of // groups_of, heads_of // groups_of * p)
    if share.get("ssm_columns_held", heads * p) != heads * p:
        refuse("share.ssm_columns_held=%r is not the %d held heads of %d",
               share["ssm_columns_held"], heads, p)
    q_heads, kv_heads = (config["num_attention_heads"],
                         config["num_key_value_heads"])
    q_of = share.get("attention_heads_of", q_heads)
    kv_of = share.get("kv_heads_of", kv_heads)
    if kv_heads < 1 or q_heads % kv_heads or q_heads * kv_of != q_of * kv_heads:
        refuse("%r of %d query heads on %r of %d key/value heads: a share "
               "holds a key/value head with all the query heads that read "
               "it", q_heads, q_of, kv_heads, kv_of)
    width = config["intermediate_size"]
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"], mamba_heads=heads,
        mamba_head_dim=p, state_size=config["mamba_d_state"],
        num_groups=groups, conv_kernel=config["mamba_d_conv"],
        chunk_size=config["mamba_chunk_size"], num_heads=q_heads,
        num_kv_heads=kv_heads, head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        dense_width=share.get("dense_columns_held", width),
        embedding_multiplier=config["embedding_multiplier"],
        lm_head_multiplier=config["lm_head_multiplier"],
        attention_in_multiplier=config["attention_in_multiplier"],
        attention_out_multiplier=config["attention_out_multiplier"],
        key_multiplier=config["key_multiplier"],
        ssm_in_multiplier=config["ssm_in_multiplier"],
        ssm_out_multiplier=config["ssm_out_multiplier"],
        ssm_multipliers=tuple(config["ssm_multipliers"]),
        mlp_multipliers=tuple(config["mlp_multipliers"]),
        ssm_fan_in=config["mamba_d_ssm"],
        attn_fan_in=q_of * config["head_dim"], dense_fan_in=width,
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
