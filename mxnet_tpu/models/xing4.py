"""Xing4.0-29B-A4B (``model_type`` xing4_0: ``deepseek_v3``'s key set plus
``hc_*``): a decoder-only LM of latent attention with a query latent under
a YaRN-scaled RoPE, over shared and sigmoid-routed sparse experts, whose
residual path is FOUR streams a token mixed by learned, Sinkhorn-normalised
matrices round every sub-layer (manifold-constrained hyper-connections,
arXiv:2512.24880 on arXiv:2409.19606), with a multi-token-prediction module
that has a layer of its own (arXiv:2412.19437 section 2.2) — as an
``mx.sym`` graph that ``Module.fit`` trains, whole or as one chip's share.

The defaults are ``XingChen-AGI/Xing4.0-29B-A4B``'s ``config.json`` (hidden
3584; 40 layers; 32 heads of 128 un-rotated + 64 rotary query/key and 128
value dimensions, keys and values projected up from one 512-wide latent a
token, queries from a 768-wide one; theta 1e4 under YaRN factor 64 over
4096 positions; layers 0-1 a dense SwiGLU of 9216, every other layer one
shared SwiGLU of 1024 beside 64 routed experts of 1024, top-4 by sigmoid
score plus a selection bias, renormalised, times 2; ``hc_mult`` 4 streams,
``hc_sinkhorn_iters`` 20; one prediction module; vocabulary 131072, untied
head). The stream is ``X`` [tokens, 4 x hidden], ``X_0[j] = embed(id)``
for every j. A block is two sub-layers, each wrapped alike
(``lm_blocks.hyper_block``):

    pre, post, res = HyperCoeff(X)            # of the token, float32
    u  = sum_j pre[j] X[j]
    y  = F(RMSNorm(u))                        # attention, or feed-forward
    X' = res X + post y                       # res doubly stochastic

``F`` is ``deepseek_v3``'s: ``LatentAttention`` on ``q_b(RMSNorm(q_a(x)))``
and ``kv_a(x)`` with ``rope_scaling`` and the score scale ``192^-0.5 m^2``,
``m = 0.1 mscale_all_dim ln(factor) + 1``, then ``o_proj``; the dense
SwiGLU, or the shared SwiGLU plus ``TopKMoE`` as ``models/kanana2.py``
calls it. After the last block ``h = sum_j X[j]``, ``final_norm``,
``lm_head``.

**The prediction module** (``mtp0_*``): at position i, ``h' = mtp0_proj
[RMSNorm(embed(id_{i+1})) ; RMSNorm(h_i)]`` (``h_i`` the summed streams
before ``final_norm``; ``id_{i+1}`` is ``softmax_label``), replicated to
the 4 streams, ONE expert block of the same class with its own weights,
mixing and router, the streams' sum, ``mtp0_final_norm`` and the model's
ONE head; it predicts ``id_{i+2}`` (the label one to the left; the last
position has none and is left out of its mean). ``embed_weight`` and
``lm_head_weight`` are one ``Variable`` each, read twice: one gradient, the
sum of both uses. Loss = main cross-entropy + ``mtp_loss_weight`` x the
module's.

**One chip's share** is ``models/kanana2.py``'s: ``vocab_size`` the rows
held, ``experts_held`` of ``num_experts`` from ``expert_offset`` on, rows
compacted into ``share_rows_bound``; attention, both latents, the shared
expert, the router, the dense layer and the mixing whole.

Outputs: the loss per sequence behind ``MakeLoss`` (``loss``); each expert
layer's row counts (the module's last); then, behind ``BlockGrad``, the
main loss (``loss_part``), the module's (``mtp0_loss_part``) and
``hc_res_sum_err`` [1], the largest ``|rowsum - 1|``, ``|colsum - 1|`` of
any carry matrix in the step. Float32 whatever ``dtype`` is: router, norm
statistics, RoPE, softmaxes, the losses, and everything of the mixing up
to its accumulation (the coefficient products take ``dtype`` operands and
accumulate in float32).

Initialisation the model states (``sym.Variable(init=)``): a unit
embedding, zero selection biases, ``hc_phi`` Normal(0.02), ``hc_alpha``
0.01, ``hc_bias`` 0 but 4 on the carry's diagonal. Left out of the step,
here and in ``models/xing4_reference.py``: the rule that moves the
selection bias, any auxiliary loss.
"""
import math

from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import (
    expert_layer, heads_and_loss, hyper_block, linear, swiglu)


def score_scale(qk_head_dim, factor=1.0, mscale_all_dim=0.0):
    """``deepseek_v3``'s scale on the scores: ``1 / sqrt(qk_head_dim)``
    times ``m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1`` under a scaled
    RoPE that states ``mscale_all_dim``."""
    m = (0.1 * mscale_all_dim * math.log(factor) + 1.0
         if factor > 1 and mscale_all_dim else 1.0)
    return qk_head_dim ** -0.5 * m * m


def get_symbol(vocab_size=131072, hidden_size=3584, num_layers=40,
               dense_layers=2, num_heads=32, nope_head_dim=128,
               rope_head_dim=64, v_head_dim=128, latent_width=512,
               query_latent_width=768, rope_theta=1e4,
               rope_scaling=(64.0, 32.0, 1.0, 4096.0), mscale_all_dim=1.0,
               rope_interleave=True, dense_width=9216, num_experts=64,
               experts_held=0, expert_offset=0, share_rows_bound=0,
               experts_per_token=4, expert_width=1024, shared_experts=1,
               routed_scale=2.0, norm_topk_prob=True, scoring="sigmoid",
               streams=4, sinkhorn_iters=20, hc_eps=1e-6,
               res_clamp=(-30.0, 30.0), mtp_modules=1, mtp_loss_weight=0.3,
               seq_len=4096, rms_eps=1e-6, dtype="float32",
               embed_sigma=1.0):
    """The first ``dense_layers`` layers have the dense feed-forward, the
    rest (and the prediction module's block) shared and routed experts.
    ``rope_scaling``: ``(factor, beta_fast, beta_slow, original positions)``
    or ``()`` for the plain rotation."""
    if mtp_modules not in (0, 1):
        raise ValueError("xing4.get_symbol: mtp_modules=%r (0 or 1: a chain "
                         "of modules is not implemented)" % (mtp_modules,))
    q_width = num_heads * (nope_head_dim + rope_head_dim)
    scale = score_scale(nope_head_dim + rope_head_dim,
                        rope_scaling[0] if rope_scaling else 1.0,
                        mscale_all_dim)
    plain = (nope_head_dim + rope_head_dim) ** -0.5

    def norm(x, name):
        return csym.RMSNorm(x, eps=rms_eps, name=name)

    def positions(x, width, name):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width), name=name)

    def attention(x, p):
        # the query latent: three nodes, ``<p>q_latent_*``
        cq = norm(linear(x, p + "q_latent_a_proj", query_latent_width),
                  p + "q_latent_norm")
        attn = csym.LatentAttention(
            positions(linear(cq, p + "q_latent_b_proj", q_width), q_width,
                      p + "q_positions"),
            positions(linear(x, p + "kv_a_proj", latent_width + rope_head_dim),
                      latent_width + rope_head_dim, p + "kv_positions"),
            num_heads=num_heads, rope_dim=rope_head_dim,
            v_head_dim=v_head_dim, theta=rope_theta, eps=rms_eps,
            interleave=rope_interleave, query_latent=query_latent_width,
            rope_scaling=tuple(rope_scaling),
            score_scale=0.0 if scale == plain else scale, name=p + "attn")
        attn = sym.Reshape(attn, shape=(-1, num_heads * v_head_dim),
                           name=p + "attn_tokens")
        return linear(attn, p + "o_proj", hidden_size)

    counts, errs = [], []

    def feed_forward(experts):
        def dense(x, p):
            return swiglu(x, p, dense_width, hidden_size)

        def sparse(x, p):
            moe, count = expert_layer(
                x, p, num_experts=num_experts, num_hidden=expert_width,
                top_k=experts_per_token, norm_topk_prob=norm_topk_prob,
                scoring=scoring, routed_scale=routed_scale,
                experts_held=experts_held, expert_offset=expert_offset,
                share_rows_bound=share_rows_bound)
            counts.append(count)
            if shared_experts:
                moe = moe + swiglu(x, p + "shared_",
                                   shared_experts * expert_width, hidden_size)
            return moe

        return sparse if experts else dense

    def block(stream, p, experts):
        for part, sublayer in (("attn", attention),
                               ("ffn", feed_forward(experts))):
            stream, err = hyper_block(
                stream, p, part, rms_eps, sublayer, streams,
                sinkhorn_iters, hc_eps, res_clamp)
            errs.append(err)
        return stream

    def replicated(h, name):  # [tokens, hidden] -> every stream a copy
        return sym.Concat(*[h] * streams, dim=1, name=name) \
            if streams > 1 else h

    def summed(stream, name):  # [tokens, n hidden] -> the streams' sum
        if streams == 1:
            return stream
        return csym.ScaledSum(
            *[sym.slice_axis(stream, axis=1, begin=j * hidden_size,
                             end=(j + 1) * hidden_size,
                             name="%s%d" % (name, j))
              for j in range(streams)],
            scales=(1.0,) * streams, name=name)

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    # ONE embedding and ONE head: the prediction module reads both again
    embed_weight = sym.Variable("embed_weight",
                                init=init.Normal(sigma=embed_sigma))
    head_weight = sym.Variable("lm_head_weight")

    def embed(ids, name):
        return sym.Embedding(
            sym.Reshape(ids, shape=(-1,), name=name + "_ids"),
            weight=embed_weight,
            input_dim=vocab_size, output_dim=hidden_size, dtype=dtype,
            name=name)

    stream = replicated(embed(data, "embed"), "streams")
    for i in range(num_layers):
        stream = block(stream, "layer%d_" % i, i >= dense_layers)
    h = summed(stream, "stream_sum")
    heads = [("", h, label, seq_len, 1.0)]
    for k in range(mtp_modules):
        p = "mtp%d_" % k
        joined = sym.Concat(
            norm(embed(label, p + "embed"), p + "embed_norm"),
            norm(h, p + "hidden_norm"), dim=1, name=p + "concat")
        stream = block(replicated(linear(joined, p + "proj", hidden_size),
                                  p + "streams"), p, True)
        # position i's target is id_{i+2}: the label one to the left (the
        # last column is a stand-in that ``targets`` leaves out)
        ahead = sym.Concat(
            sym.slice_axis(label, axis=1, begin=1, end=seq_len,
                           name=p + "label_ahead"),
            sym.slice_axis(label, axis=1, begin=0, end=1,
                           name=p + "label_last"), dim=1, name=p + "label")
        heads.append((p, summed(stream, p + "stream_sum"), ahead,
                      seq_len - 1, mtp_loss_weight))
    err = sym.BlockGrad(
        sym.max(sym.Concat(*errs, dim=0), axis=0, keepdims=True),
        name="hc_res_sum_err")
    return heads_and_loss(heads, counts, [err], vocab_size, seq_len,
                          rms_eps, head_weight)


# keys whose value changes the mathematics and that this builder takes in
# one form only
_ONLY = {"attention_bias": False, "hidden_act": "silu",
         "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
         "topk_method": "noaux_tc", "moe_layer_freq": 1,
         "scoring_func": "sigmoid", "ep_size": 1}


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type`` xing4_0),
    given as a dict. A key that would change the mathematics and that this
    builder does not implement (grouped routing, a projection bias, a tied
    head, a RoPE scaling other than YaRN with ``mscale`` =
    ``mscale_all_dim``, no query latent, more than one prediction module)
    raises. A share of the model is ``kanana2.from_config``'s: the counts
    held in ``vocab_size`` and ``n_routed_experts`` and a group ``share``
    (``experts_of``, ``expert_offset``, ``share_rows_bound``)."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("xing4.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    if not config.get("q_lora_rank"):
        raise ValueError("xing4.from_config: q_lora_rank=%r (the query "
                         "latent is what this builder builds; "
                         "models/kanana2.py has the form without)"
                         % (config.get("q_lora_rank"),))
    if config.get("num_nextn_predict_layers", 0) not in (0, 1):
        raise ValueError("xing4.from_config: num_nextn_predict_layers=%r "
                         "(0 or 1)" % (config["num_nextn_predict_layers"],))
    heads = config["num_attention_heads"]
    if config.get("num_key_value_heads", heads) != heads:
        raise ValueError(
            "xing4.from_config: num_key_value_heads=%r differs from "
            "num_attention_heads=%r (the up-projection gives every head "
            "its own key and value)" % (config["num_key_value_heads"], heads))
    scaling = config.get("rope_scaling") or None
    rope_scaling, mscale_all_dim = (), 0.0
    if scaling is not None:
        kind = scaling.get("type", scaling.get("rope_type"))
        mscale_all_dim = scaling.get("mscale_all_dim", 0)
        if kind != "yarn" or scaling.get("mscale", 1) != (mscale_all_dim
                                                          or 1):
            raise ValueError(
                "xing4.from_config: rope_scaling=%r (only type yarn with "
                "mscale == mscale_all_dim, whose cos and sin are not "
                "scaled)" % (scaling,))
        rope_scaling = (float(scaling["factor"]),
                        float(scaling.get("beta_fast", 32)),
                        float(scaling.get("beta_slow", 1)),
                        float(scaling["original_max_position_embeddings"]))
    clamp = (config.get("mhc_h_res_clamp_min", -30),
             config.get("mhc_h_res_clamp_max", 30))
    share = config.get("share", {})
    held = config["n_routed_experts"]
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"], num_heads=heads,
        nope_head_dim=config["qk_nope_head_dim"],
        rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        latent_width=config["kv_lora_rank"],
        query_latent_width=config["q_lora_rank"],
        rope_theta=float(config["rope_theta"]), rope_scaling=rope_scaling,
        mscale_all_dim=float(mscale_all_dim),
        rope_interleave=config.get("rope_interleave", True),
        dense_width=config["intermediate_size"],
        num_experts=of, experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config.get("n_shared_experts") or 0,
        routed_scale=config.get("routed_scaling_factor") or 1.0,
        norm_topk_prob=config["norm_topk_prob"],
        streams=config["hc_mult"],
        sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"], res_clamp=clamp,
        mtp_modules=config.get("num_nextn_predict_layers", 0),
        mtp_loss_weight=config.get("mtp_loss_weight", 0.3),
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
