"""OLMoE: a decoder-only LM whose every FFN is a dropless top-k
sparse-expert layer, as an ``mx.sym`` graph that ``Module.fit`` trains.

Muennighoff et al., "OLMoE: Open Mixture-of-Experts Language Models",
arXiv:2409.02060; the defaults are ``OLMoE-1B-7B-0125-Instruct``'s
``config.json`` (hidden 2048, 16 heads of 128, 16 layers, 64 experts of
width 1024 with 8 per token, RMSNorm eps 1e-5 also on the projected
queries and keys, RoPE theta 10000, vocabulary 50304, untied head).
Per layer:

    h = h + o_proj(Attention(RoPE(q_norm(q_proj(x))),
                             RoPE(k_norm(k_proj(x))), v_proj(x)))
        with x = attn_norm(h)
    h = h + TopKMoE(ffn_norm(h))

then ``final_norm`` and ``lm_head``. ``data`` holds token ids
``[batch, seq_len]`` and ``softmax_label`` the next token at each
position.

Outputs: (0) the loss, one value per sequence — that sequence's mean
next-token cross-entropy, from float32 logits — behind ``MakeLoss``, so
the step's gradient times the optimizer's ``rescale_grad = 1/batch`` is
the gradient of the batch's mean token loss, and ``mx.metric.Loss``
reports that mean while fetching ``batch`` floats; (1..num_layers) each
layer's per-expert row counts behind ``BlockGrad``. The logits are the
internal ``lm_head_f32_output`` (``lm_head_output`` in the model's own
dtype).

Departures from the published training job, shared with
``models/olmoe_reference.py``: the router's load-balancing and z losses
are not part of the objective, and the router runs in float32 whatever
``dtype`` is. ``dtype="bfloat16"`` makes every parameter bf16 (pure
bf16 weights, what one chip runs: ``MXTPU_AMP`` needs dp > 1).
"""
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import head_and_loss, linear


def get_symbol(vocab_size=50304, hidden_size=2048, num_layers=16,
               num_heads=16, num_experts=64, experts_per_token=8,
               expert_width=1024, seq_len=4096, rope_theta=10000.0,
               rms_eps=1e-5, dtype="float32", norm_topk_prob=False):
    def norm(x, name):
        return csym.RMSNorm(x, eps=rms_eps, name=name)

    def heads(x):  # [B*T, d] -> [B, T, d] for the ops that see positions
        return sym.Reshape(x, shape=(-1, seq_len, hidden_size))

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    # the residual stream is [tokens, hidden] throughout
    h = sym.Embedding(sym.Reshape(data, shape=(-1,)), input_dim=vocab_size,
                      output_dim=hidden_size, dtype=dtype, name="embed")
    counts = []
    for i in range(num_layers):
        p = "layer%d_" % i
        x = norm(h, p + "attn_norm")
        q = norm(linear(x, p + "q_proj", hidden_size), p + "q_norm")
        k = norm(linear(x, p + "k_proj", hidden_size), p + "k_norm")
        v = linear(x, p + "v_proj", hidden_size)
        q = csym.RoPE(heads(q), num_heads=num_heads, theta=rope_theta,
                      name=p + "q_rope")
        k = csym.RoPE(heads(k), num_heads=num_heads, theta=rope_theta,
                      name=p + "k_rope")
        attn = csym.Attention(q, k, heads(v), num_heads=num_heads,
                              causal=True, name=p + "attn")
        attn = sym.Reshape(attn, shape=(-1, hidden_size))
        h = h + linear(attn, p + "o_proj", hidden_size)
        moe = csym.TopKMoE(
            norm(h, p + "ffn_norm"), num_experts=num_experts,
            num_hidden=expert_width, top_k=experts_per_token,
            norm_topk_prob=norm_topk_prob, name=p + "moe")
        h = h + moe[0]
        counts.append(sym.BlockGrad(moe[1], name=p + "expert_count"))
    return head_and_loss(h, label, counts, vocab_size, seq_len, rms_eps)


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type`` olmoe),
    given as a dict. Keys that would change the mathematics and that
    this builder does not implement must have the values OLMoE ships."""
    shipped = {"attention_bias": False, "clip_qkv": None,
               "hidden_act": "silu", "rope_scaling": None,
               "tie_word_embeddings": False,
               "num_key_value_heads": config["num_attention_heads"]}
    for key, value in shipped.items():
        if config.get(key, value) != value:
            raise ValueError("olmoe.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["intermediate_size"],
        seq_len=seq_len or config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"], dtype=dtype,
        norm_topk_prob=config["norm_topk_prob"])
