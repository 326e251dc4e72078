"""Keye-VL-2.0-30B-A3B's language model (``model_type`` KeyeVL2; Kwai-Keye,
30B total, 3B active): a decoder-only LM of grouped-query attention that
reads only the keys an indexer picks, in EVERY layer, over softmax-routed
sparse experts, as an ``mx.sym`` graph that ``Module.fit`` trains — whole,
or as one chip's share of its layers.

The defaults are ``Kwai-Keye/Keye-VL-2.0-30B-A3B``'s ``config.json``
(hidden 2048; 48 layers, all alike; 32 query heads on 4 key/value heads
of 128; ``rope_theta`` 1e7; RMSNorm eps 1e-6; 128 routed experts of 768,
top-8 of a softmax, weights renormalised, no shared expert; ``sa_config``:
an indexer of 16 heads of 64 against one key a token that keeps the 2,048
best keys a query; vocabulary 151936, untied head). Per layer, ``x =
attn_norm(h)``:

    keep = KeyIndexer(x, x)                          # layer<i>_index
    q, k = q_norm(q_proj(x)), k_norm(k_proj(x))      # a head's own 128
    q, k = RoPE(q), RoPE(k)
    a = Attention(q, k, v_proj(x), keep=keep)        # layer<i>_attn
    h = h + o_proj(a)
    h = h + TopKMoE(ffn_norm(h))                     # layer<i>_moe

then ``final_norm`` and ``lm_head``. ``KeyIndexer`` is DeepSeek-V3.2-Exp's
indexer with its query taken from the block's normed input (the model has
no query latent): 16 heads of 64 against ONE LayerNormed 64-wide key a
token, both rotated over their whole width (rotate-half pairs), a weight
a head and token; it keeps the ``min(t + 1, 2048)`` best keys of query t,
and all 32 heads' softmax runs over those alone. The indexer has no
gradient and its weights get none. ``q_norm`` and ``k_norm`` are an
RMSNorm over each head's own ``head_dim`` columns, one gamma of
``head_dim`` shared by the heads, BEFORE the rotation (rotate-half pairs
over the whole head; ``mrope_section`` splits the head's 64 pairs among
three position ids that are equal on text tokens). ``data`` holds token
ids ``[batch, seq_len]`` and ``softmax_label`` the next token at each
position.

**One chip's share.** As ``models/afmoe.py``: ``vocab_size`` the rows
held, ``experts_held`` of the router's ``num_experts`` from
``expert_offset`` on, their rows compacted into ``share_rows_bound``.
Attention (all heads), the indexer (every member must choose the same
keys) and the router stay whole: every chip of the deployment computes
them alike, on its own sequences. Nothing stands in for the chips that
hold the other experts or for the exchange with them;
``models/keye_vl2_reference.py`` is given the same share.

Outputs: the loss per sequence behind ``MakeLoss``, each layer's row
counts over all of the router's experts, then each layer's selection
count a sequence (``layer<i>_keys_selected``).

**Initialisation the model states itself** (``sym.Variable(init=)``): the
embedding Normal(``embed_sigma``), by default ``STREAM_RMS`` = 4. The
other untied LM symbols' unit embedding keeps a token's own vector the
largest part of what the routers read, so that seeded weights route
near-uniformly, as a trained model's balanced routers do
(``models/mimo_v2.py``). Here every layer adds an attention output whose
rms is up to 1.2 at the first positions (a row that attends few keys
keeps a value's size) and a softmax router has no bias to hold it: at a
unit stream the chip read the last two layers' held rows at 0.56-1.80 of
uniform after forty steps and one expert at 14.8 times its share, at a
stream of 4 or 8 every layer at 0.96-1.03 (``PERF.md`` section 6, PR 75).
The first loss does not depend on it: the head reads the final norm's
output. Router, norm statistics (the heads' too), RoPE,
softmaxes and the loss are float32 whatever ``dtype`` is, the indexer's
ReLU, weights, sum over heads and compare too; its two products take
operands of ``dtype`` and accumulate in float32.

Departures from the published training job, shared with the reference: no
auxiliary loss, no loss of the indexer's own (its weights stand still),
no vision tower (its settings are not in the language model's keys); the
indexer's Hadamard rotation (applied to both sides: every product as it
was) and its FP8 cast (a deployment's precision) are left out.
"""
from .. import initializer as init
from .. import symbol as sym
from ..contrib import symbol as csym
from .lm_blocks import head_and_loss, linear

# rms the embedding starts the stream at (the docstring says why)
STREAM_RMS = 4.0


def get_symbol(vocab_size=151936, hidden_size=2048, num_layers=48,
               num_heads=32, num_kv_heads=4, head_dim=128, rope_theta=1e7,
               index_heads=16, index_head_dim=64, index_topk=2048,
               num_experts=128, experts_held=0, expert_offset=0,
               share_rows_bound=0, experts_per_token=8, expert_width=768,
               norm_topk_prob=True, seq_len=8192, rms_eps=1e-6,
               dtype="float32", embed_sigma=STREAM_RMS):
    """``num_layers`` layers, all alike: selected attention, then
    experts."""
    q_width, kv_width = num_heads * head_dim, num_kv_heads * head_dim

    def norm(x, name):
        return csym.RMSNorm(x, eps=rms_eps, name=name)

    def positions(x, width):  # [B*T, w] -> [B, T, w]
        return sym.Reshape(x, shape=(-1, seq_len, width))

    selected = []

    def attention(x, p):
        def rotated(name, heads):  # per-head norm, then the rotation
            y = norm(sym.Reshape(
                linear(x, p + name + "_proj", heads * head_dim),
                shape=(-1, head_dim)), p + name + "_norm")
            return csym.RoPE(positions(y, heads * head_dim),
                             num_heads=heads, theta=rope_theta,
                             name=p + name + "_rope")

        x3 = positions(x, hidden_size)
        index = csym.KeyIndexer(
            x3, x3, num_heads=index_heads, head_dim=index_head_dim,
            rope_dim=index_head_dim, topk=index_topk, theta=rope_theta,
            name=p + "index")
        selected.append(sym.BlockGrad(index[1], name=p + "keys_selected"))
        attn = csym.Attention(
            rotated("q", num_heads), rotated("k", num_kv_heads),
            positions(linear(x, p + "v_proj", kv_width), kv_width),
            with_keep=True, keep=index[0], num_heads=num_heads,
            num_kv_heads=num_kv_heads, causal=True, name=p + "attn")
        return linear(sym.Reshape(attn, shape=(-1, q_width)), p + "o_proj",
                      hidden_size)

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
    for i in range(num_layers):
        p = "layer%d_" % i
        h = h + attention(norm(h, p + "attn_norm"), p)
        moe = csym.TopKMoE(
            norm(h, p + "ffn_norm"), num_experts=num_experts,
            num_hidden=expert_width, top_k=experts_per_token,
            norm_topk_prob=norm_topk_prob, experts_held=experts_held,
            expert_offset=expert_offset, share_rows_bound=share_rows_bound,
            name=p + "moe")
        h = h + moe[0]
        counts.append(sym.BlockGrad(moe[1], name=p + "expert_count"))
    return head_and_loss(h, label, counts + selected, vocab_size, seq_len,
                         rms_eps)


# keys whose value changes the mathematics and that this builder takes in
# one form only; ``ASSUMED_UNREAD`` are the keys nothing here reads (the
# configuration file lists them under ``assumed``)
_ONLY = {"attention_bias": False, "use_sliding_window": False,
         "mlp_only_layers": [], "decoder_sparse_step": 1,
         "hidden_act": "silu", "tie_word_embeddings": False,
         "model_type": "KeyeVL2"}
ASSUMED_UNREAD = ("intermediate_size", "max_window_layers",
                  "max_position_embeddings", "sliding_window")


def from_config(config, seq_len=None, dtype="float32"):
    """The symbol of a published ``config.json`` (``model_type`` KeyeVL2),
    given as a dict. A key that would change the mathematics and that
    this builder does not implement raises: a bias on the attention
    projections, a sliding window, a layer without experts
    (``mlp_only_layers`` non-empty, ``decoder_sparse_step`` other than
    1), an activation other than silu, tied embeddings, a ``rope_scaling``
    whose type is not ``default`` or whose ``mrope_section`` does not
    split the whole head's pairs, an indexer with more than one key a
    token (``sa_config.indexer_num_kv_heads``), ``num_local_experts``
    that differs from ``num_experts``. ``q_chunk_size`` /
    ``kv_chunk_size`` are the blocks the published implementation
    evaluates in and are read by nothing; ``ASSUMED_UNREAD`` lists the
    other keys nothing reads (``intermediate_size`` is the width of a
    dense feed-forward no layer has; ``max_position_embeddings`` is the
    sequence length only where the caller gives none).

    A share of the model is the same dict with the counts held in place
    of the published ones (``vocab_size``, ``num_experts``,
    ``num_local_experts``) and a group ``share`` beside them, as
    ``afmoe.from_config`` reads it."""
    for key, value in _ONLY.items():
        if config.get(key, value) != value:
            raise ValueError("keye_vl2.from_config: %s=%r is not supported "
                             "(only %r)" % (key, config[key], value))
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    scaling = config.get("rope_scaling") or {}
    kinds = {scaling.get(key, "default") for key in ("rope_type", "type")}
    sections = scaling.get("mrope_section")
    if kinds != {"default"} or (sections is not None
                                and 2 * sum(sections) != head_dim):
        raise ValueError(
            "keye_vl2.from_config: rope_scaling=%r: only the default type "
            "with an mrope_section over the whole head's %d pairs is built"
            % (config["rope_scaling"], head_dim // 2))
    sa = config["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError(
            "keye_vl2.from_config: sa_config.indexer_num_kv_heads=%r: only "
            "one key a token is built" % (sa["indexer_num_kv_heads"],))
    held = config["num_experts"]
    if config.get("num_local_experts", held) != held:
        raise ValueError(
            "keye_vl2.from_config: num_local_experts=%r differs from "
            "num_experts=%r" % (config["num_local_experts"], held))
    share = config.get("share", {})
    of = share.get("experts_of", held)
    return get_symbol(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"], num_heads=heads,
        num_kv_heads=config["num_key_value_heads"], head_dim=head_dim,
        rope_theta=float(config["rope_theta"]),
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        num_experts=of, experts_held=0 if held == of else held,
        expert_offset=share.get("expert_offset", 0),
        share_rows_bound=share.get("share_rows_bound", 0),
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        seq_len=seq_len or config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"], dtype=dtype)
