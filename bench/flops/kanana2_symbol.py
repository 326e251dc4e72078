"""Required forward operations per sample (one sequence) of the
Kanana-2 symbol AS HELD HERE, from the configuration's keys alone: two
operations per multiply-add of every matrix product the mathematics
needs — the query projection, the down-projection to the latent and the
shared rotary key, the up-projection from the latent to every head's
keys and values, the causal scores and their values over the triangle
((T+1)/2 keys a query on average, ``qk_nope_head_dim +
qk_rope_head_dim`` a score and ``v_head_dim`` a value), the output
projection; the dense layer; in an expert layer the shared experts, the
router at its full width (``share.experts_of``) and the held experts at
the rows the share expects (tokens x experts-per-token x held /
routed-over: what uniform routing sends here; the rows really received
are ``moe_share_roofline_share``'s business); the head over the held
vocabulary. Norms, rotary embedding, softmaxes, the key's concatenation,
the compaction and the embedding lookup are not matrix products and
count nothing. Training is three times this; recomputed operations
never count — the flash kernel's backward recomputes its scores, which
is why ``mla_kernel_flops`` counts 3x its forward and not 3.5x. The
absorbed form (the up-projection folded into the query and the output)
is another count for another program: this one materialises keys and
values, as training does.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def expert_layers(cfg):
    """How many of the layers have experts."""
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return sum(1 for i in range(n)
               if i >= dense and i % cfg["moe_layer_freq"] == 0)


def mla_kernel_flops(cfg):
    """Forward operations of ONE layer's attention kernel for one
    sequence: scores and values over the causal triangle, every head."""
    t = _t(cfg)
    width = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
             + cfg["v_head_dim"])
    return 2.0 * cfg["num_attention_heads"] * width * t * (t + 1) / 2.0


def mla_projection_flops(cfg):
    """Forward operations of ONE layer's four projections round the
    kernel: query, down to the latent, up from it, output."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, latent = cfg["v_head_dim"], cfg["kv_lora_rank"]
    return 2.0 * _t(cfg) * (d * heads * (nope + rope) + d * (latent + rope)
                            + latent * heads * (nope + dv)
                            + heads * dv * d)


def shared_expert_flops(cfg):
    """Forward operations of ONE expert layer's shared experts."""
    width = (cfg.get("n_shared_experts") or 0) * cfg["moe_intermediate_size"]
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * width


def expected_share_rows(cfg):
    """Rows a layer's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    return (_t(cfg) * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / float(of))


def moe_share_flops(cfg, rows=None):
    """Forward operations of ONE expert layer's routed part for one
    sequence: the router over all its experts and ``rows`` rows
    (default: the expected) through a SwiGLU expert."""
    d = cfg["hidden_size"]
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    rows = expected_share_rows(cfg) if rows is None else rows
    return 2.0 * (_t(cfg) * d * of
                  + rows * 3 * d * cfg["moe_intermediate_size"])


def forward_flops_per_sample(cfg):
    d, t = cfg["hidden_size"], _t(cfg)
    layers, experts = cfg["num_hidden_layers"], expert_layers(cfg)
    return (2.0 * t * d * cfg["vocab_size"]                        # head
            + layers * (mla_projection_flops(cfg) + mla_kernel_flops(cfg))
            + (layers - experts) * 2.0 * t * 3 * d * cfg["intermediate_size"]
            + experts * (shared_expert_flops(cfg) + moe_share_flops(cfg)))
