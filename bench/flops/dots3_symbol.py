"""Required forward operations per sample (one sequence) of the
dots3-note-prev symbol AS HELD HERE, from the configuration's keys alone:
two operations per multiply-add of every matrix product the mathematics
needs. A ``full_attention`` layer: the two down-projections, the held
heads' two up-projections, their gate and output projection; the indexer
whole (its three projections and its scores, ``index_n_heads x
index_head_dim`` a (query, key) pair over the causal triangle: it must
score every key to choose among them); the scores and values of the
SELECTED pairs alone (``sum_t min(t + 1, index_topk)`` a head,
``qk_nope_head_dim + qk_rope_head_dim`` a score and ``v_head_dim`` a
value). A ``sliding_attention`` layer: the same projections at the
``swa_`` sizes and the band ``min(i + 1, sliding_window_size)`` keys for
query i. The dense layer's held columns; in an expert layer the shared
expert, the router at its full width (``share.experts_of``) and the held
experts at the rows the share expects; the head over the held
vocabulary. Norms, the rescale, rotary embedding, softmaxes, the gates'
sigmoid, ReLU and the weighted sum over the indexer's heads, the top-k,
the compaction and the embedding lookup are not matrix products and count
nothing. Training is three times this for everything that is
differentiated; the indexer has no backward (its weights are not trained)
and counts once, which ``train_flops_per_sample`` says and
``TRAIN_MULTIPLIER`` cannot: the harness multiplies the forward by 3, so
``forward_flops_per_sample`` carries the indexer at a third of its
forward operations. Recomputed operations never count.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3
FULL, SLIDING = "full_attention", "sliding_attention"


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def _layer_types(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def full_layers(cfg):
    return sum(1 for kind in _layer_types(cfg) if kind == FULL)


def window_layers(cfg):
    return sum(1 for kind in _layer_types(cfg) if kind == SLIDING)


def expert_layers(cfg):
    """How many of the layers have experts."""
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return sum(1 for i in range(n)
               if i >= dense and i % cfg["moe_layer_freq"] == 0)


def selected_pairs(cfg):
    """(query, key) pairs one full layer keeps of one sequence:
    ``sum_t min(t + 1, index_topk)``."""
    t, k = _t(cfg), min(cfg["index_topk"], _t(cfg))
    return k * (k + 1) // 2 + (t - k) * k


def band_pairs(cfg):
    t, w = _t(cfg), min(cfg["sliding_window_size"], _t(cfg))
    return w * (w + 1) // 2 + (t - w) * w


def _pair_flops(cfg, pairs, pre):
    width = (cfg[pre + "qk_nope_head_dim"] + cfg[pre + "qk_rope_head_dim"]
             + cfg[pre + "v_head_dim"])
    return 2.0 * cfg[pre + "num_attention_heads"] * width * pairs


def attn_select_flops(cfg):
    """Forward operations of ONE full layer's attention over its selected
    pairs, every held head."""
    return _pair_flops(cfg, selected_pairs(cfg), "")


def attn_window_flops(cfg):
    """Forward operations of ONE window layer's attention over the band,
    every held head."""
    return _pair_flops(cfg, band_pairs(cfg), "swa_")


def index_projection_flops(cfg):
    """ONE full layer's indexer: its queries up from the query latent, its
    one key and its head weights from the block's input."""
    heads, width = cfg["index_n_heads"], cfg["index_head_dim"]
    return 2.0 * _t(cfg) * (cfg["q_lora_rank"] * heads * width
                            + cfg["hidden_size"] * (width + heads))


def index_score_flops(cfg):
    """ONE full layer's index scores over the causal triangle."""
    t = _t(cfg)
    return (2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]
            * (t * (t + 1) // 2))


def projection_flops(cfg, kind):
    """ONE layer's attention projections round the kernel: both
    down-projections, both up-projections, the gate a head and the output
    projection, at the heads held."""
    pre = "" if kind == FULL else "swa_"
    d, heads = cfg["hidden_size"], cfg[pre + "num_attention_heads"]
    nope, rope = cfg[pre + "qk_nope_head_dim"], cfg[pre + "qk_rope_head_dim"]
    dv = cfg[pre + "v_head_dim"]
    q_rank, kv_rank = cfg[pre + "q_lora_rank"], cfg[pre + "kv_lora_rank"]
    gate = heads if cfg.get(pre + "attention_gate_type") else 0
    return 2.0 * _t(cfg) * (
        d * q_rank + q_rank * heads * (nope + rope) + d * (kv_rank + rope)
        + kv_rank * heads * (nope + dv) + heads * dv * d + d * gate)


def shared_expert_flops(cfg):
    width = (cfg.get("n_shared_experts") or 0) * cfg["moe_intermediate_size"]
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * width


def expected_share_rows(cfg):
    """Rows a layer's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    return (_t(cfg) * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / float(of))


def moe_share_flops(cfg, rows=None):
    """ONE expert layer's routed part: the router over all its experts and
    ``rows`` rows (default: the expected) through a SwiGLU expert."""
    d = cfg["hidden_size"]
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    rows = expected_share_rows(cfg) if rows is None else rows
    return 2.0 * (_t(cfg) * d * of
                  + rows * 3 * d * cfg["moe_intermediate_size"])


def dense_flops(cfg):
    columns = cfg.get("share", {}).get("dense_columns_held",
                                       cfg["intermediate_size"])
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * columns


def head_flops(cfg):
    return 2.0 * _t(cfg) * cfg["hidden_size"] * cfg["vocab_size"]


def parts(cfg):
    """The forward operations of one sequence by part, the indexer at its
    full forward count."""
    full, window = full_layers(cfg), window_layers(cfg)
    experts = expert_layers(cfg)
    return {
        "full_projections": full * projection_flops(cfg, FULL),
        "index": full * (index_projection_flops(cfg)
                         + index_score_flops(cfg)),
        "select_pairs": full * attn_select_flops(cfg),
        "window_projections": window * projection_flops(cfg, SLIDING),
        "window_pairs": window * attn_window_flops(cfg),
        "dense": (cfg["num_hidden_layers"] - experts) * dense_flops(cfg),
        "experts": experts * (shared_expert_flops(cfg)
                              + moe_share_flops(cfg)),
        "head": head_flops(cfg),
    }


def true_forward_flops_per_sample(cfg):
    """What one forward pass needs (the issue's 3.52 TFLOP at the cell's
    sizes)."""
    return sum(parts(cfg).values())


def train_flops_per_sample(cfg):
    """What one training step needs: three times the differentiated
    parts, the indexer (forward only, no gradient) once."""
    p = parts(cfg)
    return TRAIN_MULTIPLIER * (sum(p.values()) - p["index"]) + p["index"]


def forward_flops_per_sample(cfg):
    """``train_flops_per_sample / TRAIN_MULTIPLIER``: the harness's
    training count is three times this, so the indexer, which has no
    backward, enters at a third."""
    return train_flops_per_sample(cfg) / TRAIN_MULTIPLIER
