"""Required forward operations per sample (one sequence) of the afmoe
(Trinity) symbol AS HELD HERE, from the configuration's keys alone: two
operations per multiply-add of every matrix product the mathematics
needs. An attention layer: its five projections (query, key, value, the
gate's and the output's; the gate's is as wide as the query's) and the
causal scores and their values, over the triangle in a
``full_attention`` layer ((T+1)/2 keys a query on average) and over the
band in a ``sliding_attention`` one (``min(i + 1, sliding_window)`` keys
for query i). The dense layers' SwiGLU; in an expert layer the shared
expert, the router at its full width (``share.experts_of``) and the held
experts at the rows the share expects (tokens x experts-per-token x held
/ routed-over: what uniform routing sends here; the rows really received
are ``trinity_held_rows_over_expected``'s business); the head over the
held vocabulary. Norms (the heads' too), the rotation, softmaxes, the
sigmoid gate's product, the muP multiplier, the compaction and the
embedding lookup are not matrix products and count nothing. Training is
three times this; recomputed operations never count — the flash kernel's
backward recomputes its scores, which is why the attention kernels count
3x their forward and not 3.5x.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3
SLIDING, FULL = "sliding_attention", "full_attention"


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def _kinds(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def window_layers(cfg):
    """How many of the layers held are sliding-window layers."""
    return sum(1 for kind in _kinds(cfg) if kind == SLIDING)


def full_layers(cfg):
    return sum(1 for kind in _kinds(cfg) if kind == FULL)


def expert_layers(cfg):
    """How many of the layers have experts."""
    return max(cfg["num_hidden_layers"] - cfg["num_dense_layers"], 0)


def _head_dim(cfg):
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def _pair_flops(cfg, pairs):
    """Scores and values of ``pairs`` (query, key) pairs a head, all
    query heads: a score and a value are ``head_dim`` multiply-adds
    each."""
    return 2.0 * cfg["num_attention_heads"] * pairs * 2 * _head_dim(cfg)


def window_pairs(cfg):
    """(query, key) pairs a head of one sequence inside the band."""
    t, w = _t(cfg), min(cfg["sliding_window"], _t(cfg))
    return w * (w + 1) / 2.0 + (t - w) * w


def attn_window_flops(cfg):
    """Forward operations of ONE sliding layer's attention kernel for one
    sequence: scores and values over the band."""
    return _pair_flops(cfg, window_pairs(cfg))


def attn_full_flops(cfg):
    """Forward operations of ONE full layer's attention kernel for one
    sequence: scores and values over the causal triangle."""
    t = _t(cfg)
    return _pair_flops(cfg, t * (t + 1) / 2.0)


def projection_flops(cfg):
    """Forward operations of ONE attention layer's five projections:
    query, gate and output over all query heads, key and value over the
    key/value heads."""
    d = _head_dim(cfg)
    columns = (3 * cfg["num_attention_heads"]
               + 2 * cfg["num_key_value_heads"]) * d
    return 2.0 * _t(cfg) * cfg["hidden_size"] * columns


def shared_expert_flops(cfg):
    """Forward operations of ONE expert layer's shared expert."""
    width = ((cfg.get("num_shared_experts") or 0)
             * cfg["moe_intermediate_size"])
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * width


def expected_share_rows(cfg):
    """Rows a layer's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["num_experts"])
    return (_t(cfg) * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / float(of))


def moe_share_flops(cfg, rows=None):
    """Forward operations of ONE expert layer's routed part for one
    sequence: the router over all its experts and ``rows`` rows
    (default: the expected) through a SwiGLU expert."""
    d = cfg["hidden_size"]
    of = cfg.get("share", {}).get("experts_of", cfg["num_experts"])
    rows = expected_share_rows(cfg) if rows is None else rows
    return 2.0 * (_t(cfg) * d * of
                  + rows * 3 * d * cfg["moe_intermediate_size"])


def forward_flops_per_sample(cfg):
    d, t = cfg["hidden_size"], _t(cfg)
    layers, experts = cfg["num_hidden_layers"], expert_layers(cfg)
    return (2.0 * t * d * cfg["vocab_size"]                        # head
            + layers * projection_flops(cfg)
            + window_layers(cfg) * attn_window_flops(cfg)
            + full_layers(cfg) * attn_full_flops(cfg)
            + (layers - experts) * 2.0 * t * 3 * d * cfg["intermediate_size"]
            + experts * (shared_expert_flops(cfg) + moe_share_flops(cfg)))
