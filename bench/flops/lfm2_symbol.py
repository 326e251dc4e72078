"""Required forward operations per sample (one sequence) of the LFM2
symbol AS HELD HERE, from the configuration's keys alone: two operations
per multiply-add of every matrix product the mathematics needs. A
``conv`` layer's operator: ``in_proj`` (hidden -> 3 x hidden) and
``out_proj``; the two gates and the taps are elementwise and count
nothing (``sconv_bytes`` says what they have to move). A
``full_attention`` layer's operator: the four projections (32 query
heads on 8 key/value heads of 64) and the causal scores and values over
the triangle ((T + 1) / 2 keys a query). The dense feed-forward of the
leading layers; in a sparse layer the router at its full width
(``share.experts_of``) and the held SwiGLU experts at the rows the share
expects (tokens x experts-per-token x held / routed-over). The head over
the held vocabulary (the embedding's own matrix: a lookup one way, a
product the other). Norms (the heads' too), RoPE, softmaxes, the
compaction and the embedding lookup are not matrix products and count
nothing. Training is three times this; recomputed operations never
count (``ShortConv`` recomputes its float32 tables in the backward pass,
the flash kernel its scores).
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3
CONV, FULL = "conv", "full_attention"


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def layers(cfg, kind):
    """How many layers' mixer is ``kind`` (``CONV`` or ``FULL``)."""
    return list(cfg["layer_types"]).count(kind)


def expert_layers(cfg):
    """How many of the layers have experts."""
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def head_dim(cfg):
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def sconv_projection_flops(cfg):
    """Forward operations of ONE conv layer's two projections."""
    d = cfg["hidden_size"]
    return 2.0 * _t(cfg) * d * (3 * d + d)


def sconv_bytes(cfg, itemsize=2, backward=False):
    """Bytes ONE ``ShortConv`` op has to move for one sequence, in the
    configuration's dtype: forward its input ``[T, 3 hidden]`` and its
    output ``[T, hidden]`` once; backward the input, the output's
    cotangent and the input's cotangent once. The taps are 3 x hidden
    numbers and count nothing."""
    d = cfg["hidden_size"]
    return float(itemsize) * _t(cfg) * d * ((3 + 1 + 3) if backward
                                            else (3 + 1))


def attention_projection_flops(cfg):
    """Forward operations of ONE attention layer's four projections."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2.0 * _t(cfg) * d * hd * (2 * heads + 2 * kv)


def attn64_kernel_flops(cfg):
    """Forward operations of ONE attention layer's scores and values
    over the causal triangle, every query head."""
    t = _t(cfg)
    return (2.0 * cfg["num_attention_heads"] * 2 * head_dim(cfg)
            * t * (t + 1) / 2.0)


def dense_mlp_flops(cfg):
    """Forward operations of ONE leading layer's dense SwiGLU."""
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expected_share_rows(cfg):
    """Rows a layer's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["num_experts"])
    return (_t(cfg) * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / float(of))


def moe_share_flops(cfg, rows=None):
    """Forward operations of ONE sparse layer for one sequence: the
    router over all its experts and ``rows`` rows (default: the
    expected) through a SwiGLU expert."""
    d = cfg["hidden_size"]
    of = cfg.get("share", {}).get("experts_of", cfg["num_experts"])
    rows = expected_share_rows(cfg) if rows is None else rows
    return 2.0 * (_t(cfg) * d * of
                  + rows * 3 * d * cfg["moe_intermediate_size"])


def forward_flops_per_sample(cfg):
    dense = cfg["num_dense_layers"]
    return (2.0 * _t(cfg) * cfg["hidden_size"] * cfg["vocab_size"]  # head
            + layers(cfg, CONV) * sconv_projection_flops(cfg)
            + layers(cfg, FULL) * (attention_projection_flops(cfg)
                                   + attn64_kernel_flops(cfg))
            + dense * dense_mlp_flops(cfg)
            + expert_layers(cfg) * moe_share_flops(cfg))
