"""Required forward operations per sample (one sequence) of the
MiMo-V2-Flash symbol AS HELD HERE, from the configuration's keys alone:
two operations per multiply-add of every matrix product the mathematics
needs — the held heads' four projections (query/key heads of
``head_dim``, value heads of ``v_head_dim``), the causal scores and
their values over the triangle in full layers ((T+1)/2 keys a query on
average) and over the band in window layers (``min(i + 1, window)`` keys
for query i), the router at its full width (``share.experts_of``), the
held experts at the rows the share expects (tokens x experts-per-token x
held / routed-over: what uniform routing sends here; the rows really
received are ``moe_share_roofline_share``'s business), the held dense
columns, and the head over the held vocabulary. Norms, rotary
embedding, softmaxes, sinks, the compaction and the embedding lookup are
not matrix products and count nothing. Training is three times this;
recomputed operations never count — the flash kernel's backward
recomputes its scores, which is why the attention kernels count 3x
their forward and not 3.5x.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def _layers(cfg):
    n = cfg["num_hidden_layers"]
    return (cfg["hybrid_layer_pattern"][:n], cfg["moe_layer_freq"][:n])


def window_layers(cfg):
    return sum(1 for w in _layers(cfg)[0] if w)


def expert_layers(cfg):
    return sum(1 for e in _layers(cfg)[1] if e)


def _pair_flops(cfg, pairs, windowed):
    """Scores and values of ``pairs`` (query, key) pairs a head, all
    held query heads."""
    pre = "swa_" if windowed else ""
    heads = cfg[pre + "num_attention_heads"]
    return 2.0 * heads * pairs * (cfg[pre + "head_dim"]
                                  + cfg[pre + "v_head_dim"])


def attn_window_flops(cfg):
    """Forward operations of ONE window layer's attention kernel for one
    sequence: scores and values over the band."""
    t, w = _t(cfg), min(cfg["sliding_window"], _t(cfg))
    return _pair_flops(cfg, w * (w + 1) / 2.0 + (t - w) * w, True)


def attn_full_flops(cfg):
    """Forward operations of ONE full layer's attention kernel for one
    sequence: scores and values over the causal triangle."""
    t = _t(cfg)
    return _pair_flops(cfg, t * (t + 1) / 2.0, False)


def expected_share_rows(cfg):
    """Rows a layer's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    return (_t(cfg) * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / float(of))


def moe_share_flops(cfg, rows=None):
    """Forward operations of ONE expert layer for one sequence: the
    router over all its experts and ``rows`` rows (default: the expected)
    through a SwiGLU expert."""
    d = cfg["hidden_size"]
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    rows = expected_share_rows(cfg) if rows is None else rows
    return 2.0 * (_t(cfg) * d * of
                  + rows * 3 * d * cfg["moe_intermediate_size"])


def _projection_flops(cfg, windowed):
    pre = "swa_" if windowed else ""
    heads, kv = cfg[pre + "num_attention_heads"], cfg[pre + "num_key_value_heads"]
    dk, dv = cfg[pre + "head_dim"], cfg[pre + "v_head_dim"]
    columns = heads * dk + kv * dk + kv * dv + heads * dv
    return 2.0 * _t(cfg) * cfg["hidden_size"] * columns


def forward_flops_per_sample(cfg):
    d, t = cfg["hidden_size"], _t(cfg)
    dense_columns = cfg.get("share", {}).get(
        "dense_columns_held", cfg["intermediate_size"])
    total = 2.0 * t * d * cfg["vocab_size"]                    # head
    for windowed, experts in zip(*_layers(cfg)):
        total += _projection_flops(cfg, windowed)
        total += (attn_window_flops if windowed else attn_full_flops)(cfg)
        total += (moe_share_flops(cfg) if experts
                  else 2.0 * t * 3 * d * dense_columns)
    return total
