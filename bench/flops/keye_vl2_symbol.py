"""Required forward operations per sample (one sequence) of the
Keye-VL-2.0 language-model symbol AS HELD HERE, from the configuration's
keys alone: two operations per multiply-add of every matrix product the
mathematics needs. Every layer: the four attention projections (query
and output over the query heads, key and value over the key/value
heads); the indexer whole (its three projections and its scores,
``indexer_num_heads x indexer_head_dim`` a (query, key) pair over the
causal triangle: it must score every key to choose among them); the
scores and values of the KEPT pairs alone (``sum_t min(t + 1, topk)`` a
query head, ``head_dim`` a score and ``head_dim`` a value); the router at
its full width (``share.experts_of``) and the held experts at the rows
the share expects (tokens x experts-per-token x held / routed-over: what
uniform routing sends here); then the head over the held vocabulary.
Norms (the heads' too), the rotations, softmaxes, the indexer's ReLU and
weighted sum over its heads, the top-k, the compaction and the embedding
lookup are not matrix products and count nothing. Training is three times
this for everything that is differentiated; the indexer has no backward
(its weights are not trained) and counts once, which
``train_flops_per_sample`` says and ``TRAIN_MULTIPLIER`` cannot: the
harness multiplies the forward by 3, so ``forward_flops_per_sample``
carries the indexer at a third of its forward operations. Recomputed
operations never count (the flash pair's backward recomputes its scores).
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def _head_dim(cfg):
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def layers(cfg):
    """Every layer has the indexer, the selected attention and experts."""
    return cfg["num_hidden_layers"]


def selected_pairs(cfg):
    """(query, key) pairs one layer keeps of one sequence, a head:
    ``sum_t min(t + 1, topk)``."""
    t, k = _t(cfg), min(cfg["sa_config"]["topk"], _t(cfg))
    return k * (k + 1) // 2 + (t - k) * k


def causal_pairs(cfg):
    t = _t(cfg)
    return t * (t + 1) // 2


def select_flops(cfg):
    """Forward operations of ONE layer's attention over its kept pairs,
    every query head: a score and a value are ``head_dim`` multiply-adds
    each."""
    return (2.0 * cfg["num_attention_heads"] * 2 * _head_dim(cfg)
            * selected_pairs(cfg))


def select_bytes(cfg, itemsize=2):
    """Bytes ONE layer's selected pair has to move over a step, forward
    and backward together: q, o, dO and dq over the query heads, k, v, dk
    and dv over the key/value heads (``itemsize`` each: bf16), and the
    keep-mask's causal half (int8) once a key/value head's group and
    pass."""
    t, d = _t(cfg), _head_dim(cfg)
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (itemsize * t * d * (4 * heads + 4 * groups)
            + 2 * groups * causal_pairs(cfg))


def index_projection_flops(cfg):
    """ONE layer's indexer: its queries, its one key and its head weights
    from the block's normed input."""
    sa = cfg["sa_config"]
    heads, width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return 2.0 * _t(cfg) * cfg["hidden_size"] * (heads * width + width
                                                 + heads)


def index_score_flops(cfg):
    """ONE layer's index scores over the causal triangle."""
    sa = cfg["sa_config"]
    return (2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
            * causal_pairs(cfg))


def index_flops(cfg):
    """ONE layer's indexer, projections and scores (forward; it has no
    backward)."""
    return index_projection_flops(cfg) + index_score_flops(cfg)


def projection_flops(cfg):
    """ONE layer's four attention projections."""
    columns = (2 * cfg["num_attention_heads"]
               + 2 * cfg["num_key_value_heads"]) * _head_dim(cfg)
    return 2.0 * _t(cfg) * cfg["hidden_size"] * columns


def expected_share_rows(cfg):
    """Rows a layer's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["num_experts"])
    return (_t(cfg) * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / float(of))


def moe_share_flops(cfg, rows=None):
    """ONE layer's routed part: the router over all its experts and
    ``rows`` rows (default: the expected) through a SwiGLU expert."""
    d = cfg["hidden_size"]
    of = cfg.get("share", {}).get("experts_of", cfg["num_experts"])
    rows = expected_share_rows(cfg) if rows is None else rows
    return 2.0 * (_t(cfg) * d * of
                  + rows * 3 * d * cfg["moe_intermediate_size"])


def head_flops(cfg):
    return 2.0 * _t(cfg) * cfg["hidden_size"] * cfg["vocab_size"]


def parts(cfg):
    """The forward operations of one sequence by part, the indexer at its
    full forward count."""
    n = layers(cfg)
    return {"projections": n * projection_flops(cfg),
            "index": n * index_flops(cfg),
            "select_pairs": n * select_flops(cfg),
            "experts": n * moe_share_flops(cfg),
            "head": head_flops(cfg)}


def true_forward_flops_per_sample(cfg):
    """What one forward pass needs."""
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
