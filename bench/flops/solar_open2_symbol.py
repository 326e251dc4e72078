"""Required forward operations per sample (one sequence) of the Solar
Open 2 symbol AS HELD HERE, from the configuration's keys alone: two
operations per multiply-add of every matrix product the mathematics
needs, at the heads, experts and vocabulary rows the configuration holds.
A KDA layer: its nine projections at the held heads (``q``, ``k``, ``v``
and ``o`` wide, the two low-rank pairs into the decay and the gate, whose
first halves are whole whatever is held, the write strength's) and the
delta rule's chunk form at the products it needs (``kda_chunk_flops``:
chunks of 64, the causal half of a chunk's two tables, the triangular
system by substitution, the products with the state). The gated
grouped-attention layer: its five projections (query, gate and output
over the held query heads, key and value over the held key/value heads)
and the causal scores and values over the triangle, 128 multiply-adds a
score and 128 a value. In EVERY layer the shared expert, the router at
its full width (``share.experts_of``) and the held experts at the rows
the share expects (tokens x experts-per-token x held / routed-over); the
head over the held vocabulary. The convolution's four taps, unit norms,
write strengths, decays and their exponentials, norms, softmaxes, both
sigmoid gates' products, the compaction and the embedding lookup are not
matrix products and count nothing. Training is three times this;
recomputed operations never count (the KDA core is computed again in the
backward pass, the flash kernel recomputes its scores).

``kda_core_flops`` and ``kda_core_bytes`` are what the rule needs
WHATEVER computes it, for ``solar2_kda_core_roofline_share``: the
recurrence's own 7 K V operations a token and head, and ``q``, ``k``,
``v``, the decay's K pre-activations and a write strength a head in,
``o`` out, once, in the configuration's dtype. ``gqa_kernel_flops`` and
``gqa_kernel_bytes`` are the same for ``solar2_gqa_roofline_share``: the
causal pairs' scores and values, and ``q``, ``k``, ``v`` in and the
output out, once.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3
CHUNK = 64


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def _kda(cfg):
    linear = cfg["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"]


def gqa_layers(cfg):
    """How many of the layers held are grouped-attention layers."""
    return len(cfg["gqa_layers"])


def kda_layers(cfg):
    """How many of the layers held are KDA layers."""
    return cfg["num_hidden_layers"] - gqa_layers(cfg)


def kda_projection_flops(cfg):
    """Forward operations of ONE KDA layer's nine projections at the H
    heads held: ``q``, ``k``, ``v``, ``o`` (hidden x H K each), the two
    low-rank pairs (hidden x rank whole, rank x H K; rank = ``head_dim``)
    and ``b`` (hidden x H)."""
    h, k = _kda(cfg)
    d = cfg["hidden_size"]
    return 2.0 * _t(cfg) * (4 * d * h * k + 2 * (d * k + k * h * k) + d * h)


def kda_chunk_flops(cfg, chunk=CHUNK):
    """Forward operations of ONE KDA layer's delta rule in its chunk form
    for one sequence, a token and head with K = V: the causal half of the
    two C x C tables (2 x C K), the substitution of C rows into [W | Y]
    (C (K + V)), ``M W`` and ``M Y`` over the causal half (C (K + V)) and
    three products with the state (6 K V)."""
    h, k = _kda(cfg)
    return float(_t(cfg)) * h * (2 * chunk * k + 2 * chunk * 2 * k
                                 + 6 * k * k)


def kda_core_flops(cfg):
    """Forward operations of ONE KDA layer's delta rule for one sequence
    as the RECURRENCE computes it, a token and held head: the state's K x
    V entries decayed (1 each), read through the key, written by the
    outer product of the key and the correction, and read through the
    query (a multiply-add each): 7 K V."""
    h, k = _kda(cfg)
    return 7.0 * _t(cfg) * h * k * k


def kda_core_bytes(cfg, itemsize=2):
    """Bytes ONE KDA layer's delta rule has to move forward for one
    sequence: ``q``, ``k``, ``v``, the decay's K pre-activations and a
    write strength a head in, ``o`` out."""
    h, k = _kda(cfg)
    return float(itemsize) * _t(cfg) * h * (5 * k + 1)


def gqa_projection_flops(cfg):
    """Forward operations of ONE grouped-attention layer's five
    projections: query, gate and output over the held query heads, key
    and value over the held key/value heads."""
    columns = (3 * cfg["num_attention_heads"]
               + 2 * cfg["num_key_value_heads"]) * cfg["head_dim"]
    return 2.0 * _t(cfg) * cfg["hidden_size"] * columns


def gqa_kernel_flops(cfg):
    """Forward operations of ONE grouped-attention layer's kernel for one
    sequence: a score and a value (``head_dim`` multiply-adds each) a
    (query, key) pair of the causal triangle and held query head."""
    t = _t(cfg)
    return (2.0 * cfg["num_attention_heads"] * (t * (t + 1) / 2.0)
            * 2 * cfg["head_dim"])


def gqa_kernel_bytes(cfg, itemsize=2):
    """Bytes ONE grouped-attention layer's kernel has to move forward for
    one sequence: the held heads' queries in and outputs out, the held
    key/value heads' keys and values in."""
    heads = 2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]
    return float(itemsize) * _t(cfg) * heads * cfg["head_dim"]


def shared_expert_flops(cfg):
    """Forward operations of ONE layer's shared expert."""
    width = (cfg.get("n_shared_experts") or 0) * cfg["moe_intermediate_size"]
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * width


def expected_share_rows(cfg):
    """Rows a layer's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    return (_t(cfg) * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / float(of))


def moe_share_flops(cfg, rows=None):
    """Forward operations of ONE layer's routed part for one sequence:
    the router over all its experts and ``rows`` rows (default: the
    expected) through a SwiGLU expert."""
    d = cfg["hidden_size"]
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    rows = expected_share_rows(cfg) if rows is None else rows
    return 2.0 * (_t(cfg) * d * of
                  + rows * 3 * d * cfg["moe_intermediate_size"])


def forward_flops_per_sample(cfg):
    d, t = cfg["hidden_size"], _t(cfg)
    return (2.0 * t * d * cfg["vocab_size"]                        # head
            + kda_layers(cfg) * (kda_projection_flops(cfg)
                                 + kda_chunk_flops(cfg))
            + gqa_layers(cfg) * (gqa_projection_flops(cfg)
                                 + gqa_kernel_flops(cfg))
            + cfg["num_hidden_layers"] * (shared_expert_flops(cfg)
                                          + moe_share_flops(cfg)))
