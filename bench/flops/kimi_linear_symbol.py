"""Required forward operations per sample (one sequence) of the Kimi
Linear symbol AS HELD HERE, from the configuration's keys alone: two
operations per multiply-add of every matrix product the mathematics
needs. A KDA layer: its nine projections (``q``, ``k``, ``v`` and ``o``
wide, the two low-rank pairs into the decay and the gate, the write
strength's) and the delta rule's chunk form at the products it needs
(``kda_chunk_flops``: chunks of 64, the causal half of a chunk's two
tables, the triangular system by substitution, the products with the
state). A latent-attention layer: ``flops/kanana2_symbol``'s count under
this family's keys (query, down to the latent, up from it, output; the
causal scores and values over the triangle, 192 a score and 128 a
value). The dense layer's SwiGLU; in an expert layer the shared expert,
the router at its full width (``share.experts_of``) and the held experts
at the rows the share expects (tokens x experts-per-token x held /
routed-over); the head over the held vocabulary. The convolution's four
taps, unit norms, write strengths, decays and their exponentials, norms,
softmaxes, the compaction and the embedding lookup are not matrix
products and count nothing. Training is three times this; recomputed
operations never count (the KDA core is computed again in the backward
pass, the flash kernel recomputes its scores).

``kda_core_flops`` and ``kda_core_bytes`` are what the rule needs
WHATEVER computes it, for the roofline reader (``kda_core_roofline_share``):
the recurrence's own 7 K V operations a token and head, and ``q``,
``k``, ``v``, the decay's K pre-activations and a write strength a head
in, ``o`` out, once, in the configuration's dtype. A later kernel is read
against the same work.
"""
from __future__ import annotations

import lib

_KANANA = lib.load_module("flops", "kanana2_symbol")
TRAIN_MULTIPLIER = 3
CHUNK = 64


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def _kda(cfg):
    linear = cfg["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"]


def kda_layers(cfg):
    """How many of the layers held are KDA layers."""
    return len(cfg["linear_attn_config"]["kda_layers"])


def mla_layers(cfg):
    return len(cfg["linear_attn_config"]["full_attn_layers"])


def expert_layers(cfg):
    """How many of the layers have experts."""
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return sum(1 for i in range(n)
               if i >= dense and i % cfg["moe_layer_freq"] == 0)


def kda_projection_flops(cfg):
    """Forward operations of ONE KDA layer's nine projections: ``q``,
    ``k``, ``v``, ``o`` (hidden x H K each), the two low-rank pairs
    (hidden x rank, rank x H K; rank = ``head_dim``) and ``b`` (hidden x
    H)."""
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
    as the RECURRENCE computes it, a token and head: the state's K x V
    entries decayed (1 each), read through the key, written by the outer
    product of the key and the correction, and read through the query (a
    multiply-add each): 7 K V."""
    h, k = _kda(cfg)
    return 7.0 * _t(cfg) * h * k * k


def kda_core_bytes(cfg, itemsize=2):
    """Bytes ONE KDA layer's delta rule has to move forward for one
    sequence: ``q``, ``k``, ``v``, the decay's K pre-activations and a
    write strength a head in, ``o`` out."""
    h, k = _kda(cfg)
    return float(itemsize) * _t(cfg) * h * (5 * k + 1)


# the latent layer is Kanana's under the same keys: scores and values over
# the causal triangle, and the four projections round the kernel
mla_kernel_flops = _KANANA.mla_kernel_flops
mla_projection_flops = _KANANA.mla_projection_flops


def shared_expert_flops(cfg):
    """Forward operations of ONE expert layer's shared expert."""
    width = ((cfg.get("num_shared_experts") or 0)
             * cfg["moe_intermediate_size"])
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * width


def expected_share_rows(cfg):
    """Rows a layer's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["num_experts"])
    return (_t(cfg) * cfg["num_experts_per_token"]
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
            + kda_layers(cfg) * (kda_projection_flops(cfg)
                                 + kda_chunk_flops(cfg))
            + mla_layers(cfg) * (mla_projection_flops(cfg)
                                 + mla_kernel_flops(cfg))
            + (layers - experts) * 2.0 * t * 3 * d * cfg["intermediate_size"]
            + experts * (shared_expert_flops(cfg) + moe_share_flops(cfg)))
