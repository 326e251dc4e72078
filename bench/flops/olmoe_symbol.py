"""Required forward operations per sample (one sequence) of the OLMoE
symbol, from the configuration's published keys alone: two operations
per multiply-add of every matrix product the mathematics needs — the
four attention projections, the causal scores and their values at
(T+1)/2 keys a query on average, the router, the ``top_k`` experts a
token is sent to (gate, up and down projections), and the untied head.
Norms, rotary embedding, softmaxes, the sort and the embedding lookup
are not matrix products and count nothing. Training is three times this
(forward, and backward for data and for weights); recomputed operations
never count — the flash kernel's backward recomputes its scores, which
is why ``attn_kernel_flops`` counts 3x its forward and not 3.5x.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["kwargs"]["seq_len"])


def moe_flops(cfg):
    """Forward operations of ONE expert layer for one sequence: router
    and the ``num_experts_per_tok`` SwiGLU experts of every token."""
    d, _, t = _sizes(cfg)
    router = d * cfg["num_experts"]
    experts = cfg["num_experts_per_tok"] * 3 * d * cfg["intermediate_size"]
    return 2.0 * t * (router + experts)


def attn_kernel_flops(cfg):
    """Forward operations of ONE layer's attention kernel for one
    sequence: scores and values over the causal half, all heads."""
    d, _, t = _sizes(cfg)
    return 2.0 * t * 2 * d * (t + 1) / 2.0


def forward_flops_per_sample(cfg):
    d, layers, t = _sizes(cfg)
    projections = 2.0 * t * 4 * d * d
    head = 2.0 * t * d * cfg["vocab_size"]
    return layers * (projections + attn_kernel_flops(cfg)
                     + moe_flops(cfg)) + head
