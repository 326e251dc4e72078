"""Required forward operations per sample (one sequence) of the
Olmo-Hybrid symbol AS HELD HERE, from the configuration's keys alone:
two operations per multiply-add of every matrix product the mathematics
needs. A linear-attention layer: its seven projections (``q``, ``k``,
``v``, the gate, ``o`` and the two a head) and the gated delta rule AS
THE RECURRENCE computes it (``core_flops``), not as any chunk form does:
a later kernel is read against the same work. A full-attention layer:
the four projections and the causal scores and values over the triangle
((T + 1) / 2 keys a query). Every layer: the dense SwiGLU's three
products. The head over the held vocabulary. The convolution's four
taps, unit norms, write strengths, decays, norms, softmaxes and the
embedding lookup are not matrix products and count nothing. Training is
three times this; recomputed operations never count (the linear layers'
core is computed again in the backward pass, the flash kernel recomputes
its scores).

``core_bytes`` is what the rule has to move whatever its form: ``q``,
``k``, ``v`` and the two scalars a head in, ``o`` out, once, in the
configuration's dtype.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3
LINEAR, FULL = "linear_attention", "full_attention"


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def layers(cfg, kind):
    """How many of the layers held are ``kind``."""
    return list(cfg["layer_types"]).count(kind)


def _linear(cfg):
    return (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def linear_projection_flops(cfg):
    """Forward operations of ONE linear-attention layer's projections:
    ``q`` and ``k`` (H K each), ``v``, the gate and ``o`` (H V each) and
    the two of H columns."""
    h, dk, dv = _linear(cfg)
    return 2.0 * _t(cfg) * cfg["hidden_size"] * (
        2 * h * dk + 3 * h * dv + 2 * h)


def core_flops(cfg):
    """Forward operations of ONE linear-attention layer's gated delta
    rule for one sequence, a token and head: the state's K x V entries
    decayed (1 each), read through the key, written by the outer product
    of the key and the correction, and read through the query (a
    multiply-add each): 7 K V."""
    h, dk, dv = _linear(cfg)
    return 7.0 * _t(cfg) * h * dk * dv


def core_bytes(cfg, itemsize=2):
    """Bytes ONE linear-attention layer's gated delta rule has to move
    forward for one sequence: ``q``, ``k``, ``v``, a log decay and a
    write strength a head in, ``o`` out."""
    h, dk, dv = _linear(cfg)
    return float(itemsize) * _t(cfg) * h * (2 * dk + 2 * dv + 2)


def attention_projection_flops(cfg):
    """Forward operations of ONE full-attention layer's four
    projections."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    return 2.0 * _t(cfg) * d * (2 * d + 2 * cfg["num_key_value_heads"] * hd)


def attention_kernel_flops(cfg):
    """Forward operations of ONE full-attention layer's scores and
    values over the causal triangle, every query head."""
    t = _t(cfg)
    return 2.0 * 2 * cfg["hidden_size"] * t * (t + 1) / 2.0


def mlp_flops(cfg):
    """Forward operations of ONE layer's dense SwiGLU."""
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def forward_flops_per_sample(cfg):
    return (2.0 * _t(cfg) * cfg["hidden_size"] * cfg["vocab_size"]  # head
            + layers(cfg, LINEAR) * (linear_projection_flops(cfg)
                                     + core_flops(cfg))
            + layers(cfg, FULL) * (attention_projection_flops(cfg)
                                   + attention_kernel_flops(cfg))
            + len(cfg["layer_types"]) * mlp_flops(cfg))
