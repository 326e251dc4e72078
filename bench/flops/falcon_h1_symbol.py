"""Required forward operations per sample (one sequence) of the Falcon-H1
symbol AS HELD HERE, from the configuration's keys alone: two operations
per multiply-add of every matrix product the mathematics needs. Every
layer: the Mamba-2 mixer's ``in_proj`` and ``out_proj`` over the heads
and the group held, and its state-space scan in the chunked (SSD) form at
the published ``mamba_chunk_size`` (``scan_flops``); the attention
mixer's four projections over the heads held and the causal scores and
values over the triangle ((T + 1) / 2 keys a query;
``attn_kernel_flops``); the dense SwiGLU's three products over the
columns held (``share.dense_columns_held``). The head over the held
vocabulary. The fourteen multipliers, the convolution's four taps, step
sizes, decays, the recurrence over chunks, RoPE, norms, softmaxes and the
embedding lookup are not matrix products and count nothing. What the
other chip of the tensor-parallel pair computes is not counted: these are
this chip's operations. Training is three times this; recomputed
operations never count (the flash kernel recomputes its scores, the
taps' backward its sum).

``scan_bytes`` is what the scan has to move whatever its form: its
operands in and its result out once, in the configuration's dtype.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def layers(cfg):
    return cfg["num_hidden_layers"]


def _mamba(cfg):
    return (cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_n_groups"], cfg["mamba_d_state"])


def mamba_projection_flops(cfg):
    """Forward operations of ONE layer's ``in_proj`` and ``out_proj``."""
    h, p, g, n = _mamba(cfg)
    width = 2 * h * p + 2 * g * n + h
    return 2.0 * _t(cfg) * cfg["hidden_size"] * (width + h * p)


def scan_flops(cfg):
    """Forward operations of ONE layer's scan for one sequence, chunks of
    Q tokens: ``C B^T`` a group over the causal triangle of a chunk ((Q +
    1) / 2 tokens a token, N a score), the masked product against ``dt
    x`` over the same triangle (P a head), a chunk's end state (H P N a
    token) and the carried state read through ``C`` (H P N a token)."""
    h, p, g, n = _mamba(cfg)
    triangle = (cfg["mamba_chunk_size"] + 1) / 2.0
    return 2.0 * _t(cfg) * (triangle * (g * n + h * p) + 2 * h * p * n)


def scan_bytes(cfg, itemsize=2):
    """Bytes ONE layer's scan has to move forward for one sequence:
    ``x``, ``B``, ``C`` and a step size a head in, ``y`` out."""
    h, p, g, n = _mamba(cfg)
    return float(itemsize) * _t(cfg) * (2 * h * p + 2 * g * n + h)


def attention_projection_flops(cfg):
    """Forward operations of ONE layer's ``q``, ``k``, ``v`` and ``o``
    projections."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (2.0 * _t(cfg) * cfg["hidden_size"] * cfg["head_dim"]
            * (2 * heads + 2 * kv))


def attn_kernel_flops(cfg):
    """Forward operations of ONE layer's scores and values over the
    causal triangle, every query head held."""
    t = _t(cfg)
    return (2.0 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
            * t * (t + 1) / 2.0)


def mlp_flops(cfg):
    """Forward operations of ONE layer's dense SwiGLU over the columns
    held."""
    width = cfg.get("share", {}).get("dense_columns_held",
                                     cfg["intermediate_size"])
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * width


def head_flops(cfg):
    return 2.0 * _t(cfg) * cfg["hidden_size"] * cfg["vocab_size"]


def forward_flops_per_sample(cfg):
    return head_flops(cfg) + layers(cfg) * (
        mamba_projection_flops(cfg) + scan_flops(cfg)
        + attention_projection_flops(cfg) + attn_kernel_flops(cfg)
        + mlp_flops(cfg))
