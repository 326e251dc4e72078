"""Required forward operations per sample (one sequence) of the Ouro
symbol AS HELD HERE, from the configuration's keys alone: two operations
per multiply-add of every matrix product the mathematics needs. The
stack runs ``total_ut_steps`` times over one set of weights, so a layer
counts once A VISIT, ``total_ut_steps x num_hidden_layers`` visits a
sequence (a count by parameters would read a quarter of the work): its
four attention projections, the causal scores and values over the
triangle ((T + 1) / 2 keys a query) and the dense SwiGLU's three
products. After every pass the head over the held vocabulary and the
exit gate's ``hidden_size`` multiply-adds a token. Norms, the rotation,
softmaxes, the exit distribution and the embedding lookup are not matrix
products and count nothing. Training is three times this; recomputed
operations never count (the flash kernel recomputes its scores).

``attention_bytes`` is what a visit's attention has to move whatever
computes it: ``q``, ``k`` and ``v`` in, the output out, once, in the
configuration's dtype.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def _heads(cfg):
    heads = cfg["num_attention_heads"]
    return (heads, cfg.get("num_key_value_heads") or heads,
            cfg.get("head_dim") or cfg["hidden_size"] // heads)


def passes(cfg):
    return cfg["total_ut_steps"]


def visits(cfg):
    """Layer visits a sequence: every held layer once a pass."""
    return passes(cfg) * cfg["num_hidden_layers"]


def projection_flops(cfg):
    """Forward operations of ONE visit's four attention projections."""
    heads, kv, d = _heads(cfg)
    return 2.0 * _t(cfg) * cfg["hidden_size"] * (2 * heads * d + 2 * kv * d)


def attention_flops(cfg):
    """Forward operations of ONE visit's scores and values over the
    causal triangle, every query head."""
    heads, _, d = _heads(cfg)
    t = _t(cfg)
    return 2.0 * 2 * heads * d * t * (t + 1) / 2.0


def attention_bytes(cfg, itemsize=2):
    """Bytes ONE visit's attention has to move forward: ``q`` and the
    output at every query head, ``k`` and ``v`` at every key/value head."""
    heads, kv, d = _heads(cfg)
    return float(itemsize) * _t(cfg) * d * (2 * heads + 2 * kv)


def mlp_flops(cfg):
    """Forward operations of ONE visit's dense SwiGLU."""
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def exit_flops(cfg):
    """Forward operations after ONE pass: the head over the held
    vocabulary and the gate's one output a token."""
    return 2.0 * _t(cfg) * cfg["hidden_size"] * (cfg["vocab_size"] + 1)


def forward_flops_per_sample(cfg):
    return (visits(cfg) * (projection_flops(cfg) + attention_flops(cfg)
                           + mlp_flops(cfg))
            + passes(cfg) * exit_flops(cfg))
