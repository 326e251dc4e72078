"""Required forward operations per sample (one sequence) of the
Nemotron-H symbol AS HELD HERE, from the configuration's keys alone: two
operations per multiply-add of every matrix product the mathematics
needs. A Mamba-2 block: ``in_proj`` and ``out_proj``, and the
state-space scan in its chunked (SSD) form at the published
``chunk_size`` (``scan_flops``). An attention block: the four
projections and the causal scores and values over the triangle
((T + 1) / 2 keys a query). An expert block: the shared expert, the
router at its full width (``share.experts_of``) and the held experts at
the rows the share expects (tokens x experts-per-token x held /
routed-over), each un-gated expert two products where a SwiGLU one is
three. The head over the held vocabulary. The convolution's four taps,
step sizes, decays, the recurrence over chunks, norms, softmaxes, the
compaction and the embedding lookup are not matrix products and count
nothing. Training is three times this; recomputed operations never
count (the state-space core recomputes its elementwise work in the
backward pass, the flash kernel its scores).

``scan_bytes`` is what the scan has to move whatever its form: its
operands in and its result out once, in the configuration's dtype.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def blocks(cfg, kind):
    """How many blocks of the pattern are ``kind`` (M, E or *)."""
    return cfg["hybrid_override_pattern"].count(kind)


def _mamba(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return h, p, cfg["n_groups"], cfg["ssm_state_size"]


def mamba_projection_flops(cfg):
    """Forward operations of ONE Mamba-2 block's two projections."""
    h, p, g, n = _mamba(cfg)
    width = 2 * h * p + 2 * g * n + h
    return 2.0 * _t(cfg) * cfg["hidden_size"] * (width + h * p)


def scan_flops(cfg):
    """Forward operations of ONE Mamba-2 block's scan for one sequence,
    chunks of Q tokens: ``C B^T`` a group over the causal triangle of a
    chunk ((Q + 1) / 2 tokens a token, N a score), the masked product
    against ``dt x`` over the same triangle (P a head), a chunk's end
    state (H P N a token) and the carried state read through ``C`` (H P
    N a token)."""
    h, p, g, n = _mamba(cfg)
    q = cfg["chunk_size"]
    triangle = (q + 1) / 2.0
    return 2.0 * _t(cfg) * (triangle * (g * n + h * p) + 2 * h * p * n)


def scan_bytes(cfg, itemsize=2):
    """Bytes ONE Mamba-2 block's scan has to move forward for one
    sequence: ``x``, ``B``, ``C`` and a step size a head in, ``y`` out."""
    h, p, g, n = _mamba(cfg)
    return float(itemsize) * _t(cfg) * (2 * h * p + 2 * g * n + h)


def attention_projection_flops(cfg):
    """Forward operations of ONE attention block's four projections."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2.0 * _t(cfg) * d * hd * (2 * heads + 2 * kv)


def attention_kernel_flops(cfg):
    """Forward operations of ONE attention block's scores and values
    over the causal triangle, every query head."""
    t = _t(cfg)
    return (2.0 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
            * t * (t + 1) / 2.0)


def shared_expert_flops(cfg):
    """Forward operations of ONE expert block's shared expert."""
    if not cfg.get("n_shared_experts"):
        return 0.0
    return (2.0 * _t(cfg) * 2 * cfg["hidden_size"]
            * cfg["moe_shared_expert_intermediate_size"])


def expected_share_rows(cfg):
    """Rows a block's held experts receive of one sequence under uniform
    routing."""
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    return (_t(cfg) * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / float(of))


def moe_share_flops(cfg, rows=None):
    """Forward operations of ONE expert block's routed part for one
    sequence: the router over all its experts and ``rows`` rows
    (default: the expected) through an un-gated expert."""
    d = cfg["hidden_size"]
    of = cfg.get("share", {}).get("experts_of", cfg["n_routed_experts"])
    rows = expected_share_rows(cfg) if rows is None else rows
    return 2.0 * (_t(cfg) * d * of
                  + rows * 2 * d * cfg["moe_intermediate_size"])


def forward_flops_per_sample(cfg):
    return (2.0 * _t(cfg) * cfg["hidden_size"] * cfg["vocab_size"]  # head
            + blocks(cfg, "M") * (mamba_projection_flops(cfg)
                                  + scan_flops(cfg))
            + blocks(cfg, "*") * (attention_projection_flops(cfg)
                                  + attention_kernel_flops(cfg))
            + blocks(cfg, "E") * (shared_expert_flops(cfg)
                                  + moe_share_flops(cfg)))
