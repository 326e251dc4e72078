"""Required forward operations per sample (one sequence) of the
Phi-4-mini-flash (SambaY) symbol AS HELD HERE, from the configuration's
keys alone: two operations per multiply-add of every matrix product the
mathematics needs. A Mamba-1 layer: ``in_proj``, ``x_proj``, ``dt_proj``
and ``out_proj`` (``mamba_projection_flops``); its selective scan is no
matrix product (a decay a channel and a state index) and counts nothing
here. A differential-attention layer: its projections (four, or the
query's and the output's alone in a cross layer) and the scores and
values of its two maps a pair against the pair's value of width 128, over
the causal triangle or the window's band (``diff_attn_flops``). A GMU
layer: its two projections. Every layer: the dense SwiGLU's three
products. The head over the held vocabulary. The convolution's taps,
step sizes, decays, the recurrence, gates, lambda, norms, softmaxes and
the embedding lookup are not matrix products and count nothing. Training
is three times this; recomputed operations never count (the flash kernel
recomputes its scores, the scan's backward its states).

``sscan_flops`` and ``sscan_bytes`` are the selective scan's own: the
elementwise recurrence's operations (a decay's product and exponential, the
input's two products, the update's multiply-add and the read's a state
entry and token) and what the scan has to move whatever its form, ``x``,
``dt``, ``B`` and ``C`` in and ``m`` out once. ``bench/peaks.json`` has
no vector peak (its ``bf16_flops`` is the matrix unit's), so the scan's
least time is its bytes over the HBM peak: ``sscan_roofline_share`` reads
against that bound alone and says so.
"""
from __future__ import annotations

TRAIN_MULTIPLIER = 3
KINDS = ("mamba", "window", "memory", "full", "gmu", "cross")


def _t(cfg):
    return cfg["kwargs"]["seq_len"]


def _mamba(cfg):
    """(channels, state, dt_rank) of a Mamba-1 mixer."""
    sizes = cfg.get("assumed_sizes", {})
    d_in = sizes.get("expand", 2) * cfg["hidden_size"]
    return (d_in, sizes.get("state_size", 16),
            sizes.get("dt_rank", -(-cfg["hidden_size"] // 16)))


def kind_of(cfg, number):
    """The mixer of published layer ``number``."""
    half = cfg.get("published", {}).get(
        "num_hidden_layers", cfg["num_hidden_layers"]) // 2
    if number < half:
        return "mamba" if number % 2 == 0 else "window"
    if number in (half, half + 1):
        return "memory" if number == half else "full"
    return "gmu" if number % 2 == 0 else "cross"


def layers(cfg, *kinds):
    """How many of the layers held are one of ``kinds`` (all held layers
    where none is named)."""
    held = cfg.get("layers_held")
    held = range(cfg["num_hidden_layers"]) if held is None else held
    if not kinds:
        return len(held)
    return sum(kind_of(cfg, number) in kinds for number in held)


def mamba_layers(cfg):
    return layers(cfg, "mamba", "memory")


def diff_layers(cfg):
    return layers(cfg, "window", "full", "cross")


def mamba_projection_flops(cfg):
    """Forward operations of ONE Mamba-1 layer's four projections."""
    d_in, n, rank = _mamba(cfg)
    return 2.0 * _t(cfg) * (cfg["hidden_size"] * 3 * d_in
                            + d_in * (rank + 2 * n) + rank * d_in)


def sscan_flops(cfg):
    """Elementwise operations of ONE layer's selective scan forward for
    one sequence, a token, channel and state index: ``dt A`` and its
    exponential, ``dt x B`` (the product ``dt x`` is a channel's, counted
    with the skip below), decay times state plus input, ``C`` times state
    and its sum: 7; and a channel and token the product ``dt x`` and the
    skip's multiply-add: 3."""
    d_in, n, _ = _mamba(cfg)
    return float(_t(cfg)) * d_in * (7 * n + 3)


def sscan_bytes(cfg, itemsize=2):
    """Bytes ONE layer's selective scan has to move forward for one
    sequence: ``x`` in and ``m`` out in the configuration's dtype, ``dt``
    in float32, ``B`` and ``C`` in the configuration's dtype."""
    d_in, n, _ = _mamba(cfg)
    return float(_t(cfg)) * (d_in * (2 * itemsize + 4) + 2 * n * itemsize)


def _keys(cfg, kind):
    """Keys a query reads on average in a layer of ``kind``."""
    t, w = _t(cfg), cfg["sliding_window"]
    if kind != "window" or w >= t:
        return (t + 1) / 2.0
    return (w * (w + 1) / 2.0 + (t - w) * w) / t


def diff_projection_flops(cfg, kind):
    """Forward operations of ONE attention layer's projections: ``q`` and
    ``o``, and ``k`` and ``v`` where the layer makes its own."""
    d = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    return 2.0 * _t(cfg) * d * (2 * d + (0 if kind == "cross" else 2 * kv))


def diff_attn_flops(cfg, kind):
    """Forward operations of ONE differential-attention layer's two maps:
    a pair's two score rows of head width and their two reads of the
    pair's value of twice that width, every pair."""
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    return 2.0 * _t(cfg) * _keys(cfg, kind) * (heads // 2) * 2 * (d + 2 * d)


def diff_attn_bytes(cfg, kind, itemsize=2):
    """Bytes ONE differential-attention layer's two flash calls have to
    move forward: the queries in, the keys once a map, the values once a
    map, the two results out."""
    d = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    return float(itemsize) * _t(cfg) * (d + kv + 2 * kv + 2 * d)


def diff_attn_flops_held(cfg):
    """``diff_attn_flops`` summed over the attention layers held."""
    return sum(layers(cfg, kind) * diff_attn_flops(cfg, kind)
               for kind in ("window", "full", "cross"))


def gmu_flops(cfg):
    """Forward operations of ONE GMU layer's two projections."""
    d_in, _, _ = _mamba(cfg)
    return 2.0 * _t(cfg) * cfg["hidden_size"] * 2 * d_in


def mlp_flops(cfg):
    """Forward operations of ONE layer's dense SwiGLU."""
    return 2.0 * _t(cfg) * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def forward_flops_per_sample(cfg):
    attention = sum(
        layers(cfg, kind) * (diff_projection_flops(cfg, kind)
                             + diff_attn_flops(cfg, kind))
        for kind in ("window", "full", "cross"))
    return (2.0 * _t(cfg) * cfg["hidden_size"] * cfg["vocab_size"]  # head
            + mamba_layers(cfg) * mamba_projection_flops(cfg)
            + attention
            + layers(cfg, "gmu") * gmu_flops(cfg)
            + layers(cfg) * mlp_flops(cfg))
