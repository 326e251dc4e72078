"""Transformer-block operators for the Symbol API: RMSNorm, RoPE,
Attention and TopKMoE.

Beyond-reference capability (the 2017 operator set has no attention and
no sparse-expert layer): what a decoder-only LM with sparse experts
(``models/olmoe.py``) needs to be an ``mx.sym`` graph that
``Module.fit`` trains through the fused step. Each op is a thin
``OpDef`` over one function kept elsewhere: ``Attention`` over the one
attention dispatch ``ops/pallas_kernels.attention`` (flash kernel on the
TPU at T >= 128, the materialised reference elsewhere), ``TopKMoE`` over
``parallel/moe.topk_moe``. Exported as ``mx.contrib.sym`` /
``mx.contrib.nd`` functions through ``contrib.ops.CONTRIB_OP_EXPORTS``.

Layout: activations are ``[batch, time, heads * head_dim]`` between ops
(what ``FullyConnected(flatten=False)`` produces); the expert layer
takes ``[tokens, d_model]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .registry import OpDef, register


def _known(shape, what):
    if shape is None:
        raise MXNetError("%s: data shape required" % what)  # resolvable later
    return tuple(shape)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------
def rms_norm(x, gamma, eps):
    """``gamma * x / sqrt(mean(x^2) + eps)`` over the last axis: the
    statistics in float32, the normalised value cast back to ``x``'s
    dtype before the scale (the order of the published OLMoE code)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return gamma.astype(x.dtype) * normed


def _rms_norm(attrs, ins, is_train):
    data, gamma = ins
    return [rms_norm(data, gamma, float(attrs.get("eps", 1e-5)))]


def _rms_norm_infer(attrs, in_shapes):
    data = _known(in_shapes[0], "RMSNorm")
    return [data, (data[-1],)], [data], []


register(
    OpDef(
        "_contrib_RMSNorm",
        _rms_norm,
        arguments=("data", "gamma"),
        defaults={"eps": 1e-5},
        infer_shape=_rms_norm_infer,
        aliases=("RMSNorm",),
    )
)


# --------------------------------------------------------------------------
# RoPE — rotary position embedding, half-rotation convention
# --------------------------------------------------------------------------
def rope(x, num_heads, theta):
    """Rotate ``x`` [B, T, H*D] by its positions 0..T-1. The pairs are
    (i, i + D/2) within a head — the ``rotate_half`` convention of the
    published code, not the interleaved (2i, 2i+1) one. Angles, sines
    and the rotation itself are float32; the result is ``x``'s dtype."""
    b, t, hd = x.shape
    d = hd // num_heads
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[None, :, None, :]
    x4 = x.astype(jnp.float32).reshape(b, t, num_heads, d)
    x1, x2 = x4[..., : d // 2], x4[..., d // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(b, t, hd).astype(x.dtype)


def _rope(attrs, ins, is_train):
    return [rope(ins[0], int(attrs["num_heads"]),
                 float(attrs.get("theta", 10000.0)))]


def _heads_infer(what, n_in):
    def infer(attrs, in_shapes):
        data = _known(in_shapes[0], what)
        heads = int(attrs["num_heads"])
        if len(data) != 3 or heads <= 0 or data[2] % (2 * heads):
            # ValueError: a known-but-wrong shape must survive the infer
            # fixpoint loop (see SwitchMoE)
            raise ValueError(
                "%s: data must be [batch, time, num_heads * head_dim] with "
                "an even head_dim, got %s for num_heads=%d"
                % (what, data, heads))
        return [data] * n_in, [data], []

    return infer


register(
    OpDef(
        "_contrib_RoPE",
        _rope,
        arguments=("data",),
        defaults={"num_heads": 1, "theta": 10000.0},
        infer_shape=_heads_infer("RoPE", 1),
        aliases=("RoPE",),
    )
)


# --------------------------------------------------------------------------
# Attention — multi-head scaled-dot-product attention
# --------------------------------------------------------------------------
def _attention(attrs, ins, is_train):
    from .pallas_kernels import attention

    q, k, v = ins
    heads = int(attrs["num_heads"])
    b, t, hd = q.shape
    split = (b, t, heads, hd // heads)
    out = attention(q.reshape(split), k.reshape(split), v.reshape(split),
                    causal=bool(attrs.get("causal", True)))
    return [out.reshape(b, t, hd)]


register(
    OpDef(
        "_contrib_Attention",
        _attention,
        arguments=("query", "key", "value"),
        defaults={"num_heads": 1, "causal": True},
        infer_shape=_heads_infer("Attention", 3),
        aliases=("Attention",),
    )
)


# --------------------------------------------------------------------------
# TopKMoE — dropless top-k sparse-expert SwiGLU FFN
# --------------------------------------------------------------------------
def _topk_moe(attrs, ins, is_train):
    """``parallel/moe.topk_moe`` as a Symbol op. Two outputs: the routed
    FFN result and how many (token, expert) rows each expert received —
    float32 so that it can ride out of a training step beside the loss
    (behind ``BlockGrad``; it has no gradient)."""
    from ..parallel.moe import topk_moe

    data, gate_w, w_gate_up, w_down = ins
    y, counts = topk_moe(
        {"gate_w": gate_w, "w_gate_up": w_gate_up, "w_down": w_down},
        data, top_k=int(attrs["top_k"]),
        norm_topk_prob=bool(attrs.get("norm_topk_prob", False)))
    return [y, counts.astype(jnp.float32)]


def _topk_moe_infer(attrs, in_shapes):
    data = _known(in_shapes[0], "TopKMoE")
    if len(data) != 2:
        raise ValueError("TopKMoE: data must be [tokens, d_model] "
                         "(Reshape (B,T,D) inputs to (B*T, D))")
    d_model = data[1]
    num_experts = int(attrs["num_experts"])
    hidden = int(attrs["num_hidden"])
    top_k = int(attrs["top_k"])
    if hidden <= 0:
        raise ValueError("TopKMoE: num_hidden must be set (> 0)")
    if not 1 <= top_k <= num_experts:
        raise ValueError("TopKMoE: top_k must lie in 1..num_experts, got "
                         "%d of %d" % (top_k, num_experts))
    return (
        [data, (d_model, num_experts),
         (num_experts, d_model, 2 * hidden), (num_experts, hidden, d_model)],
        [data, (num_experts,)],
        [],
    )


def _topk_moe_infer_type(attrs, in_types):
    known = [t for t in in_types if t is not None]
    if not known:
        raise MXNetError("TopKMoE: cannot infer type")
    t = known[0]
    return ([t if x is None else x for x in in_types],
            [t, np.float32], [])


register(
    OpDef(
        "_contrib_TopKMoE",
        _topk_moe,
        arguments=("data", "gate_weight", "gate_up_weight", "down_weight"),
        outputs=("output", "expert_count"),
        defaults={"num_experts": 8, "num_hidden": 0, "top_k": 2,
                  "norm_topk_prob": False},
        infer_shape=_topk_moe_infer,
        infer_type=_topk_moe_infer_type,
        aliases=("TopKMoE",),
    )
)
