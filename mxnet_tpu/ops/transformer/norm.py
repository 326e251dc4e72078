"""The norms the families share: ``rms_norm`` (the op ``RMSNorm``, the latent's
norm inside ``LatentAttention``) and ``layer_norm`` (the op ``LayerNorm``,
``KeyIndexer``'s one key a token). Plain ``jax.numpy`` on every platform, no kernel family."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..registry import OpDef, register
from ..utils import required_shape


def rms_norm(x, gamma, eps):
    """``gamma * x / sqrt(mean(x^2) + eps)`` over the last axis: the
    statistics in float32, the normalised value cast back to ``x``'s
    dtype before the scale (the order of the published OLMoE code)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return gamma.astype(x.dtype) * normed


def layer_norm(x, gamma, beta, eps):
    """LayerNorm over the last axis: statistics float32, the normalised
    value cast to ``x``'s dtype before scale and shift (``rms_norm``'s
    order)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    normed = ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return gamma.astype(x.dtype) * normed + beta.astype(x.dtype)


def _rms_norm(attrs, ins, is_train):
    data, gamma = ins
    return [rms_norm(data, gamma, float(attrs.get("eps", 1e-5)))]


def _rms_norm_infer(attrs, in_shapes):
    data = required_shape(in_shapes[0], "RMSNorm")
    return [data, (data[-1],)], [data], []


register(
    OpDef(
        "_contrib_RMSNorm",
        _rms_norm,
        arguments=("data", "gamma"),
        defaults={"eps": 1e-5},
        infer_shape=_rms_norm_infer,
        aliases=("RMSNorm",),
        op_class="norm",
    )
)


def _layer_norm(attrs, ins, is_train):
    data, gamma, beta = ins
    return [layer_norm(data, gamma, beta, float(attrs.get("eps", 1e-5)))]


def _layer_norm_infer(attrs, in_shapes):
    data = required_shape(in_shapes[0], "LayerNorm")
    return [data, (data[-1],), (data[-1],)], [data], []


register(
    OpDef(
        "_contrib_LayerNorm",
        _layer_norm,
        arguments=("data", "gamma", "beta"),
        defaults={"eps": 1e-5},
        infer_shape=_layer_norm_infer,
        aliases=("LayerNorm",),
        op_class="norm",
    )
)
