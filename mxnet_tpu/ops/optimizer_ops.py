"""Fused optimizer update ops.

Parity: reference ``src/operator/tensor/optimizer_op.cc:18-102``
(sgd_update, sgd_mom_update, adam_update, rmsprop_update,
rmspropalex_update). Each is one fused XLA computation; the reference's
in-place mutation of weight/state maps to ``mutate_inputs`` write-back.

Update math matches the reference kernels in ``optimizer_op-inl.h``:
  rescaled = clip(rescale_grad * grad, clip_gradient) + wd * weight
"""
from __future__ import annotations

import jax.numpy as jnp

from .registry import OpDef, register


def _prep_grad(weight, grad, attrs):
    g = grad * _f(attrs.get("rescale_grad", 1.0))
    clip = attrs.get("clip_gradient", -1.0)
    if clip is not None and float(clip) > 0:
        g = jnp.clip(g, -float(clip), float(clip))
    return g + _f(attrs.get("wd", 0.0)) * weight



def _f(v):
    """Attr as multiplier: host floats stay floats; traced scalars pass
    through (lr/wd enter the fused ShardedTrainStep as per-call inputs)."""
    try:
        return float(v)
    except TypeError:
        return v

_COMMON = {"lr": 0.01, "wd": 0.0, "rescale_grad": 1.0, "clip_gradient": -1.0}


def _sgd_update(attrs, ins, is_train):
    weight, grad = ins
    g = _prep_grad(weight, grad, attrs)
    return [weight - _f(attrs["lr"]) * g]


register(
    OpDef(
        "sgd_update",
        _sgd_update,
        arguments=("weight", "grad"),
        defaults=dict(_COMMON),
    )
)


def _sgd_mom_update(attrs, ins, is_train):
    weight, grad, mom = ins
    g = _prep_grad(weight, grad, attrs)
    new_mom = _f(attrs.get("momentum", 0.0)) * mom - _f(attrs["lr"]) * g
    return [weight + new_mom, new_mom]


register(
    OpDef(
        "sgd_mom_update",
        _sgd_mom_update,
        arguments=("weight", "grad", "mom"),
        defaults=dict(_COMMON, momentum=0.0),
        mutate_inputs=(2,),
    )
)


def _adam_update(attrs, ins, is_train):
    weight, grad, mean, var = ins
    beta1 = float(attrs.get("beta1", 0.9))
    beta2 = float(attrs.get("beta2", 0.999))
    eps = float(attrs.get("epsilon", 1e-8))
    g = _prep_grad(weight, grad, attrs)
    new_mean = beta1 * mean + (1.0 - beta1) * g
    new_var = beta2 * var + (1.0 - beta2) * jnp.square(g)
    new_w = weight - _f(attrs["lr"]) * new_mean / (jnp.sqrt(new_var) + eps)
    return [new_w, new_mean, new_var]


register(
    OpDef(
        "adam_update",
        _adam_update,
        arguments=("weight", "grad", "mean", "var"),
        defaults=dict(_COMMON, beta1=0.9, beta2=0.999, epsilon=1e-8),
        mutate_inputs=(2, 3),
    )
)


def _rmsprop_update(attrs, ins, is_train):
    weight, grad, n = ins
    gamma1 = float(attrs.get("gamma1", 0.95))
    eps = float(attrs.get("epsilon", 1e-8))
    g = _prep_grad(weight, grad, attrs)
    new_n = (1.0 - gamma1) * jnp.square(g) + gamma1 * n
    delta = -_f(attrs["lr"]) * g / jnp.sqrt(new_n + eps)
    cw = attrs.get("clip_weights", -1.0)
    new_w = weight + delta
    if cw is not None and float(cw) > 0:
        new_w = jnp.clip(new_w, -float(cw), float(cw))
    return [new_w, new_n]


register(
    OpDef(
        "rmsprop_update",
        _rmsprop_update,
        arguments=("weight", "grad", "n"),
        defaults=dict(_COMMON, gamma1=0.95, epsilon=1e-8, clip_weights=-1.0),
        mutate_inputs=(2,),
    )
)


def _rmspropalex_update(attrs, ins, is_train):
    weight, grad, n, g_avg, delta = ins
    gamma1 = float(attrs.get("gamma1", 0.95))
    gamma2 = float(attrs.get("gamma2", 0.9))
    eps = float(attrs.get("epsilon", 1e-8))
    g = _prep_grad(weight, grad, attrs)
    new_n = (1.0 - gamma1) * jnp.square(g) + gamma1 * n
    new_g = (1.0 - gamma1) * g + gamma1 * g_avg
    new_delta = gamma2 * delta - _f(attrs["lr"]) * g / jnp.sqrt(
        new_n - jnp.square(new_g) + eps
    )
    new_w = weight + new_delta
    cw = attrs.get("clip_weights", -1.0)
    if cw is not None and float(cw) > 0:
        new_w = jnp.clip(new_w, -float(cw), float(cw))
    return [new_w, new_n, new_g, new_delta]


register(
    OpDef(
        "rmspropalex_update",
        _rmspropalex_update,
        arguments=("weight", "grad", "n", "g", "delta"),
        defaults=dict(_COMMON, gamma1=0.95, gamma2=0.9, epsilon=1e-8, clip_weights=-1.0),
        mutate_inputs=(2, 3, 4),
    )
)


# -- the AMP flat update's rule over a slab ---------------------------------
# State slabs a rule keeps beside the master (``parallel/train_step.py``).
SLAB_STATE_SLOTS = {"sgd": 0, "sgd_mom": 1, "adam": 2}


def slab_update(kind, w, g, states, lr, inv_scale, finite, *, wd,
                rescale_grad, clip_gradient, momentum=0.0, beta1=0.9,
                beta2=0.999, epsilon=1e-8):
    """One AMP optimizer step on a flat slab (a shard of the fused step's
    float32 masters), as one XLA fusion: ``_prep_grad`` and the sgd /
    sgd_mom / adam update above with the AMP extras, the gradient's
    unscale up front, the branchless finite select at the end and the
    bf16 weight copy out. All math in f32 whatever the gradient's dtype.

    w: (S,) f32 master shard; g: (S,) grad shard (bf16 under AMP);
    states: tuple of (S,) f32 state slabs (``SLAB_STATE_SLOTS[kind]`` of
    them); lr / inv_scale / finite: f32 scalars, traced or not (finite:
    1.0 = apply, 0.0 = skip, every slab bit for bit what it was).
    Returns (new_w f32, new_states tuple, w16 bf16), each (S,)."""
    assert len(states) == SLAB_STATE_SLOTS[kind], (kind, len(states))
    lr, inv_scale, finite = (jnp.asarray(x, jnp.float32)
                             for x in (lr, inv_scale, finite))
    w = w.astype(jnp.float32)
    g = g.astype(jnp.float32) * inv_scale
    if rescale_grad != 1.0:
        g = g * jnp.float32(rescale_grad)
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -jnp.float32(clip_gradient),
                     jnp.float32(clip_gradient))
    if wd != 0.0:
        g = g + jnp.float32(wd) * w
    if kind == "sgd":
        new_w = w - lr * g
        new_states = ()
    elif kind == "sgd_mom":
        mom = states[0].astype(jnp.float32)
        new_mom = jnp.float32(momentum) * mom - lr * g
        new_w = w + new_mom
        new_states = (new_mom,)
    elif kind == "adam":
        mean = states[0].astype(jnp.float32)
        var = states[1].astype(jnp.float32)
        new_mean = beta1 * mean + (1.0 - beta1) * g
        new_var = beta2 * var + (1.0 - beta2) * jnp.square(g)
        new_w = w - lr * new_mean / (jnp.sqrt(new_var) + epsilon)
        new_states = (new_mean, new_var)
    else:
        raise ValueError("unknown slab kind %r" % (kind,))
    keep = finite > jnp.float32(0.5)
    new_w = jnp.where(keep, new_w, w)
    new_states = tuple(jnp.where(keep, ns, os_.astype(jnp.float32))
                       for ns, os_ in zip(new_states, states))
    return new_w, new_states, new_w.astype(jnp.bfloat16)
