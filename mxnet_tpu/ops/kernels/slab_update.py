"""Fused optimizer-slab kernel (AMP update path, parallel/train_step.py).

The flat sharded update applies one elementwise optimizer step to a 1/N
contiguous slab of the flattened parameter space. Under AMP that step
is a chain of ~10 elementwise HLOs (unscale, clip, wd, state math,
finite-select, bf16 cast-out) each of which round-trips the slab
through HBM. The kernel below runs the whole chain in one VMEM pass:
each grid step streams a (block_rows, 128) tile of every operand in,
does the full update in registers, and writes new master weight, new
state, and the bf16 weight copy out.

The jnp path (`slab_update_reference`) and the kernel share
`_slab_update_math`, so kernel-vs-reference parity reduces to the
pallas_call plumbing (tiling, padding, SMEM scalars) — which is what
the interpret-mode tests pin across 1/2/4/8 simulated devices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANES, no_x64, on_tpu

SLAB_STATE_SLOTS = {"sgd": 0, "sgd_mom": 1, "adam": 2}


def _slab_update_math(kind, w, g, states, lr, inv_scale, finite, *, wd,
                      rescale_grad, clip_gradient, momentum, beta1, beta2,
                      epsilon):
    """One AMP optimizer step on a slab, mirroring optimizer_ops.py
    (`_prep_grad` + sgd/sgd_mom/adam update) with the AMP extras: grad
    unscale up front, branchless finite-select at the end, bf16 weight
    copy out. All math in f32 regardless of grad dtype."""
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


def _slab_kernel(kind, n_state, scalar_ref, w_ref, g_ref, *refs, wd,
                 rescale_grad, clip_gradient, momentum, beta1, beta2,
                 epsilon):
    state_refs = refs[:n_state]
    out_w_ref = refs[n_state]
    out_state_refs = refs[n_state + 1:2 * n_state + 1]
    out_w16_ref = refs[2 * n_state + 1]
    lr = scalar_ref[0, 0]
    inv_scale = scalar_ref[0, 1]
    finite = scalar_ref[0, 2]
    new_w, new_states, w16 = _slab_update_math(
        kind, w_ref[...], g_ref[...],
        tuple(r[...] for r in state_refs), lr, inv_scale, finite,
        wd=wd, rescale_grad=rescale_grad, clip_gradient=clip_gradient,
        momentum=momentum, beta1=beta1, beta2=beta2, epsilon=epsilon)
    out_w_ref[...] = new_w
    for r, ns in zip(out_state_refs, new_states):
        r[...] = ns
    out_w16_ref[...] = w16


def _slab_pad_2d(x, rows, block_rows):
    """(S,) -> (rows_padded, 128), zero-filled."""
    x2 = jnp.pad(x, (0, rows * LANES - x.shape[0])).reshape(
        rows, LANES)
    if rows % block_rows:
        x2 = jnp.pad(x2, ((0, block_rows - rows % block_rows), (0, 0)))
    return x2


def slab_update_reference(kind, w, g, states, lr, inv_scale, finite, *,
                          wd, rescale_grad, clip_gradient, momentum=0.0,
                          beta1=0.9, beta2=0.999, epsilon=1e-8):
    """The pure-jnp slab update (the XLA path and the kernel's oracle)."""
    new_w, new_states, w16 = _slab_update_math(
        kind, w, g, states, jnp.asarray(lr, jnp.float32),
        jnp.asarray(inv_scale, jnp.float32),
        jnp.asarray(finite, jnp.float32), wd=wd, rescale_grad=rescale_grad,
        clip_gradient=clip_gradient, momentum=momentum, beta1=beta1,
        beta2=beta2, epsilon=epsilon)
    return new_w, new_states, w16


def fused_slab_update(kind, w, g, states, lr, inv_scale, finite, *, wd,
                      rescale_grad, clip_gradient, momentum=0.0, beta1=0.9,
                      beta2=0.999, epsilon=1e-8, interpret=False):
    """AMP optimizer step over a flat slab in one Pallas VMEM pass where
    the computation is lowered for the TPU, ``slab_update_reference`` (the
    same ``_slab_update_math`` as XLA's chain) on every other platform;
    ``interpret=True`` (the kernel's tests) runs the kernel through the
    Pallas interpreter wherever the computation is lowered.

    w: (S,) f32 master shard; g: (S,) grad shard (bf16 under AMP);
    states: tuple of (S,) f32 state slabs (len per `kind`); lr /
    inv_scale / finite: traced f32 scalars (finite: 1.0 = apply,
    0.0 = skip bitwise-cleanly). Static hyperparameters are baked into
    the kernel. Returns (new_w f32, new_states tuple, w16 bf16), each
    (S,).
    """
    n_state = SLAB_STATE_SLOTS[kind]
    assert len(states) == n_state, (kind, len(states))
    hyper = dict(wd=float(wd), rescale_grad=float(rescale_grad),
                 momentum=float(momentum), beta1=float(beta1),
                 beta2=float(beta2), epsilon=float(epsilon))

    def kernel(w, g, states, lr, inv_scale, finite, interpret):
        return _slab_call(
            kind, w, g, states, lr, inv_scale, finite, interpret,
            clip_gradient=float(clip_gradient) if clip_gradient else -1.0,
            **hyper)

    def plain(w, g, states, lr, inv_scale, finite):
        return slab_update_reference(
            kind, w, g, states, lr, inv_scale, finite,
            clip_gradient=clip_gradient, **hyper)

    return on_tpu(kernel, plain, interpret, w, g, tuple(states),
                  *(jnp.asarray(x, jnp.float32)
                    for x in (lr, inv_scale, finite)))


def _slab_call(kind, w, g, states, lr, inv_scale, finite, interpret,
               **hyper):
    """The slab as (rows, 128) tiles through ``_slab_kernel``."""
    n_state = len(states)
    s = w.shape[0]
    rows = -(-s // LANES)
    block_rows = 256 if rows >= 256 else (-(-rows // 16) * 16)
    kern = functools.partial(_slab_kernel, kind, n_state, **hyper)
    # pads/stacks stay OUTSIDE the 32-bit context: under the global
    # jax_enable_x64 an outer trace caches their lowered subfunctions
    # with i64 scalar operands, and re-tracing them under no_x64
    # emits i32 signatures for the same cache key — mixed-width
    # func.call verifier errors. Only the pallas_call itself (whose
    # Mosaic grid indexing must be 32-bit) runs under no_x64.
    scalars = jnp.stack([lr, inv_scale, finite]).reshape(1, 3)
    w2 = _slab_pad_2d(w.astype(jnp.float32), rows, block_rows)
    g2 = _slab_pad_2d(g, rows, block_rows)
    st2 = [_slab_pad_2d(st.astype(jnp.float32), rows, block_rows)
           for st in states]
    rp = w2.shape[0]
    blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    with no_x64():
        outs = pl.pallas_call(
            kern,
            grid=(rp // block_rows,),
            in_specs=[pl.BlockSpec((1, 3), lambda i: (0, 0),
                                   memory_space=pltpu.SMEM),
                      blk, blk] + [blk] * n_state,
            out_specs=[blk] * (n_state + 2),
            out_shape=[jax.ShapeDtypeStruct((rp, LANES), jnp.float32)]
            * (n_state + 1)
            + [jax.ShapeDtypeStruct((rp, LANES), jnp.bfloat16)],
            interpret=interpret,
        )(scalars, w2, g2, *st2)
    new_w = outs[0].reshape(-1)[:s]
    new_states = tuple(o.reshape(-1)[:s] for o in outs[1:n_state + 1])
    w16 = outs[n_state + 1].reshape(-1)[:s]
    return new_w, new_states, w16
